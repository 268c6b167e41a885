"""Kernel backend resolution and padding helpers.

Every kernel family (spar_cost, gw_cost, sinkhorn, flash_attention, ssd)
asks this module two things: which backend is active and whether its
Pallas kernels run in interpret mode, both resolved *at call time* so
``jax.config`` updates or distributed init after the import are
respected. It also holds the padding helpers the families share. Block
sizes and memory gates are not decided here: each family states its own
as constants in its ``ops.py``, beside the kernel.

See DESIGN.md §2 for the architecture discussion.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

_TRUTHY = {"1", "true", "yes", "on"}
_FALSY = {"0", "false", "no", "off"}


# ---------------------------------------------------------------------------
# Backend / interpret resolution (call time, never import time)
# ---------------------------------------------------------------------------

def backend() -> str:
    """The active JAX backend, resolved now (not at import)."""
    return jax.default_backend()


def interpret_mode(override: Optional[bool] = None) -> bool:
    """Whether Pallas kernels should run in interpret mode.

    Priority: explicit ``override`` > ``REPRO_PALLAS_INTERPRET`` env
    ("1"/"0"/"auto") > auto (interpret everywhere except TPU, where the
    Mosaic path compiles).
    """
    if override is not None:
        return override
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "auto").strip().lower()
    if env in _TRUTHY:
        return True
    if env in _FALSY:
        return False
    return backend() != "tpu"


# ---------------------------------------------------------------------------
# Padding helpers
# ---------------------------------------------------------------------------

def pad_to_multiple(x, mults):
    """Zero-pad each dim of ``x`` up to a multiple of ``mults[i]``.

    Returns ``(padded, original_shape)``; no-op (no copy) when already
    aligned. Slice back with :func:`unpad`.
    """
    pads = [(0, (-x.shape[i]) % mults[i]) for i in range(x.ndim)]
    if any(p for _, p in pads):
        return jnp.pad(x, pads), x.shape
    return x, x.shape


def pad_dim(x, mult: int, axis: int = 0, value=0):
    """Pad one axis of ``x`` up to a multiple of ``mult`` with ``value``."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def unpad(x, shape):
    """Slice ``x`` back to ``shape`` (inverse of :func:`pad_to_multiple`)."""
    if tuple(x.shape) == tuple(shape):
        return x
    return x[tuple(slice(0, d) for d in shape)]
