"""Unified kernel dispatch: backend, padding, block sizing, micro-autotune.

Every kernel family (spar_cost, gw_cost, sinkhorn, flash_attention, ssd)
routes its backend / interpret / padding / block-size decisions through
this module instead of carrying its own copy. Two rules it enforces:

1. **No import-time backend freezing.** ``interpret_mode()`` resolves the
   Pallas interpret flag *at call time*, so ``jax.config`` updates or
   distributed init that run after the module import are respected
   (the old per-``ops.py`` ``_INTERPRET = jax.default_backend() != "tpu"``
   globals evaluated before any of that could run).
2. **One knob surface.** Block sizes resolve as
   explicit argument > ``REPRO_BLOCK_<FAMILY>`` env var > autotune cache >
   registry default, and memory budgets come from one place, so
   benchmarks and production code can tune without touching kernel code.

Caveat: inside a ``jax.jit``'d solver, "call time" means *trace time* —
an executable cached for a given shape/static-arg key bakes in the env
values seen at its first trace. Changing ``REPRO_*`` knobs mid-process
only affects new traces; clear the jit cache (or use fresh shapes) to
re-resolve.

See DESIGN.md §2 for the architecture discussion.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp

from repro.obs.registry import registry as _obs_registry

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
# Memory budgets (env-overridable)
# ---------------------------------------------------------------------------

def _env_bytes(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if not raw:
        return default
    return int(float(raw))


def vmem_budget() -> int:
    """On-chip budget for VMEM-resident operands (sinkhorn's kernel K)."""
    return _env_bytes("REPRO_VMEM_BUDGET", 8 * 2**20)


def materialize_budget() -> int:
    """HBM budget for materializing the (s, s) spar_cost loss matrix."""
    return _env_bytes("REPRO_SPAR_MATERIALIZE_BUDGET", 512 * 2**20)


# ---------------------------------------------------------------------------
# Kernel family registry
# ---------------------------------------------------------------------------

@dataclass
class KernelFamily:
    name: str
    default_block: int
    description: str = ""


_REGISTRY: dict[str, KernelFamily] = {}


def register(name: str, default_block: int, description: str = "") -> KernelFamily:
    """Register (or re-register, idempotently) a kernel family."""
    fam = KernelFamily(name, default_block, description)
    _REGISTRY[name] = fam
    return fam


def registry() -> dict[str, KernelFamily]:
    return dict(_REGISTRY)


def block_size(family: str, override: Optional[int] = None,
               cap: Optional[int] = None) -> int:
    """Resolve the block size for a kernel family.

    Priority: ``override`` arg > ``REPRO_BLOCK_<FAMILY>`` env > autotune
    cache (populated by :func:`autotune`) > registry default. ``cap``
    clamps from above (e.g. to the problem size) while keeping ≥ 1.
    """
    bs, source = override, "override"
    if bs is None:
        env = os.environ.get(f"REPRO_BLOCK_{family.upper()}")
        if env:
            bs, source = int(env), "env"
    if bs is None:
        bs, source = _AUTOTUNE_CACHE.get(family), "autotune"
    if bs is None:
        fam = _REGISTRY.get(family)
        bs = fam.default_block if fam is not None else 128
        source = "default"
    # per-family resolution counts: a production trace where "default"
    # dominates a tuned family means the autotune cache never warmed.
    # NB: under jit this counts *traces*, not executions (see module
    # docstring caveat) — executable reuse never re-resolves.
    _obs_registry().counter(
        "repro_kernel_block_resolutions_total",
        "block_size() resolutions by family and winning source",
        family=family, source=source).inc()
    if cap is not None:
        bs = min(bs, cap)
    return max(int(bs), 1)


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


# ---------------------------------------------------------------------------
# Micro-autotune
# ---------------------------------------------------------------------------

_AUTOTUNE_CACHE: dict[str, int] = {}
_AUTOTUNE_RECORDS: list[dict] = []


def autotune(family: str, candidates: Iterable[int],
             bench_fn: Callable[[int], object], reps: int = 3) -> int:
    """Time ``bench_fn(block)`` over candidate block sizes; cache the best.

    The winner feeds subsequent :func:`block_size` resolutions for
    ``family`` (below any explicit/env override) and is appended to the
    in-process record list that ``benchmarks/roofline.py`` reports.
    Candidates that raise are skipped (e.g. blocks over the VMEM budget);
    when every candidate raises, so does this, with the last error chained.
    The timings are host wall clock: they rank blocks, and are no
    measure of a kernel's device time or utilization.
    """
    timings: dict[int, float] = {}
    last_error: Optional[Exception] = None
    for cand in candidates:
        try:
            jax.block_until_ready(bench_fn(cand))        # compile + warm
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(bench_fn(cand))
            timings[int(cand)] = (time.perf_counter() - t0) / reps
        except Exception as e:  # noqa: BLE001 — invalid candidate, sweep on
            last_error = e
    if not timings:
        raise RuntimeError(
            f"autotune({family!r}): every candidate block failed") \
            from last_error
    best = min(timings, key=timings.get)
    best_s = timings[best]
    _AUTOTUNE_CACHE[family] = best
    record = {
        "family": family,
        "backend": backend(),
        "best_block": best,
        "timings_s": {str(k): v for k, v in timings.items()},
    }
    reg = _obs_registry()
    reg.gauge("repro_autotune_best_block", "autotune-selected block size",
              family=family, backend=backend()).set(best)
    reg.gauge("repro_autotune_best_time_seconds",
              "best per-call time of the autotune winner",
              family=family, backend=backend()).set(best_s)
    _AUTOTUNE_RECORDS.append(record)
    return best


def autotune_records() -> list[dict]:
    return list(_AUTOTUNE_RECORDS)


def autotune_artifact_dir() -> Path:
    return Path(__file__).resolve().parents[3] / "artifacts" / "autotune"


def dump_autotune_records(path: Optional[os.PathLike] = None) -> Optional[Path]:
    """Persist this process's autotune records for roofline reporting."""
    if not _AUTOTUNE_RECORDS:
        return None
    if path is None:
        path = autotune_artifact_dir() / f"{backend()}.json"
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(_AUTOTUNE_RECORDS, f, indent=1)
    return path


def clear_autotune_cache() -> None:
    _AUTOTUNE_CACHE.clear()
    _AUTOTUNE_RECORDS.clear()
