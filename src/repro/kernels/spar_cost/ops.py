"""Public wrappers, impl dispatch and sizes for the COO spar_cost kernels.

Three interchangeable implementations of the affine contract
``fn(t, off) = L-matvec(t) + off`` (see spar_cost.py):

- ``"jnp"``          — row-chunked ``lax.map`` oracle (ref.py). Gathers the
                       (chunk, s) support blocks from HBM every call.
- ``"pallas"``       — gather-fused Pallas kernel; O(s·(m+n)) resident
                       sublane-dense transposed row panels, per-tile
                       gathers are VMEM tile loads.
- ``"materialized"`` — iteration-invariant loss matrix hoisted once
                       (O(s²) HBM, budget-gated); every call is a single
                       fused matvec + epilogue with zero gathers.

``make_spar_cost_fn`` hoists the per-support setup (padding, panel/loss
materialization) out of the outer PGA loop and returns the closure the
solvers scan with; ``"auto"`` picks materialized when the budget gate
allows, else the kernel path on TPU or the jnp oracle elsewhere.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.spar_cost.ref import materialize_loss, spar_cost_ref
from repro.kernels.spar_cost.spar_cost import (
    K_BLOCK,
    L_BLOCK,
    row_panels,
    spar_cost_pallas,
    spar_matvec_pallas,
)

# The family's sizes. The fused kernel's k-block is ``K_BLOCK`` and its
# l-block ``L_BLOCK`` (spar_cost.py); the materialized matvec tiles its
# (s, s) matrix in square blocks of ``MATVEC_BLOCK``, capped at s.
MATVEC_BLOCK = 256
# HBM the resident (s, s) float32 loss matrix may take: "auto"
# materializes iff 4·s² fits (s ≤ 11,585)
MATERIALIZE_BYTES = 512 * 2**20


def resolve_impl(impl: str, s: int) -> str:
    """Resolve ``"auto"`` to a concrete impl for a support of size s."""
    if impl != "auto":
        return impl
    if s * s * 4 <= MATERIALIZE_BYTES:
        return "materialized"
    return "pallas" if dispatch.backend() == "tpu" else "jnp"


def _fused_setup(Cx, Cy, rows, cols, block: Optional[int] = None):
    """The support padded for the fused kernel's l-walk (to ``L_BLOCK``),
    and its sublane-dense row panels over the support padded to the
    k-block (``block`` where given, else ``K_BLOCK``)."""
    bk = block or K_BLOCK
    rows, cols = rows.astype(jnp.int32), cols.astype(jnp.int32)
    XT = row_panels(Cx, dispatch.pad_dim(rows, bk), bk)
    YT = row_panels(Cy, dispatch.pad_dim(cols, bk), bk)
    return (dispatch.pad_dim(rows, L_BLOCK), dispatch.pad_dim(cols, L_BLOCK),
            XT, YT)


def _vec(x, s_p: int):
    """Broadcast a scalar / (s,) vector to a zero-padded (s_p,) float32."""
    x = jnp.broadcast_to(jnp.asarray(x, jnp.float32),
                         (s_p,) if jnp.ndim(x) == 0 else jnp.shape(x))
    return dispatch.pad_dim(x, s_p) if x.shape[0] != s_p else x


def _fused_call(setup, t, off, loss: str, interpret: bool):
    """L-matvec(t) + off on the support of ``_fused_setup``, (s_k,)."""
    rows_l, cols_l, XT, YT = setup
    s_k = XT.shape[0] * XT.shape[2] * XT.shape[3]
    return spar_cost_pallas(XT, YT, rows_l, cols_l, _vec(t, rows_l.shape[0]),
                            _vec(off, s_k), loss=loss, interpret=interpret)


def spar_cost_fused(Cx, Cy, rows, cols, t, off=0.0, loss: str = "l2",
                    block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """One-shot gather-fused cost: L @ t + off on the COO support, (s,).
    ``block`` sets the k-block (default: ``K_BLOCK``)."""
    setup = _fused_setup(Cx, Cy, rows, cols, block)
    out = _fused_call(setup, t, off, loss, dispatch.interpret_mode(interpret))
    return out[:rows.shape[0]]


def spar_matvec(Lmat, t, off=0.0, block: Optional[int] = None,
                interpret: Optional[bool] = None):
    """One-shot materialized-support matvec: Lmat @ t + off, (s,).
    ``block`` sets the square block (default: ``MATVEC_BLOCK``), capped
    at s."""
    s = Lmat.shape[0]
    b = min(block or MATVEC_BLOCK, s)
    Lp, _ = dispatch.pad_to_multiple(Lmat, (b, b))
    s_p = Lp.shape[0]
    out = spar_matvec_pallas(Lp, _vec(t, s_p), _vec(off, s_p), bk=b, bl=b,
                             interpret=dispatch.interpret_mode(interpret))
    return out[:s]


def make_spar_cost_fn(Cx, Cy, rows, cols, loss: str, impl: str = "auto",
                      chunk: int = 1024, interpret: Optional[bool] = None
                      ) -> Callable[..., jnp.ndarray]:
    """Build ``fn(t, off=0.0) -> (s,) f32`` computing L-matvec(t) + off.

    Per-support setup (impl resolution, padding, panel gathers or loss
    materialization) happens here, once; inside a jit'd solver XLA hoists
    it out of the outer ``lax.scan``, so every iteration pays only the
    fused matvec (materialized) or tiled gather+loss+matvec (pallas).
    The setup and every call are traced under the ``gw.cost`` named
    scope, whichever implementation runs, so their ops carry it in the
    HLO ``op_name`` metadata.
    """
    with jax.named_scope("gw.cost"):
        fn = _cost_fn(Cx, Cy, rows, cols, loss, resolve_impl(
            impl, rows.shape[0]), chunk, interpret)

    def scoped(t, off=0.0):
        with jax.named_scope("gw.cost"):
            return fn(t, off)
    return scoped


def _cost_fn(Cx, Cy, rows, cols, loss: str, impl: str, chunk: int,
             interpret: Optional[bool]):
    """The unscoped closure of one resolved ``impl``."""
    s = rows.shape[0]
    if impl == "jnp":
        def fn(t, off=0.0):
            return spar_cost_ref(Cx, Cy, rows, cols, t, loss, chunk) + off
        return fn

    if impl == "pallas":
        setup = _fused_setup(Cx, Cy, rows, cols)
        itp = dispatch.interpret_mode(interpret)

        def fn(t, off=0.0):
            return _fused_call(setup, t, off, loss, itp)[:s]
        return fn

    if impl == "materialized":
        # the gate bounds the resident s² matrix; the one-shot vectorized
        # gather additionally needs a ~3·s² transient (Gx, Gy, result) —
        # fall back to the O(chunk·s)-transient chunked build past that
        direct_ok = 3 * s * s * 4 <= MATERIALIZE_BYTES
        Lmat = materialize_loss(Cx, Cy, rows, cols, loss,
                                None if direct_ok else chunk)
        if dispatch.interpret_mode(interpret):
            # No Mosaic on this backend: the affine form is a single XLA
            # matvec that fuses fine on its own; interpret-mode Pallas
            # would only add per-tile overhead (parity tests exercise the
            # kernel explicitly via spar_matvec(interpret=True)).
            def fn(t, off=0.0):
                return Lmat @ t.astype(jnp.float32) + off
            return fn
        b = min(MATVEC_BLOCK, s)
        Lp, _ = dispatch.pad_to_multiple(Lmat, (b, b))
        s_p = Lp.shape[0]

        def fn(t, off=0.0):
            out = spar_matvec_pallas(Lp, _vec(t, s_p), _vec(off, s_p),
                                     bk=b, bl=b, interpret=False)
            return out[:s]
        return fn

    raise ValueError(f"unknown spar_cost impl: {impl!r}")
