"""Pure-jnp oracles for the COO spar_cost family.

``spar_cost_ref`` is the paper-faithful row-chunked ``lax.map`` assembly
(the pre-kernel hot path, kept as the correctness oracle and the CPU
fallback for supports too large to materialize). ``materialize_loss``
hoists the iteration-invariant loss matrix for the materialized fast mode.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core import ground_cost as gc


def _chunked(rows, cols, chunk: int):
    s = rows.shape[0]
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    rows_p = jnp.pad(rows, (0, pad))
    cols_p = jnp.pad(cols, (0, pad))
    return (rows_p.reshape(n_chunks, chunk), cols_p.reshape(n_chunks, chunk))


def spar_cost_ref(Cx, Cy, rows, cols, tvals, loss: str, chunk: int = 1024):
    """C̃(T̃)_k = Σ_l L(Cx[r_k, r_l], Cy[c_k, c_l]) T̃_l for k ∈ [s].  O(s²).

    Row-chunked so the gathered (chunk, s) blocks stay cache/VMEM-sized.
    """
    L = gc.get_loss(loss)
    s = rows.shape[0]

    def one(args):
        rk, ck = args                      # (chunk,)
        Gx = Cx[rk][:, rows]               # (chunk, s)
        Gy = Cy[ck][:, cols]               # (chunk, s)
        # HIGHEST: the oracle's matvec must not drop to bf16 passes on TPU
        return jnp.dot(L(Gx, Gy), tvals,   # (chunk,)
                       precision=lax.Precision.HIGHEST)

    out = lax.map(one, _chunked(rows, cols, chunk))
    return out.reshape(-1)[:s]


def materialize_loss(Cx, Cy, rows, cols, loss: str, chunk: int = None):
    """Lmat[k, l] = L(Cx[r_k, r_l], Cy[c_k, c_l]) — (s, s) float32.

    Iteration-invariant (the support is fixed after sampling), so the
    materialized mode computes it once and amortizes it over every outer
    iteration. Default is one vectorized gather — ~3× faster than
    chunking but with a ~3·s² transient (Gx, Gy, result), so callers
    must check that against their budget (ops.make_spar_cost_fn does);
    pass ``chunk`` to bound the transient to O(chunk·s) instead.
    """
    L = gc.get_loss(loss)
    if chunk is None:
        return L(Cx[rows][:, rows], Cy[cols][:, cols]).astype(jnp.float32)
    s = rows.shape[0]

    def one(args):
        rk, ck = args
        Gx = Cx[rk][:, rows]
        Gy = Cy[ck][:, cols]
        return L(Gx, Gy).astype(jnp.float32)

    out = lax.map(one, _chunked(rows, cols, chunk))
    return out.reshape(-1, s)[:s]
