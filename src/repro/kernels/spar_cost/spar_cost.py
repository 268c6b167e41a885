"""Pallas TPU kernels: fused COO spar_cost assembly with affine epilogue.

The paper's O(s²) hotspot on the COO support is

    C̃(T̃)_k = Σ_l L(Cx[r_k, r_l], Cy[c_k, c_l]) T̃_l,      k ∈ [s]

and the outer PGA step only ever consumes the *log-kernel*
logK = -C/ε + log w (+ log T̃ + linear terms). Both kernels below therefore
compute the affine form

    out = L-matvec(t) + off

with fp32 accumulation: callers pre-scale ``t`` by -α/ε and fold
log w / log T̃ / the FGW linear term into ``off``, so one (s,) vector (the
log-kernel itself) is the only thing written back to HBM per outer
iteration — no C, no K, no separate logK intermediates.

Two entry points (see DESIGN.md §3):

- ``spar_cost_pallas`` — gather-fused. Each k-block keeps the transposed
  row panels XT = Cx[rows].T, YT = Cy[cols].T resident in VMEM as (m, bk)
  and (n, bk) blocks; the l-block's rows/cols/t ride in as SMEM blocks, and
  the kernel walks them one scalar at a time, reading the panel row
  XT[rows[l], :] = Cx[rows[k-block], rows[l]] with a dynamic sublane
  offset. So the (s, s) support blocks never touch HBM, and every gather
  is a row load that Mosaic lowers (lane gathers ``x[:, idx]`` and vector
  loads from SMEM do not). Memory high-water: O(s·(m+n)) for the panels.
- ``spar_matvec_pallas`` — materialized-support fast mode. The loss matrix
  Lmat[k, l] = L(Gx, Gy) is **constant across all outer iterations**
  (rows/cols are fixed after sampling), so when the HBM budget allows it
  is hoisted once and every iteration collapses to this fused
  matvec + epilogue with zero gathers.

Each ``pallas_call`` is named after its entry point (``name=``): that is
the name its custom call carries in the compiled HLO and in profiler
captures, whatever the Python functions around it are called.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _loss_tile(loss: str, a, b):
    if loss == "l1":
        return jnp.abs(a - b)
    if loss == "l2":
        d = a - b
        return d * d
    if loss == "kl":
        eps = 1e-10
        return a * (jnp.log(jnp.maximum(a, eps)) -
                    jnp.log(jnp.maximum(b, eps))) - a + b
    raise ValueError(loss)


def _fused_kernel(rows_ref, cols_ref, t_ref, xt_ref, yt_ref, off_ref, o_ref,
                  *, loss: str, bl: int, n_l: int, unroll: int):
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body(j, accs):
        # unrolled by hand (Mosaic's fori_loop takes no partial unroll),
        # one (1, bk) partial sum per unrolled slot: independent add
        # chains, each bl / unroll long
        out = []
        for u, acc in enumerate(accs):
            l = j * unroll + u
            gx = xt_ref[pl.ds(rows_ref[0, l], 1), :].astype(jnp.float32)
            gy = yt_ref[pl.ds(cols_ref[0, l], 1), :].astype(jnp.float32)
            out.append(acc + _loss_tile(loss, gx, gy) * t_ref[0, l])
        return tuple(out)

    zero = jnp.zeros(o_ref.shape, jnp.float32)
    accs = jax.lax.fori_loop(0, bl // unroll, body, (zero,) * unroll)
    while len(accs) > 1:                                 # pairwise sum
        accs = tuple(accs[i] + accs[i + 1] if i + 1 < len(accs) else accs[i]
                     for i in range(0, len(accs), 2))
    o_ref[...] += accs[0]

    @pl.when(li == n_l - 1)
    def _epilogue():
        o_ref[...] += off_ref[...].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("loss", "bk", "bl", "interpret"))
def spar_cost_pallas(XT, YT, rows, cols, t, off, loss: str = "l2",
                     bk: int = 256, bl: int = 256, interpret: bool = True):
    """Gather-fused COO cost: out[k] = Σ_l L(XT[rows_l, k], YT[cols_l, k]) t_l
    + off[k].

    XT: (m, s_p) = Cx[rows].T, YT: (n, s_p) = Cy[cols].T transposed row
    panels (gathered once per support, outside); rows/cols: (s_p,) int32;
    t, off: (s_p,). s_p must be a multiple of bk and bl (ops.py pads;
    padded tail has t = 0 so it contributes nothing, and out rows ≥ s are
    sliced away). Compiled for TPU, bk must be a multiple of 128 or s_p.
    Returns (s_p,) float32.
    """
    m, s_p = XT.shape
    n = YT.shape[0]
    grid = (s_p // bk, s_p // bl)
    def blocks(v):
        # (s_p // bl, 1, bl): an SMEM block whose last two dims are the
        # array's own, which Mosaic accepts for any bl (a 1-D SMEM block
        # must match XLA's 1024-element tiling)
        return v.reshape(grid[1], 1, bl)

    smem = pl.BlockSpec((None, 1, bl), lambda k, l: (l, 0, 0),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, loss=loss, bl=bl, n_l=grid[1],
                          unroll=math.gcd(bl, 8)),
        grid=grid,
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((m, bk), lambda k, l: (0, k)),
            pl.BlockSpec((n, bk), lambda k, l: (0, k)),
            pl.BlockSpec((1, bk), lambda k, l: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, bk), lambda k, l: (0, k)),
        out_shape=jax.ShapeDtypeStruct((1, s_p), jnp.float32),
        interpret=interpret,
        name="spar_cost_pallas",
    )(blocks(rows.astype(jnp.int32)), blocks(cols.astype(jnp.int32)),
      blocks(t.astype(jnp.float32)), XT, YT, off.reshape(1, s_p))
    return out[0]


def _matvec_kernel(l_ref, t_ref, off_ref, o_ref, *, n_l: int):
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    lmat = l_ref[...].astype(jnp.float32)                # (bk, bl)
    t = t_ref[...].astype(jnp.float32)[0]                # (bl,)
    o_ref[...] += jax.lax.dot_general(
        lmat, t, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[None, :]

    @pl.when(li == n_l - 1)
    def _epilogue():
        o_ref[...] += off_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bk", "bl", "interpret"))
def spar_matvec_pallas(Lmat, t, off, bk: int = 256, bl: int = 256,
                       interpret: bool = True):
    """Materialized-support fast mode: out = Lmat @ t + off, tiled fp32.

    Lmat: (s_p, s_p) precomputed loss values; t, off: (s_p,). Returns
    (s_p,) float32. s_p must be a multiple of bk and bl.
    """
    s_p = Lmat.shape[0]
    grid = (s_p // bk, s_p // bl)
    out = pl.pallas_call(
        functools.partial(_matvec_kernel, n_l=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bl), lambda k, l: (k, l)),
            pl.BlockSpec((1, bl), lambda k, l: (0, l)),
            pl.BlockSpec((1, bk), lambda k, l: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, bk), lambda k, l: (0, k)),
        out_shape=jax.ShapeDtypeStruct((1, s_p), jnp.float32),
        interpret=interpret,
        name="spar_matvec_pallas",
    )(Lmat, t.reshape(1, s_p), off.reshape(1, s_p))
    return out[0]
