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

- ``spar_cost_pallas`` — gather-fused. Each k-block of bk support entries
  keeps its transposed row panels XT = Cx[rows].T, YT = Cy[cols].T resident
  in VMEM, laid out sublane-dense as (m, 8, bk/8) and (n, 8, bk/8); the
  l-block's rows/cols/t ride in as SMEM blocks, and the kernel walks them
  one scalar at a time, loading the panel tile XT[rows[l]] =
  Cx[rows[k-block], rows[l]] (whole (8, bk/8) vregs, indexed on the
  leading untiled dimension). So the (s, s) support blocks never touch
  HBM, and every gather is a tile load that Mosaic lowers (lane gathers
  ``x[:, idx]`` and vector loads from SMEM do not). bk = ``K_BLOCK``.
  Memory high-water: O(s·(m+n)) for the panels.
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


# the gather-fused kernel's k-block: 8 · 128, the least that fills a whole
# (8, 128) float32 tile a panel row. Its double-buffered panels fit the
# VMEM budget for m + n ≤ 11,774 (every bucket through 4096)
K_BLOCK = 1024
L_BLOCK = 256
_VMEM_BUDGET = 96 * 2**20            # what the panels may claim of VMEM
_VMEM_HEADROOM = 4 * 2**20           # Mosaic's own scratch


def _vmem_limit(m: int, n: int, bk: int) -> int:
    """Scoped VMEM of one grid step: the two panels' (m | n, 8, bk/8)
    k-blocks and the off and out blocks, each double-buffered, plus
    headroom. Mosaic's default scoped limit holds none of the served
    buckets' panels, so the kernel states it."""
    return 2 * 4 * bk * (m + n + 2) + _VMEM_HEADROOM


def row_panels(C, idx, bk: int):
    """Sublane-dense transposed row panels of C at the support rows idx:
    (s_k/bk, C.shape[0], 8, bk/8) with P[kb, i, a, b] = C[idx[k], i] at
    k = kb·bk + a·bk/8 + b. bk must be a multiple of 8, and len(idx) a
    multiple of bk."""
    if bk % 8:
        raise ValueError(f"k-block {bk} is not a multiple of 8")
    s_k, m = idx.shape[0], C.shape[0]
    return C[idx].reshape(s_k // bk, 8, bk // 8, m).transpose(0, 3, 1, 2)


def _fused_kernel(rows_ref, cols_ref, t_ref, xt_ref, yt_ref, off_ref, o_ref,
                  *, loss: str, bl: int, n_l: int, unroll: int):
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body(j, accs):
        # unrolled by hand (Mosaic's fori_loop takes no partial unroll),
        # one (8, bk/8) partial sum per unrolled slot: independent add
        # chains, each bl / unroll long
        out = []
        for u, acc in enumerate(accs):
            l = j * unroll + u
            gx = xt_ref[rows_ref[0, l]].astype(jnp.float32)
            gy = yt_ref[cols_ref[0, l]].astype(jnp.float32)
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


@functools.partial(jax.jit, static_argnames=("loss", "bl", "interpret"))
def spar_cost_pallas(XT, YT, rows, cols, t, off, loss: str = "l2",
                     bl: int = L_BLOCK, interpret: bool = True):
    """Gather-fused COO cost: out[k] = Σ_l L(Cx[r_k, r_l], Cy[c_k, c_l]) t_l
    + off[k].

    XT = ``row_panels(Cx, rows_k, bk)``: (s_k/bk, m, 8, bk/8), YT likewise
    from Cy and the columns (gathered once per support, outside), over the
    support padded to s_k; off: (s_k,). rows/cols: (s_l,) int32 and t:
    (s_l,), the same support padded to s_l, a multiple of bl (ops.py pads;
    a padded l has t = 0 so it contributes nothing, and out rows ≥ s are
    sliced away). Compiled for TPU, bk must be a multiple of 1024 whose
    panels fit the VMEM budget (ValueError otherwise).
    Returns (s_k,) float32.
    """
    n_k, m, _, lanes = XT.shape
    n = YT.shape[1]
    vmem_limit = _vmem_limit(m, n, 8 * lanes)
    if not interpret and (lanes % 128 or vmem_limit > _VMEM_BUDGET):
        raise ValueError(f"k-block {8 * lanes} of ({m}, {n})-row panels: "
                         f"the chip takes a multiple of 1024 whose panels "
                         f"fit {_VMEM_BUDGET} bytes of VMEM")
    s_l = rows.shape[0]
    grid = (n_k, s_l // bl)
    def blocks(v):
        # (s_l // bl, 1, bl): an SMEM block whose last two dims are the
        # array's own, which Mosaic accepts for any bl (a 1-D SMEM block
        # must match XLA's 1024-element tiling)
        return v.reshape(grid[1], 1, bl)

    smem = pl.BlockSpec((None, 1, bl), lambda k, l: (l, 0, 0),
                        memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((None, 8, lanes), lambda k, l: (k, 0, 0))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, loss=loss, bl=bl, n_l=grid[1],
                          unroll=math.gcd(bl, 8)),
        grid=grid,
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((None, m, 8, lanes), lambda k, l: (k, 0, 0, 0)),
            pl.BlockSpec((None, n, 8, lanes), lambda k, l: (k, 0, 0, 0)),
            tile,
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n_k, 8, lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="spar_cost_pallas",
    )(blocks(rows.astype(jnp.int32)), blocks(cols.astype(jnp.int32)),
      blocks(t.astype(jnp.float32)), XT, YT,
      off.reshape(n_k, 8, lanes))
    return out.reshape(-1)


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
