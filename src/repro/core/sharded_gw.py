"""Distributed Grid-SPAR-GW — the paper's technique sharded over the mesh.

The O(n²) phase (relation sub-block gathers) and the O(s²) phase (cost
assembly + Sinkhorn on the s_r × s_c grid block) shard as:

  CxR (s_r, s_r): rows over 'data'            P('data', None)
  CyC (s_c, s_c): rows over 'model'           P('model', None)
  T   (s_r, s_c): 2-D block-sharded           P('data', 'model')

Cost assembly (decomposable L) is a distributed matmul chain; Sinkhorn
matvecs psum over the opposing axis. Everything is ``shard_map`` with
explicit collectives, so the collective schedule is visible in the
compiled program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import ground_cost as gc


def _local_grid_cost_decomposable(dec, CxR_l, CyC_l, T_full_rows, T_full_cols,
                                  mu, nu):
    """Per-device cost block. CxR_l: (s_r/dp, s_r); CyC_l: (s_c/mp, s_c);
    T_full_rows: (s_r, s_c/mp) [gathered over data]; mu: (s_r,), nu: (s_c,).
    Returns local block (s_r/dp, s_c/mp)."""
    t1 = (dec.f1(CxR_l) @ mu)[:, None]                    # (s_r/dp, 1)
    t2 = (dec.f2(CyC_l) @ nu)[None, :]                    # (1, s_c/mp) local rows?
    # h-term: h1(CxR_l) @ T @ h2(CyC)^T, assembled from gathered pieces
    ht = dec.h1(CxR_l) @ T_full_rows                      # (s_r/dp, s_c/mp)?? see caller
    return t1, t2, ht


def make_sharded_grid_gw(mesh: Mesh, s_r: int, s_c: int, loss: str = "l2",
                         epsilon: float = 1e-2, outer_iters: int = 10,
                         inner_iters: int = 30, comm_dtype=None):
    """Returns a jit-able fn(CxR, CyC, aR, bC, w) -> (gw_value, T_block).

    Decomposable-loss path (the ``l2`` production configuration).

    ``comm_dtype=jnp.bfloat16`` casts large gathers to bf16 on the wire.
    (A psum-of-partials h-term restructure was tried and is *invalid* here:
    both contraction and output dims of each hop live on the same mesh
    axis, so partials from different devices cover different output blocks
    — caught by the 4-device equivalence test.)
    """
    dec = gc.get_decomposition(loss)
    assert dec is not None, "sharded path implements decomposable costs"
    dp, mp = mesh.shape["data"], mesh.shape["model"]

    def _gather(x, axis_name, axis):
        """bf16-on-the-wire gather: the result STAYS in comm_dtype and is
        consumed by a mixed-precision dot (f32 accumulate) — converting
        back immediately would let XLA sink the convert before the gather
        and ship f32 anyway (observed on the CPU backend)."""
        if comm_dtype is not None:
            return lax.all_gather(x.astype(comm_dtype), axis_name, axis=axis,
                                  tiled=True)
        return lax.all_gather(x, axis_name, axis=axis, tiled=True)

    def _mmt(a, b_t):
        """a @ b_t.T with f32 accumulation regardless of operand dtype."""
        return jax.lax.dot_general(a, b_t, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def solver(CxR_l, CyC_l, aR_l, bC_l, w_l):
        # locals: CxR_l (s_r/dp, s_r), CyC_l (s_c/mp, s_c),
        # aR_l (s_r/dp,), bC_l (s_c/mp,), w_l (s_r/dp, s_c/mp)
        f1x = dec.f1(CxR_l)                                # (s_r/dp, s_r)
        f2y = dec.f2(CyC_l)                                # (s_c/mp, s_c)
        h1x = dec.h1(CxR_l)
        h2y = dec.h2(CyC_l)
        la_l = jnp.log(jnp.maximum(aR_l, 1e-38))
        lb_l = jnp.log(jnp.maximum(bC_l, 1e-38))

        def cost(T_l):
            # marginals (global): psum partial sums over the opposing axis
            mu_l = jnp.sum(T_l, axis=1)                    # (s_r/dp,)
            mu_l = lax.psum(mu_l, "model")
            nu_l = jnp.sum(T_l, axis=0)                    # (s_c/mp,)
            nu_l = lax.psum(nu_l, "data")
            mu = lax.all_gather(mu_l, "data", tiled=True)  # (s_r,)
            nu = lax.all_gather(nu_l, "model", tiled=True) # (s_c,)
            t1 = (f1x @ mu)[:, None]                       # (s_r/dp, 1)
            t2 = (f2y @ nu)[None, :]                       # (1, s_c/mp)
            # h-term ht = h1(CxR) @ T @ h2(CyC)^T, block-sharded
            #   M_l = T_rows @ h2yᵀ — gather T over 'model' (full rows)
            #   ht  = h1x @ M_full — gather M over 'data' (full rows)
            T_rows = _gather(T_l, "model", 1)
            h2y_c = h2y.astype(T_rows.dtype)
            M_l = _mmt(T_rows, h2y_c)                      # (s_r/dp, s_c/mp) f32
            M_full = _gather(M_l, "data", 0)
            ht = jax.lax.dot_general(
                h1x.astype(M_full.dtype), M_full, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # (s_r/dp, s_c/mp)
            return t1 + t2 - ht

        def sinkhorn_log_block(logK_l):
            f_l = jnp.zeros_like(aR_l)
            g_l = jnp.zeros_like(bC_l)

            def body(_, fg):
                f_l, g_l = fg
                # row lse: over full s_c — local partial + psum-max trick:
                z = logK_l + g_l[None, :]
                m_l = lax.pmax(jnp.max(z, axis=1), "model")
                sums = lax.psum(jnp.sum(jnp.exp(z - m_l[:, None]), axis=1),
                                "model")
                f_l = la_l - (jnp.log(jnp.maximum(sums, 1e-38)) + m_l)
                z = logK_l + f_l[:, None]
                m_c = lax.pmax(jnp.max(z, axis=0), "data")
                sums = lax.psum(jnp.sum(jnp.exp(z - m_c[None, :]), axis=0),
                                "data")
                g_l = lb_l - (jnp.log(jnp.maximum(sums, 1e-38)) + m_c)
                return (f_l, g_l)

            f_l, g_l = lax.fori_loop(0, inner_iters, body, (f_l, g_l))
            return jnp.exp(logK_l + f_l[:, None] + g_l[None, :])

        T_l = aR_l[:, None] * bC_l[None, :]
        def outer(_, T_l):
            C_l = cost(T_l)
            logK_l = -C_l / epsilon + jnp.log(w_l) \
                + jnp.log(jnp.maximum(T_l, 1e-38))
            return sinkhorn_log_block(logK_l)

        T_l = lax.fori_loop(0, outer_iters, outer, T_l)
        val = lax.psum(lax.psum(jnp.sum(cost(T_l) * T_l), "model"), "data")
        return val, T_l

    sharded = shard_map(
        solver, mesh=mesh,
        in_specs=(P("data", None), P("model", None), P("data"), P("model"),
                  P("data", "model")),
        out_specs=(P(), P("data", "model")),
        check_vma=False,
    )
    return jax.jit(sharded)
