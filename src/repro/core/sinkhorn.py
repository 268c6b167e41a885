"""Sinkhorn-scaling solvers: dense, log-domain, unbalanced, and sparse (COO).

All loops are ``lax``-native. Every solver has a plain-domain variant
(faithful to Alg. 1/2/3 as written) and a log-domain variant (production
default — small ε and proximal kernels underflow fp32 otherwise).
``differentiable=True`` variants use ``lax.scan`` so reverse-mode AD works
(used by the GW alignment loss).

Every solver takes ``tol`` (static): ``tol=0`` runs the paper's fixed
iteration budget via ``fori_loop`` (bitwise-identical to the historical
behavior); ``tol>0`` runs a bounded ``while_loop`` that stops once the
sup-norm change of the scaling potentials drops below ``tol``. The while
path masks finished lanes so it is safe under ``vmap`` (see
api/driver.py for the same trick on the outer loop); the
``differentiable=True`` variants require ``tol=0`` (reverse-mode AD
needs the fixed-length scan) and raise otherwise. An unconverged
marginal projection is not a harmless inexactness: it stalls the outer
PGA loop at a non-coupling fixed point (the two historical pga_gw test
failures), so production configs should set an inner tolerance.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.utils import safe_div
from repro.obs.registry import registry as _obs_registry

_NEG_INF = -1e30   # proxy for -inf that stays NaN-free under arithmetic

# Largest per-lane cell grid m·n (64 MiB of float32; square buckets up to
# 4096) on which the log-domain sparse Sinkhorn keeps its kernel dense.
_DENSE_CELLS_MAX = 1 << 24


# every Sinkhorn entry point traces under this named scope, so its ops
# carry "gw.sinkhorn" in the HLO op_name metadata
_scoped = jax.named_scope("gw.sinkhorn")


def _finite(x):
    return jnp.where(jnp.isfinite(x) & (x > _NEG_INF / 2), x, 0.0)


def _scaling_loop(body, init, iters: int, tol: float):
    """Run ``carry <- body(carry)`` for a fixed budget or to tolerance.

    ``body`` maps a tuple of potential vectors to the updated tuple.
    ``tol=0`` → ``fori_loop`` over the full budget (legacy numerics).
    ``tol>0`` → bounded ``while_loop``, stopping when the largest absolute
    change across all potentials is <= tol; finished lanes are frozen so
    the loop is vmap-safe.
    """
    if not tol or tol <= 0.0:
        return lax.fori_loop(0, iters, lambda _, c: body(c), init)

    def cond(state):
        i, _, done = state
        return (i < iters) & jnp.logical_not(done)

    def wl_body(state):
        i, carry, done = state
        new = body(carry)
        delta = jnp.max(jnp.stack(
            [jnp.max(jnp.abs(n - o)) for n, o in zip(new, carry)]))
        frozen = tuple(jnp.where(done, o, n) for n, o in zip(new, carry))
        return (jnp.where(done, i, i + 1), frozen, done | (delta <= tol))

    _, carry, _ = lax.while_loop(
        cond, wl_body, (jnp.int32(0), init, jnp.bool_(False)))
    return carry


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------

@_scoped
def sinkhorn(a, b, K, iters: int, differentiable: bool = False,
             tol: float = 0.0):
    """Plain Sinkhorn scaling (Alg. 1 step 5): u = a ⊘ (K v), v = b ⊘ (Kᵀ u)."""
    m, n = K.shape
    u0 = jnp.ones((m,), K.dtype)
    v0 = jnp.ones((n,), K.dtype)

    def body(carry):
        u, v = carry
        u = safe_div(a, K @ v)
        v = safe_div(b, K.T @ u)
        return (u, v)

    if differentiable and tol and tol > 0.0:
        raise ValueError(
            "tol-based early stopping is not supported with "
            "differentiable=True (reverse-mode AD needs the fixed-length "
            "scan); pass tol=0")
    if differentiable:
        (u, v), _ = lax.scan(lambda c, _: (body(c), None), (u0, v0), None,
                             length=iters)
    else:
        u, v = _scaling_loop(body, (u0, v0), iters, tol)
    return u[:, None] * K * v[None, :]


@_scoped
def sinkhorn_log(a, b, logK, iters: int, differentiable: bool = False,
                 tol: float = 0.0):
    """Log-domain Sinkhorn. Returns the coupling T (dense)."""
    m, n = logK.shape
    la = jnp.log(jnp.maximum(a, 1e-38))
    lb = jnp.log(jnp.maximum(b, 1e-38))
    f0 = jnp.zeros((m,), logK.dtype)
    g0 = jnp.zeros((n,), logK.dtype)

    def body(carry):
        f, g = carry
        f = _finite(la - jax.scipy.special.logsumexp(logK + g[None, :], axis=1))
        g = _finite(lb - jax.scipy.special.logsumexp(logK + f[:, None], axis=0))
        return (f, g)

    if differentiable and tol and tol > 0.0:
        raise ValueError(
            "tol-based early stopping is not supported with "
            "differentiable=True (reverse-mode AD needs the fixed-length "
            "scan); pass tol=0")
    if differentiable:
        (f, g), _ = lax.scan(lambda c, _: (body(c), None), (f0, g0), None,
                             length=iters)
    else:
        f, g = _scaling_loop(body, (f0, g0), iters, tol)
    return jnp.exp(logK + f[:, None] + g[None, :])


@_scoped
def sinkhorn_unbalanced(a, b, K, lam, eps, iters: int, tol: float = 0.0):
    """Plain unbalanced Sinkhorn (Alg. 3 step 9): exponent λ̄/(λ̄+ε̄)."""
    m, n = K.shape
    rho = lam / (lam + eps)
    u0 = jnp.ones((m,), K.dtype)
    v0 = jnp.ones((n,), K.dtype)

    def body(carry):
        u, v = carry
        u = safe_div(a, K @ v) ** rho
        v = safe_div(b, K.T @ u) ** rho
        return (u, v)

    u, v = _scaling_loop(body, (u0, v0), iters, tol)
    return u[:, None] * K * v[None, :]


@_scoped
def sinkhorn_unbalanced_log(a, b, logK, lam, eps, iters: int,
                            tol: float = 0.0):
    """Log-domain unbalanced Sinkhorn: log u = ρ (log a - lse(logK + log v))."""
    m, n = logK.shape
    rho = lam / (lam + eps)
    la = jnp.log(jnp.maximum(a, 1e-38))
    lb = jnp.log(jnp.maximum(b, 1e-38))
    f0 = jnp.zeros((m,), logK.dtype)
    g0 = jnp.zeros((n,), logK.dtype)

    def body(carry):
        f, g = carry
        f = _finite(rho * (la - jax.scipy.special.logsumexp(logK + g[None, :], axis=1)))
        g = _finite(rho * (lb - jax.scipy.special.logsumexp(logK + f[:, None], axis=0)))
        return (f, g)

    f, g = _scaling_loop(body, (f0, g0), iters, tol)
    return jnp.exp(logK + f[:, None] + g[None, :])


# ---------------------------------------------------------------------------
# Sparse (COO) — the paper's Step 7 with sparse matvecs, O(H s).
# ---------------------------------------------------------------------------

def coo_matvec(rows, cols, vals, x, out_dim: int):
    """y_i = Σ_{l: rows_l = i} vals_l * x[cols_l] — sparse K @ x."""
    return jax.ops.segment_sum(vals * x[cols], rows, num_segments=out_dim)


def segment_logsumexp(vals, segs, num: int):
    """Per-segment logsumexp; empty segments -> _NEG_INF. NaN-free."""
    maxs = jax.ops.segment_max(vals, segs, num_segments=num)
    maxs_safe = jnp.where(maxs > _NEG_INF / 2, maxs, 0.0)
    sums = jax.ops.segment_sum(jnp.exp(vals - maxs_safe[segs]), segs,
                               num_segments=num)
    out = jnp.log(jnp.maximum(sums, 1e-38)) + maxs_safe
    return jnp.where(sums > 0, out, _NEG_INF)


@partial(jax.jit, static_argnames=("m", "n", "iters", "tol"))
@_scoped
def sparse_sinkhorn(a, b, rows, cols, vals, m: int, n: int, iters: int,
                    tol: float = 0.0):
    """Plain-domain sparse Sinkhorn on a COO kernel (paper-faithful).

    Returns the COO values of the coupling T̃ (same sparsity pattern).
    Rows/cols without support get scaling 0 (dead), matching sparse
    implementations of Alg. 2.
    """
    u0 = jnp.ones((m,), vals.dtype)
    v0 = jnp.ones((n,), vals.dtype)

    def body(carry):
        u, v = carry
        u = safe_div(a, coo_matvec(rows, cols, vals, v, m))
        v = safe_div(b, coo_matvec(cols, rows, vals, u, n))
        return (u, v)

    u, v = _scaling_loop(body, (u0, v0), iters, tol)
    return u[rows] * vals * v[cols]


def _grid_logsumexp(x, axis: int):
    """Logsumexp of a log-kernel grid along ``axis``; a line with no cell
    above ``_NEG_INF / 2`` -> _NEG_INF. The dense twin of
    :func:`segment_logsumexp`, NaN-free in value and gradient: a live
    line sums to at least 1, and a dead one takes the log of 1, not of a
    subnormal floor that flushes to 0 (whose log's gradient is inf)."""
    maxs = jnp.max(x, axis=axis, keepdims=True)
    maxs_safe = jnp.where(maxs > _NEG_INF / 2, maxs, 0.0)
    sums = jnp.sum(jnp.exp(x - maxs_safe), axis=axis)
    live = sums > 0
    out = jnp.log(jnp.where(live, sums, 1.0)) + jnp.squeeze(maxs_safe, axis)
    return jnp.where(live, out, _NEG_INF)


def _live(pot):
    """A potential as it meets the dense grid. A row or column with no
    support has log-sum-exp _NEG_INF, so its potential sits near
    -_NEG_INF; held at 0 here, its off-support cells stay at _NEG_INF
    instead of cancelling to 0."""
    return jnp.where(pot < -_NEG_INF / 2, pot, 0.0)


def _log_scaling(a, b, rows, cols, logvals, m: int, n: int, iters: int,
                 tol: float, rho, row_lse, col_lse):
    """The log-domain scaling both layouts share: ``row_lse(g)`` and
    ``col_lse(f)`` give each row's and column's log-sum-exp of the kernel
    plus the other side's potential; ``rho`` (None when balanced) is the
    unbalanced exponent. Returns the COO values of T̃ in support order."""
    la = jnp.log(jnp.maximum(a, 1e-38))
    lb = jnp.log(jnp.maximum(b, 1e-38))
    f0 = jnp.zeros((m,), logvals.dtype)
    g0 = jnp.zeros((n,), logvals.dtype)
    shrink = (lambda x: x) if rho is None else (lambda x: rho * x)

    def body(carry):
        f, g = carry
        f = _finite(shrink(la - row_lse(g)))
        g = _finite(shrink(lb - col_lse(f)))
        return (f, g)

    f, g = _scaling_loop(body, (f0, g0), iters, tol)
    return jnp.exp(logvals + f[rows] + g[cols])


def _sinkhorn_coo(a, b, rows, cols, logvals, m: int, n: int, iters: int,
                  tol: float = 0.0, rho=None):
    """COO layout: every trip gathers the potentials onto the s support
    entries and segment-reduces them back, O(s) scatter/gather a trip."""
    return _log_scaling(
        a, b, rows, cols, logvals, m, n, iters, tol, rho,
        lambda g: segment_logsumexp(logvals + g[cols], rows, m),
        lambda f: segment_logsumexp(logvals + f[rows], cols, n))


def _sinkhorn_dense(a, b, rows, cols, logvals, m: int, n: int, iters: int,
                    tol: float = 0.0, rho=None):
    """Dense-cell layout: the support's log-kernel merged once into an
    (m, n) grid G (duplicates by log-sum-exp, cells off the support at
    _NEG_INF), then every trip is a row and a column log-sum-exp over G,
    with no gather or scatter inside the loop. The same function as
    :func:`_sinkhorn_coo`: off-support cells contribute exactly zero."""
    G = segment_logsumexp(logvals, rows * n + cols, m * n).reshape(m, n)
    # checkpointed: reverse mode (unrolled autodiff) keeps each trip's
    # potentials and recomputes the trip's (m, n) grid of terms on the
    # way back, instead of saving it every trip; the forward is the same
    return _log_scaling(
        a, b, rows, cols, logvals, m, n, iters, tol, rho,
        jax.checkpoint(lambda g: _grid_logsumexp(G + _live(g)[None, :], 1)),
        jax.checkpoint(lambda f: _grid_logsumexp(G + _live(f)[:, None], 0)))


def _sparse_log_layout(m: int, n: int):
    """The layout of the log-domain sparse Sinkhorn for an (m, n) problem.

    Dense up to ``_DENSE_CELLS_MAX`` cells a lane, COO above. A COO trip
    pays a serialised gather or scatter per support entry (~86 ns an
    entry on a TPU v5e at s = 16n, bucket 1024); a dense trip is two
    streaming reductions over m·n cells. On that chip a served n = 1000
    solve spent 1,233 ms in the COO Sinkhorn and 18.4 ms in the dense
    one, merge included. The bound is memory, per lane since a vmapped
    trace cannot see its lanes. In the compiled peak of a served v5e
    batch (cost on the jnp path) the grid is free up to 2**24 cells, held
    in what the cost step frees (bucket 4096, 8 lanes: 5.16 against
    5.14 GiB on COO); past it
    it costs (bucket 8192, 6 lanes: 11.85 against 9.22 GiB) and can
    decide whether a batch fits (bucket 16384, 3 lanes: 20.0 GiB, over
    the chip's 15.75, against 12.2). Counts each choice in
    ``repro_sinkhorn_layout_total``: under jit that is once per trace,
    not per execution.
    """
    layout = "dense" if m * n <= _DENSE_CELLS_MAX else "coo"
    _obs_registry().counter(
        "repro_sinkhorn_layout_total",
        "log-domain sparse Sinkhorn traces by kernel layout",
        layout=layout).inc()
    return _sinkhorn_dense if layout == "dense" else _sinkhorn_coo


@partial(jax.jit, static_argnames=("m", "n", "iters", "tol"))
@_scoped
def sparse_sinkhorn_logdomain(a, b, rows, cols, logvals, m: int, n: int,
                              iters: int, tol: float = 0.0):
    """Log-domain sparse Sinkhorn (production default; small-ε safe).

    Returns the COO values of T̃ in support order; the kernel's layout
    between trips follows the shape (:func:`_sparse_log_layout`)."""
    return _sparse_log_layout(m, n)(a, b, rows, cols, logvals, m, n, iters,
                                    tol)


@partial(jax.jit, static_argnames=("m", "n", "iters", "tol"))
@_scoped
def sparse_sinkhorn_unbalanced(a, b, rows, cols, vals, lam, eps,
                               m: int, n: int, iters: int, tol: float = 0.0):
    """Plain-domain unbalanced sparse Sinkhorn (Alg. 3 step 9)."""
    rho = lam / (lam + eps)
    u0 = jnp.ones((m,), vals.dtype)
    v0 = jnp.ones((n,), vals.dtype)

    def body(carry):
        u, v = carry
        u = safe_div(a, coo_matvec(rows, cols, vals, v, m)) ** rho
        v = safe_div(b, coo_matvec(cols, rows, vals, u, n)) ** rho
        return (u, v)

    u, v = _scaling_loop(body, (u0, v0), iters, tol)
    return u[rows] * vals * v[cols]


@partial(jax.jit, static_argnames=("m", "n", "iters", "tol"))
@_scoped
def sparse_sinkhorn_unbalanced_log(a, b, rows, cols, logvals, lam, eps,
                                   m: int, n: int, iters: int,
                                   tol: float = 0.0):
    """Log-domain unbalanced sparse Sinkhorn (layout as the balanced one)."""
    rho = lam / (lam + eps)
    return _sparse_log_layout(m, n)(a, b, rows, cols, logvals, m, n, iters,
                                    tol, rho=rho)
