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

_NEG_INF = -1e30   # proxy for -inf that stays NaN-free under arithmetic


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


@partial(jax.jit, static_argnames=("m", "n", "iters", "tol"))
@_scoped
def sparse_sinkhorn_logdomain(a, b, rows, cols, logvals, m: int, n: int,
                              iters: int, tol: float = 0.0):
    """Log-domain sparse Sinkhorn (production default; small-ε safe)."""
    la = jnp.log(jnp.maximum(a, 1e-38))
    lb = jnp.log(jnp.maximum(b, 1e-38))
    f0 = jnp.zeros((m,), logvals.dtype)
    g0 = jnp.zeros((n,), logvals.dtype)

    def body(carry):
        f, g = carry
        f = _finite(la - segment_logsumexp(logvals + g[cols], rows, m))
        g = _finite(lb - segment_logsumexp(logvals + f[rows], cols, n))
        return (f, g)

    f, g = _scaling_loop(body, (f0, g0), iters, tol)
    return jnp.exp(logvals + f[rows] + g[cols])


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
    """Log-domain unbalanced sparse Sinkhorn."""
    rho = lam / (lam + eps)
    la = jnp.log(jnp.maximum(a, 1e-38))
    lb = jnp.log(jnp.maximum(b, 1e-38))
    f0 = jnp.zeros((m,), logvals.dtype)
    g0 = jnp.zeros((n,), logvals.dtype)

    def body(carry):
        f, g = carry
        f = _finite(rho * (la - segment_logsumexp(logvals + g[cols], rows, m)))
        g = _finite(rho * (lb - segment_logsumexp(logvals + f[rows], cols, n)))
        return (f, g)

    f, g = _scaling_loop(body, (f0, g0), iters, tol)
    return jnp.exp(logvals + f[rows] + g[cols])
