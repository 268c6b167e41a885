"""Solver configurations + runners for the unified Problem/Solver/Output API.

Each solver is a frozen dataclass registered twice:

* as a **pytree** — ``epsilon`` is a dynamic leaf (regularization sweeps
  don't retrace), everything that selects code paths or loop bounds
  (iteration budgets, tolerances, impl switches, support sizes) is static
  metadata;
* in a **name registry** (``get_solver`` / ``available_solvers``) so CLIs
  and configs can select any solver by string and new solvers plug in via
  ``@register_solver("name")`` without touching call sites.

``run(problem, key)`` dispatches on the *structure* of the problem:
``lam`` set → unbalanced variant, linear term present → fused variant.
All outer loops go through the shared tolerance-aware driver
(api/driver.py) and all inner Sinkhorn projections accept ``inner_tol``,
so every variant reports per-iteration marginal errors and supports early
stopping uniformly.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.api.driver import pga_loop
from repro.api.output import GridCoupling, GWOutput, SparseCoupling
from repro.api.pytree import register_pytree_dataclass
from repro.core import sampling
from repro.core.grid_gw import _dedup_marginal, grid_cost
from repro.core.gw import dense_cost, gw_objective
from repro.core.sinkhorn import (
    sinkhorn,
    sinkhorn_log,
    sinkhorn_unbalanced_log,
    sparse_sinkhorn,
    sparse_sinkhorn_logdomain,
    sparse_sinkhorn_unbalanced_log,
)
from repro.core.spar_ugw import _marginal_penalty
from repro.core.utils import quadratic_kl
from repro.kernels.spar_cost.ops import make_spar_cost_fn

# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register_solver(name: str):
    """Class decorator: register a solver config under a CLI-friendly name.

    ``repro.solve`` passes solver configs through ``jax.jit`` as pytree
    arguments, so a solver class must also be a registered pytree. Classes
    that didn't call ``register_pytree_dataclass`` themselves (e.g.
    third-party subclasses of the built-in solvers) are auto-registered
    here with ``epsilon`` as the single dynamic leaf and every other
    dataclass field as static metadata.
    """
    def deco(cls):
        if name in _REGISTRY:
            raise ValueError(f"solver name {name!r} already registered")
        # a hollow instance showing up as a pytree *leaf* means cls (as an
        # exact type — registration doesn't inherit) is not registered yet
        if jax.tree_util.all_leaves([object.__new__(cls)]):
            fields = tuple(f.name for f in dataclasses.fields(cls))
            data = tuple(f for f in fields if f in ("epsilon", "fault"))
            meta = tuple(f for f in fields if f not in data)
            register_pytree_dataclass(cls, data, meta)
        _REGISTRY[name] = cls
        cls.name = name
        return cls
    return deco


def get_solver(name: str):
    """Look up a solver class by registry name."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown solver {name!r}; available: "
            f"{', '.join(available_solvers())}") from None


def available_solvers():
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _coo_marginal_err(T, rows, cols, a, b):
    mu = jax.ops.segment_sum(T, rows, num_segments=a.shape[0])
    nu = jax.ops.segment_sum(T, cols, num_segments=b.shape[0])
    return jnp.sum(jnp.abs(mu - a)) + jnp.sum(jnp.abs(nu - b))


def _dense_marginal_err(T, a, b):
    return (jnp.sum(jnp.abs(T.sum(axis=1) - a))
            + jnp.sum(jnp.abs(T.sum(axis=0) - b)))


def _spar_pga_step(T, scale, cost_fn, a, b, rows, cols, w, logw, m: int,
                   n: int, epsilon, inner_iters: int, inner_tol: float,
                   reg: str, stable: bool, alpha=1.0, lin=0.0):
    """One proximal/entropic PGA outer step on the COO support.

    Shared by SPAR-GW (α = 1, lin = 0) and SPAR-FGW (lin = M̃): the
    iteration cost is C = α·(L @ T̃) + (1-α)·lin, and in the stable path
    the fused cost_fn writes logK = -C/ε + log w (+ log T̃) directly.
    ``scale`` is the driver's ε-rescue escalation (1.0 until a rescue
    fires; each rescue doubles it, flattening the kernel).
    """
    epsilon = epsilon * scale
    if stable:
        off = logw - ((1.0 - alpha) / epsilon) * lin
        if reg == "prox":
            off = off + jnp.log(jnp.maximum(T, 1e-38))
        logK = cost_fn((-alpha / epsilon) * T, off)
        return sparse_sinkhorn_logdomain(a, b, rows, cols, logK, m, n,
                                         inner_iters, tol=inner_tol)
    C = cost_fn(alpha * T, (1.0 - alpha) * lin)
    Cs = C - jnp.min(C)          # constant shift — Sinkhorn-invariant
    K = jnp.exp(-Cs / epsilon) * w
    if reg == "prox":
        K = K * T
    return sparse_sinkhorn(a, b, rows, cols, K, m, n, inner_iters,
                           tol=inner_tol)


def _require_key(key, solver_name: str):
    if key is None:
        raise ValueError(
            f"{solver_name} draws a random support: call "
            f"repro.solve(problem, solver, key=jax.random.PRNGKey(...))")


def _health_kw(solver):
    """Driver keywords wiring a config's rescue/fault knobs into pga_loop."""
    return dict(scaled_step=True, max_rescues=solver.max_rescues,
                rescue_factor=solver.rescue_factor, fault=solver.fault,
                trace=solver.trace)


# ---------------------------------------------------------------------------
# SPAR-GW (Algorithms 2, 3, 4 — COO importance sparsification)
# ---------------------------------------------------------------------------

@register_solver("spar_gw")
@dataclass(frozen=True)
class SparGWSolver:
    """Importance-sparsified GW — the paper's contribution.

    Covers Alg. 2 (GW), Alg. 4 (fused, problem carries a linear term) and
    Alg. 3 (unbalanced, problem carries ``lam``). ``s`` is the sampled
    support size (the paper uses s = 16n); ``cost_impl`` selects the
    O(s²) cost-assembly backend (kernels/spar_cost). ``max_rescues`` /
    ``rescue_factor`` bound the driver's in-jit ε-rescue restarts on
    detected divergence (ε-doubling from the last healthy iterate);
    ``fault`` is the chaos-testing hook (health/faults.py); ``trace``
    records per-iteration convergence buffers onto ``output.trace``
    (obs/trace.py — off by default, zero cost and zero leaves when off).
    """
    s: int = 0
    reg: str = "prox"
    epsilon: Any = 1e-2
    outer_iters: int = 20
    inner_iters: int = 50
    tol: float = 0.0
    inner_tol: float = 0.0
    shrink: float = 0.0
    cost_chunk: int = 1024
    stable: bool = True
    cost_impl: str = "auto"
    max_rescues: int = 2
    rescue_factor: float = 2.0
    fault: Any = None
    trace: bool = False

    requires_key = True

    @classmethod
    def default_config(cls, n: int):
        return cls(s=16 * n)

    def run(self, problem, key=None) -> GWOutput:
        if self.s <= 0:
            raise ValueError(
                "SparGWSolver.s (sampled support size) must be > 0; the "
                "paper's default is SparGWSolver(s=16 * n), or use "
                "SparGWSolver.default_config(n)")
        _require_key(key, "SparGWSolver")
        if problem.is_unbalanced:
            if problem.is_fused:
                raise NotImplementedError(
                    "fused + unbalanced GW is not implemented")
            return self._run_unbalanced(problem, key)
        return self._run_balanced(problem, key)

    def _run_balanced(self, problem, key) -> GWOutput:
        Cx, a = problem.geom_x.cost_matrix, problem.geom_x.weights
        Cy, b = problem.geom_y.cost_matrix, problem.geom_y.weights
        m, n = a.shape[0], b.shape[0]
        probs = sampling.balanced_probs(a, b, self.shrink)
        rows, cols = sampling.sample_pairs(key, probs, self.s)
        p = probs.pair_prob(rows, cols)                     # (s,)
        w = 1.0 / (self.s * p)                              # importance adj.
        T0 = a[rows] * b[cols]                              # step 4 init on S
        cost_fn = make_spar_cost_fn(Cx, Cy, rows, cols, problem.loss,
                                    impl=self.cost_impl, chunk=self.cost_chunk)
        fused = problem.is_fused
        alpha = problem.fused_penalty if fused else 1.0
        lin = problem.linear_cost_at(rows, cols) if fused else 0.0
        step = partial(_spar_pga_step, cost_fn=cost_fn, a=a, b=b, rows=rows,
                       cols=cols, w=w, logw=jnp.log(w), m=m, n=n,
                       epsilon=self.epsilon, inner_iters=self.inner_iters,
                       inner_tol=self.inner_tol, reg=self.reg,
                       stable=self.stable, alpha=alpha, lin=lin)
        err_fn = partial(_coo_marginal_err, rows=rows, cols=cols, a=a, b=b)

        def obj_fn(t):          # the step-8 plug-in objective, per iteration
            quad_t = jnp.sum(t * cost_fn(t))
            if fused:
                return alpha * quad_t + (1.0 - alpha) * jnp.sum(lin * t)
            return quad_t

        T, errors, n_iters, converged, status, trace = pga_loop(
            step, err_fn, T0, self.outer_iters, self.tol,
            obj_fn=obj_fn, **_health_kw(self))
        # Step 8: plug-in objective on the sparse support, O(s²).
        quad = jnp.sum(T * cost_fn(T))
        if fused:
            value = alpha * quad + (1.0 - alpha) * jnp.sum(lin * T)
        else:
            value = quad
        return GWOutput(value=value, coupling=SparseCoupling(rows, cols, T),
                        errors=errors, converged=converged, n_iters=n_iters,
                        status=status, trace=trace)

    def _run_unbalanced(self, problem, key) -> GWOutput:
        Cx, a = problem.geom_x.cost_matrix, problem.geom_x.weights
        Cy, b = problem.geom_y.cost_matrix, problem.geom_y.weights
        lam, loss, eps = problem.lam, problem.loss, self.epsilon
        m, n = a.shape[0], b.shape[0]
        scale = jnp.sqrt(jnp.sum(a) * jnp.sum(b))

        with jax.named_scope("gw.ugw_init"):
            # steps 2-3: dense rank-one init and its (log-)kernel — once
            Td = a[:, None] * b[None, :] / scale
            m0 = jnp.sum(Td)
            # HIGHEST: one bfloat16 pass on a TPU would move the eq.-9 law
            with jax.default_matmul_precision("highest"):
                C0 = dense_cost(Cx, Cy, Td, loss)
            C0 = C0 + _marginal_penalty(Td.sum(1), Td.sum(0), a, b, lam)
            logK0 = -C0 / (eps * m0) + jnp.log(jnp.maximum(Td, 1e-38))

            # steps 4-5: sampling probability (eq. 9) and index set
            P = sampling.unbalanced_probs(a, b, logK0, lam, eps, self.shrink)
            rows, cols = sampling.sample_pairs_2d(key, P, self.s)
            p = P[rows, cols]
            logw = -jnp.log(self.s * jnp.maximum(p, 1e-38))
            T0 = a[rows] * b[cols] / scale
        cost_fn = make_spar_cost_fn(Cx, Cy, rows, cols, loss,
                                    impl=self.cost_impl, chunk=self.cost_chunk)

        def step(T, scale):
            mT = jnp.sum(T)
            eps_bar = eps * scale * mT      # scale: driver ε-rescue escalation
            lam_bar = lam * mT
            mu = jax.ops.segment_sum(T, rows, num_segments=m)
            nu = jax.ops.segment_sum(T, cols, num_segments=n)
            # fused: logK = -(L@T̃ + penalty)/ε̄ + log T̃ + log w in one pass
            off = (-_marginal_penalty(mu, nu, a, b, lam) / eps_bar
                   + jnp.log(jnp.maximum(T, 1e-38)) + logw)
            logK = cost_fn((-1.0 / eps_bar) * T, off)
            T_new = sparse_sinkhorn_unbalanced_log(
                a, b, rows, cols, logK, lam_bar, eps_bar, m, n,
                self.inner_iters, tol=self.inner_tol)
            # step 10: mass rescaling
            return jnp.sqrt(mT / jnp.maximum(jnp.sum(T_new), 1e-30)) * T_new

        err_fn = partial(_coo_marginal_err, rows=rows, cols=cols, a=a, b=b)

        def obj_fn(t):          # Alg. 3 step-11 UGW objective, per iteration
            mu_t = jax.ops.segment_sum(t, rows, num_segments=m)
            nu_t = jax.ops.segment_sum(t, cols, num_segments=n)
            return (jnp.sum(t * cost_fn(t))
                    + lam * quadratic_kl(mu_t, a)
                    + lam * quadratic_kl(nu_t, b))

        T, errors, n_iters, converged, status, trace = pga_loop(
            step, err_fn, T0, self.outer_iters, self.tol,
            obj_fn=obj_fn, **_health_kw(self))
        # Alg. 3 step 11: UGW objective on the sparse coupling
        mu = jax.ops.segment_sum(T, rows, num_segments=m)
        nu = jax.ops.segment_sum(T, cols, num_segments=n)
        value = (jnp.sum(T * cost_fn(T))
                 + lam * quadratic_kl(mu, a) + lam * quadratic_kl(nu, b))
        return GWOutput(value=value, coupling=SparseCoupling(rows, cols, T),
                        errors=errors, converged=converged, n_iters=n_iters,
                        status=status, trace=trace)


# ---------------------------------------------------------------------------
# Dense GW (Algorithm 1 baselines: EGW / PGA-GW / fused / unbalanced)
# ---------------------------------------------------------------------------

@register_solver("dense_gw")
@dataclass(frozen=True)
class DenseGWSolver:
    """Dense EGW (reg='ent') / PGA-GW (reg='prox') — the paper's benchmark.

    Handles fused (problem linear term) and unbalanced (problem ``lam``)
    variants; the unbalanced path always runs in log domain.
    """
    reg: str = "prox"
    epsilon: Any = 1e-2
    outer_iters: int = 20
    inner_iters: int = 50
    tol: float = 0.0
    inner_tol: float = 0.0
    stable: bool = True
    max_rescues: int = 2
    rescue_factor: float = 2.0
    fault: Any = None
    trace: bool = False

    requires_key = False

    @classmethod
    def default_config(cls, n: int):
        return cls()

    def run(self, problem, key=None) -> GWOutput:
        # key accepted for interface uniformity; the solver is deterministic
        if problem.is_unbalanced:
            if problem.is_fused:
                raise NotImplementedError(
                    "fused + unbalanced GW is not implemented")
            return self._run_unbalanced(problem)
        return self._run_balanced(problem)

    def _run_balanced(self, problem) -> GWOutput:
        Cx, a = problem.geom_x.cost_matrix, problem.geom_x.weights
        Cy, b = problem.geom_y.cost_matrix, problem.geom_y.weights
        loss = problem.loss
        fused = problem.is_fused
        alpha = problem.fused_penalty if fused else 1.0
        M = problem.linear_cost_dense() if fused else None
        T0 = a[:, None] * b[None, :]

        def step(T, scale):
            eps = self.epsilon * scale      # scale: driver ε-rescue escalation
            C = dense_cost(Cx, Cy, T, loss)
            if fused:
                C = alpha * C + (1 - alpha) * M
            if self.stable:
                logK = -C / eps
                if self.reg == "prox":
                    logK = logK + jnp.log(jnp.maximum(T, 1e-38))
                return sinkhorn_log(a, b, logK, self.inner_iters,
                                    tol=self.inner_tol)
            Cs = C - jnp.min(C)      # constant shift — Sinkhorn-invariant
            K = jnp.exp(-Cs / eps)
            if self.reg == "prox":
                K = K * T
            return sinkhorn(a, b, K, self.inner_iters, tol=self.inner_tol)

        err_fn = partial(_dense_marginal_err, a=a, b=b)

        def obj_fn(t):
            quad_t = gw_objective(Cx, Cy, t, loss)
            if fused:
                return alpha * quad_t + (1 - alpha) * jnp.sum(M * t)
            return quad_t

        T, errors, n_iters, converged, status, trace = pga_loop(
            step, err_fn, T0, self.outer_iters, self.tol,
            obj_fn=obj_fn, **_health_kw(self))
        quad = gw_objective(Cx, Cy, T, loss)
        if fused:
            value = alpha * quad + (1 - alpha) * jnp.sum(M * T)
        else:
            value = quad
        return GWOutput(value=value, coupling=T, errors=errors,
                        converged=converged, n_iters=n_iters, status=status,
                        trace=trace)

    def _run_unbalanced(self, problem) -> GWOutput:
        Cx, a = problem.geom_x.cost_matrix, problem.geom_x.weights
        Cy, b = problem.geom_y.cost_matrix, problem.geom_y.weights
        lam, loss, eps = problem.lam, problem.loss, self.epsilon
        T0 = a[:, None] * b[None, :] / jnp.sqrt(jnp.sum(a) * jnp.sum(b))

        def step(T, scale):
            mT = jnp.sum(T)
            eps_bar = eps * scale * mT      # scale: driver ε-rescue escalation
            lam_bar = lam * mT
            C = dense_cost(Cx, Cy, T, loss) + _marginal_penalty(
                T.sum(1), T.sum(0), a, b, lam)
            logK = -C / eps_bar + jnp.log(jnp.maximum(T, 1e-38))
            T_new = sinkhorn_unbalanced_log(a, b, logK, lam_bar, eps_bar,
                                            self.inner_iters,
                                            tol=self.inner_tol)
            return jnp.sqrt(mT / jnp.maximum(jnp.sum(T_new), 1e-30)) * T_new

        err_fn = partial(_dense_marginal_err, a=a, b=b)

        def obj_fn(t):
            return (jnp.sum(t * dense_cost(Cx, Cy, t, loss))
                    + lam * quadratic_kl(t.sum(1), a)
                    + lam * quadratic_kl(t.sum(0), b))

        T, errors, n_iters, converged, status, trace = pga_loop(
            step, err_fn, T0, self.outer_iters, self.tol,
            obj_fn=obj_fn, **_health_kw(self))
        value = (jnp.sum(T * dense_cost(Cx, Cy, T, loss))
                 + lam * quadratic_kl(T.sum(1), a)
                 + lam * quadratic_kl(T.sum(0), b))
        return GWOutput(value=value, coupling=T, errors=errors,
                        converged=converged, n_iters=n_iters, status=status,
                        trace=trace)


# ---------------------------------------------------------------------------
# Grid-SPAR-GW (beyond-paper TPU-native factorized sparsification)
# ---------------------------------------------------------------------------

@register_solver("grid_gw")
@dataclass(frozen=True)
class GridGWSolver:
    """Grid-structured SPAR-GW: support = R × C, dense s_r × s_c block.

    Balanced problems only (no fused/unbalanced grid variant yet).
    ``use_kernel`` routes the arbitrary-loss cost assembly through the
    Pallas gw_cost kernel.
    """
    s_r: int = 0
    s_c: int = 0
    reg: str = "prox"
    epsilon: Any = 1e-2
    outer_iters: int = 20
    inner_iters: int = 50
    tol: float = 0.0
    inner_tol: float = 0.0
    shrink: float = 0.0
    use_kernel: bool = False
    stable: bool = True
    max_rescues: int = 2
    rescue_factor: float = 2.0
    fault: Any = None
    trace: bool = False

    requires_key = True

    @classmethod
    def default_config(cls, n: int):
        side = max(8, int(round((16 * n) ** 0.5)))   # equal budget s = 16n
        return cls(s_r=side, s_c=side)

    def run(self, problem, key=None) -> GWOutput:
        if self.s_r <= 0 or self.s_c <= 0:
            raise ValueError(
                "GridGWSolver requires s_r > 0 and s_c > 0 (grid support "
                "side lengths); use GridGWSolver.default_config(n)")
        _require_key(key, "GridGWSolver")
        if problem.is_fused or problem.is_unbalanced:
            raise NotImplementedError(
                "GridGWSolver supports balanced non-fused problems only; "
                "use SparGWSolver for fused/unbalanced variants")
        Cx, a = problem.geom_x.cost_matrix, problem.geom_x.weights
        Cy, b = problem.geom_y.cost_matrix, problem.geom_y.weights
        loss = problem.loss
        m, n = a.shape[0], b.shape[0]
        probs = sampling.balanced_probs(a, b, self.shrink)
        R, C = sampling.sample_grid(key, probs, self.s_r, self.s_c)
        CxR = Cx[R][:, R]                                # (s_r, s_r) — once
        CyC = Cy[C][:, C]                                # (s_c, s_c) — once
        s = self.s_r * self.s_c
        w = 1.0 / (s * probs.pa[R][:, None] * probs.pb[C][None, :])
        aR = _dedup_marginal(R, a, m)
        bC = _dedup_marginal(C, b, n)
        # normalize to unit mass (covered-support renorm.; DESIGN.md §4)
        aR = aR / aR.sum()
        bC = bC / bC.sum()
        T0 = aR[:, None] * bC[None, :]

        def step(T, scale):
            eps = self.epsilon * scale      # scale: driver ε-rescue escalation
            Cmat = grid_cost(CxR, CyC, T, loss, self.use_kernel)
            if self.stable:
                logK = -Cmat / eps + jnp.log(w)
                if self.reg == "prox":
                    logK = logK + jnp.log(jnp.maximum(T, 1e-38))
                return sinkhorn_log(aR, bC, logK, self.inner_iters,
                                    tol=self.inner_tol)
            Cs = Cmat - jnp.min(Cmat)
            K = jnp.exp(-Cs / eps) * w
            if self.reg == "prox":
                K = K * T
            return sinkhorn(aR, bC, K, self.inner_iters, tol=self.inner_tol)

        err_fn = partial(_dense_marginal_err, a=aR, b=bC)

        def obj_fn(t):
            return jnp.sum(t * grid_cost(CxR, CyC, t, loss, self.use_kernel))

        T, errors, n_iters, converged, status, trace = pga_loop(
            step, err_fn, T0, self.outer_iters, self.tol,
            obj_fn=obj_fn, **_health_kw(self))
        value = jnp.sum(T * grid_cost(CxR, CyC, T, loss, self.use_kernel))
        return GWOutput(value=value, coupling=GridCoupling(R, C, T),
                        errors=errors, converged=converged, n_iters=n_iters,
                        status=status, trace=trace)


