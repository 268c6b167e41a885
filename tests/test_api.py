"""Unified Problem/Solver/Output API: pytree round-trips, validation,
jit+vmap batched solves, shim equivalence, registry, early stopping."""
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import (
    DenseGWSolver,
    Geometry,
    GridGWSolver,
    GWOutput,
    QuadraticProblem,
    SparGWSolver,
    available_solvers,
    get_solver,
    register_solver,
    solve,
)
from repro.core import grid_spar_gw, gw_dense, spar_fgw, spar_gw, spar_ugw

KEY = jax.random.PRNGKey(0)
N = 20
FAST = dict(outer_iters=5, inner_iters=20)


def _cloud(key, n, d=2, scale=1.0, shift=0.0):
    x = jax.random.normal(key, (n, d)) * scale + shift
    return jnp.sqrt(jnp.sum((x[:, None] - x[None, :]) ** 2, -1))


def _problem(seed=0, n=N, loss="l2", **kw):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    Cx = _cloud(kx, n)
    Cy = _cloud(ky, n, scale=1.2)
    a = b = jnp.ones(n) / n
    return QuadraticProblem(Geometry(Cx, a), Geometry(Cy, b), loss=loss, **kw)


# ---------------------------------------------------------------------------
# pytree structure
# ---------------------------------------------------------------------------

def test_problem_pytree_roundtrip():
    prob = _problem()
    leaves, treedef = jax.tree_util.tree_flatten(prob)
    assert all(isinstance(l, jax.Array) for l in leaves)
    prob2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(prob2, QuadraticProblem)
    assert prob2.loss == prob.loss and prob2.shape == prob.shape
    np.testing.assert_array_equal(np.asarray(prob2.geom_x.cost),
                                  np.asarray(prob.geom_x.cost))


def test_output_pytree_roundtrip():
    out = solve(_problem(), SparGWSolver(s=4 * N, **FAST), key=KEY)
    leaves, treedef = jax.tree_util.tree_flatten(out)
    out2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(out2, GWOutput)
    np.testing.assert_array_equal(np.asarray(out2.coupling.vals),
                                  np.asarray(out.coupling.vals))
    assert float(out2.value) == float(out.value)


def test_solver_pytree_epsilon_is_leaf():
    """ε sweeps must not retrace: ε is the only dynamic leaf of a solver."""
    s1 = SparGWSolver(s=64, epsilon=1e-2)
    s2 = SparGWSolver(s=64, epsilon=5e-2)
    l1, t1 = jax.tree_util.tree_flatten(s1)
    l2, t2 = jax.tree_util.tree_flatten(s2)
    assert t1 == t2                      # same structure -> same jit cache
    assert l1 == [1e-2] and l2 == [5e-2]
    # but a static knob change IS a structure change
    _, t3 = jax.tree_util.tree_flatten(SparGWSolver(s=128, epsilon=1e-2))
    assert t3 != t1


def test_variant_dispatch_is_structural():
    """lam / M presence selects the variant via the pytree structure."""
    assert not _problem().is_unbalanced and not _problem().is_fused
    p_u = _problem(lam=1.0)
    assert p_u.is_unbalanced
    M = jnp.zeros((N, N))
    p_f = _problem(M=M, fused_penalty=0.5)
    assert p_f.is_fused
    _, t_plain = jax.tree_util.tree_flatten(_problem())
    _, t_u = jax.tree_util.tree_flatten(p_u)
    assert t_plain != t_u


# ---------------------------------------------------------------------------
# validation at the Problem boundary
# ---------------------------------------------------------------------------

def test_validation_rejects_nonsquare_cost():
    a = jnp.ones(N) / N
    with pytest.raises(ValueError, match="square"):
        Geometry(jnp.zeros((N, N - 1)), a)


def test_validation_rejects_marginal_length_mismatch():
    with pytest.raises(ValueError, match="weights must have shape"):
        Geometry(jnp.zeros((N, N)), jnp.ones(N + 3) / (N + 3))


def test_validation_rejects_unnormalized_weights():
    C = _cloud(KEY, N)
    a = jnp.ones(N) / N
    with pytest.raises(ValueError, match="sum to 1"):
        QuadraticProblem(Geometry(C, a * 2.0), Geometry(C, a))
    # ... but unbalanced problems allow arbitrary masses
    QuadraticProblem(Geometry(C, a * 2.0), Geometry(C, a), lam=1.0)


def test_validation_rejects_bad_fused_config():
    C = _cloud(KEY, N)
    a = jnp.ones(N) / N
    M = jnp.zeros((N, N))
    with pytest.raises(ValueError, match="fused_penalty"):
        QuadraticProblem(Geometry(C, a), Geometry(C, a), M=M)
    with pytest.raises(ValueError, match="linear term"):
        QuadraticProblem(Geometry(C, a), Geometry(C, a), fused_penalty=0.5)
    with pytest.raises(ValueError, match="must have shape"):
        QuadraticProblem(Geometry(C, a), Geometry(C, a),
                         M=jnp.zeros((N, N + 1)), fused_penalty=0.5)


def test_validation_optout_and_tracer_safety():
    C = _cloud(KEY, N)
    a = jnp.ones(N) / N
    # opt-out flag: no value checks
    QuadraticProblem(Geometry(C, a * 2.0, validate=False),
                     Geometry(C, a), validate=False)

    # value checks auto-skip under tracing; construction inside jit works
    @jax.jit
    def build_and_solve(C, a):
        prob = QuadraticProblem(Geometry(C, a), Geometry(C, a), loss="l2")
        return solve(prob, DenseGWSolver(outer_iters=2, inner_iters=5)).value

    assert np.isfinite(float(build_and_solve(C, a)))


# ---------------------------------------------------------------------------
# shim equivalence: old entry points == repro.solve, bitwise
# ---------------------------------------------------------------------------

def test_shim_spar_gw_bitwise():
    prob = _problem()
    solver = SparGWSolver(s=4 * N, **FAST)
    out = solve(prob, solver, key=KEY)
    v, (r, c, t) = spar_gw(KEY, prob.geom_x.weights, prob.geom_y.weights,
                           prob.geom_x.cost, prob.geom_y.cost, s=4 * N, **FAST)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(out.value))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(out.coupling.rows))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(out.coupling.vals))


def test_shim_spar_fgw_bitwise():
    M = jax.random.uniform(jax.random.PRNGKey(5), (N, N))
    prob = _problem(M=M, fused_penalty=0.7)
    out = solve(prob, SparGWSolver(s=4 * N, **FAST), key=KEY)
    v, (_, _, t) = spar_fgw(KEY, prob.geom_x.weights, prob.geom_y.weights,
                            prob.geom_x.cost, prob.geom_y.cost, M, s=4 * N,
                            alpha=0.7, **FAST)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(out.value))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(out.coupling.vals))


def test_shim_spar_ugw_bitwise():
    prob = _problem(lam=1.0)
    out = solve(prob, SparGWSolver(s=4 * N, **FAST), key=KEY)
    v, (_, _, t) = spar_ugw(KEY, prob.geom_x.weights, prob.geom_y.weights,
                            prob.geom_x.cost, prob.geom_y.cost, s=4 * N,
                            lam=1.0, **FAST)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(out.value))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(out.coupling.vals))


def test_shim_gw_dense_bitwise():
    prob = _problem()
    out = solve(prob, DenseGWSolver(**FAST))
    v, T = gw_dense(prob.geom_x.weights, prob.geom_y.weights,
                    prob.geom_x.cost, prob.geom_y.cost, **FAST)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(out.value))
    np.testing.assert_array_equal(np.asarray(T), np.asarray(out.coupling))


def test_shim_grid_spar_gw_bitwise():
    prob = _problem()
    out = solve(prob, GridGWSolver(s_r=16, s_c=16, **FAST), key=KEY)
    v, (R, C, T) = grid_spar_gw(KEY, prob.geom_x.weights, prob.geom_y.weights,
                                prob.geom_x.cost, prob.geom_y.cost,
                                s_r=16, s_c=16, **FAST)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(out.value))
    np.testing.assert_array_equal(np.asarray(R), np.asarray(out.coupling.rows))
    np.testing.assert_array_equal(np.asarray(T), np.asarray(out.coupling.block))


def test_shims_warn_deprecation():
    prob = _problem()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        spar_gw(KEY, prob.geom_x.weights, prob.geom_y.weights,
                prob.geom_x.cost, prob.geom_y.cost, s=2 * N, outer_iters=1,
                inner_iters=2)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)


# ---------------------------------------------------------------------------
# jit + vmap batching (acceptance criterion)
# ---------------------------------------------------------------------------

def _stacked_problems(n_problems, **prob_kw):
    probs = [_problem(seed=s, **prob_kw) for s in range(n_problems)]
    return probs, jax.tree.map(lambda *xs: jnp.stack(xs), *probs)


@pytest.mark.parametrize("variant", ["gw", "fgw", "ugw"])
def test_solve_vmap_batched_matches_legacy(variant):
    """repro.solve under a single jit over a vmap-batched stack of 4
    problems; unbatched slices must match the legacy entry points."""
    B = 4
    kw = {}
    if variant == "fgw":
        kw = dict(M=jax.random.uniform(jax.random.PRNGKey(9), (N, N)),
                  fused_penalty=0.6)
    elif variant == "ugw":
        kw = dict(lam=1.0)
    probs, stacked = _stacked_problems(B, **kw)
    keys = jax.random.split(jax.random.PRNGKey(7), B)
    solver = SparGWSolver(s=4 * N, **FAST)

    batched = jax.jit(jax.vmap(lambda p, k: solve(p, solver, key=k)))
    out = batched(stacked, keys)
    assert out.value.shape == (B,)
    assert out.coupling.vals.shape == (B, 4 * N)
    assert out.errors.shape == (B, FAST["outer_iters"])

    legacy = {"gw": lambda p, k: spar_gw(
                  k, p.geom_x.weights, p.geom_y.weights, p.geom_x.cost,
                  p.geom_y.cost, s=4 * N, **FAST),
              "fgw": lambda p, k: spar_fgw(
                  k, p.geom_x.weights, p.geom_y.weights, p.geom_x.cost,
                  p.geom_y.cost, kw["M"], s=4 * N, alpha=0.6, **FAST),
              "ugw": lambda p, k: spar_ugw(
                  k, p.geom_x.weights, p.geom_y.weights, p.geom_x.cost,
                  p.geom_y.cost, s=4 * N, lam=1.0, **FAST)}[variant]
    for i in range(B):
        v, (_, _, t) = legacy(probs[i], keys[i])
        np.testing.assert_allclose(float(out.value[i]), float(v),
                                   rtol=2e-5, atol=1e-6)
        # batched lowering reorders float ops; near-zero coupling entries
        # (log-domain exp underflow region) need an absolute tolerance
        np.testing.assert_allclose(np.asarray(out.coupling.vals[i]),
                                   np.asarray(t), rtol=1e-4, atol=1e-6)


def test_solve_vmap_dense_solver():
    B = 4
    probs, stacked = _stacked_problems(B)
    out = jax.jit(jax.vmap(lambda p: solve(p, DenseGWSolver(**FAST))))(stacked)
    assert out.coupling.shape == (B, N, N)
    for i in range(B):
        ref = solve(probs[i], DenseGWSolver(**FAST))
        np.testing.assert_allclose(float(out.value[i]), float(ref.value),
                                   rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# convergence machinery
# ---------------------------------------------------------------------------

def test_early_stopping_reports_convergence():
    """Self-distance at moderate ε: the outer loop must stop well before
    the bound, flag convergence, and NaN-pad the error buffer."""
    C = _cloud(KEY, N)
    a = jnp.ones(N) / N
    prob = QuadraticProblem(Geometry(C, a), Geometry(C, a), loss="l2")
    out = solve(prob, DenseGWSolver(epsilon=1e-3, outer_iters=50,
                                    inner_iters=500, tol=1e-6, inner_tol=1e-7))
    n_it = int(out.n_iters)
    assert bool(out.converged) and n_it < 50
    errs = np.asarray(out.errors)
    assert np.all(np.isfinite(errs[:n_it]))
    assert np.all(np.isnan(errs[n_it:]))
    # converged marginal projection -> tiny violation at the end
    assert errs[n_it - 1] < 1e-4


def test_tol_zero_runs_full_budget():
    out = solve(_problem(), SparGWSolver(s=4 * N, **FAST), key=KEY)
    assert int(out.n_iters) == FAST["outer_iters"]
    assert not bool(out.converged)
    assert np.all(np.isfinite(np.asarray(out.errors)))


def test_inner_tol_matches_full_budget_result():
    """Early-stopped inner Sinkhorn must land where the full budget lands."""
    prob = _problem()
    full = solve(prob, DenseGWSolver(outer_iters=5, inner_iters=400))
    tolled = solve(prob, DenseGWSolver(outer_iters=5, inner_iters=400,
                                       inner_tol=1e-8))
    np.testing.assert_allclose(np.asarray(tolled.coupling),
                               np.asarray(full.coupling), atol=1e-5)


# ---------------------------------------------------------------------------
# registry + front door conveniences
# ---------------------------------------------------------------------------

def test_registry_contents():
    names = available_solvers()
    assert {"spar_gw", "dense_gw", "grid_gw"} <= set(names)
    assert get_solver("spar_gw") is SparGWSolver
    with pytest.raises(KeyError, match="unknown solver"):
        get_solver("nope")


def test_registry_extensible():
    @register_solver("test_only_solver")
    class TestOnlySolver(DenseGWSolver):
        pass
    try:
        assert get_solver("test_only_solver") is TestOnlySolver
        with pytest.raises(ValueError, match="already registered"):
            register_solver("test_only_solver")(TestOnlySolver)
        # register_solver must make the class jit-able as a pytree arg:
        # solving through the front door with the custom solver works
        out = solve(_problem(), TestOnlySolver(outer_iters=2, inner_iters=5))
        assert np.isfinite(float(out.value))
    finally:
        from repro.api import solvers as _solvers
        _solvers._REGISTRY.pop("test_only_solver")


def test_solve_by_name():
    out = solve(_problem(), "dense_gw")
    assert np.isfinite(float(out.value))


def test_select_solver_heuristic():
    """solver=None auto-selects from problem structure (ROADMAP item)."""
    from repro import DenseGWSolver as D
    from repro import QuantizedGWSolver as Q
    from repro import SparGWSolver as S
    from repro import select_solver

    def shaped(n, **kw):
        a = jnp.ones(n) / n
        g = Geometry(jnp.zeros((n, n)), a, validate=False)
        return QuadraticProblem(g, g, validate=False, **kw)

    assert isinstance(select_solver(shaped(100)), D)
    mid = select_solver(shaped(1000))
    assert isinstance(mid, S) and mid.s == 16 * 1000
    assert isinstance(select_solver(shaped(4000)), Q)
    # unbalanced problems route by size like balanced ones (spar's
    # O((16n)²) assembly is infeasible at scale; quantized handles lam)
    assert isinstance(select_solver(shaped(4000, lam=1.0)), Q)
    assert isinstance(select_solver(shaped(1000, lam=1.0)), S)
    # fused structure routes like balanced
    assert isinstance(
        select_solver(shaped(100, M=jnp.zeros((100, 100)),
                             fused_penalty=0.5)), D)


def test_select_solver_lowrank_rung():
    """The low-rank rung of the auto-routing ladder: factorizable
    point-cloud problems above the spar threshold, and any eligible
    problem above _LOWRANK_MIN, route to lowrank_gw."""
    from repro import LowRankGWSolver as L
    from repro import QuantizedGWSolver as Q
    from repro import SparGWSolver as S
    from repro import select_solver
    from repro.api.solve import _LOWRANK_MIN

    def cloud(n, loss="l2", **kw):
        a = jnp.ones(n) / n
        g = Geometry(None, a, points=jnp.zeros((n, 2)), validate=False)
        return QuadraticProblem(g, g, loss=loss, validate=False, **kw)

    def shaped(n, **kw):
        a = jnp.ones(n) / n
        g = Geometry(jnp.zeros((n, n)), a, validate=False)
        return QuadraticProblem(g, g, validate=False, **kw)

    # point clouds: lowrank as soon as spar's O(s²) stops paying off
    assert isinstance(select_solver(cloud(4000)), L)
    # ... but below the spar threshold the existing ladder is untouched
    assert isinstance(select_solver(cloud(1000)), S)
    # dense-cost problems keep quantized until _LOWRANK_MIN
    assert isinstance(select_solver(shaped(4000)), Q)
    assert isinstance(select_solver(shaped(_LOWRANK_MIN + 1)), L)
    # structure lowrank can't handle stays on quantized at any size
    assert isinstance(select_solver(cloud(4000, lam=1.0)), Q)
    assert isinstance(select_solver(shaped(_LOWRANK_MIN + 1, lam=1.0)), Q)
    assert isinstance(select_solver(cloud(4000, loss="l1")), Q)
    # kl point clouds can't use the exact factorization (it needs
    # squared-euclidean h), so they wait for the _LOWRANK_MIN threshold
    assert isinstance(select_solver(cloud(4000, loss="kl")), Q)
    assert isinstance(select_solver(cloud(_LOWRANK_MIN + 1, loss="kl")), L)
    big_M = jnp.zeros((4000, 4000))
    assert isinstance(
        select_solver(cloud(4000, M=big_M, fused_penalty=0.5)), Q)


def test_solve_with_no_solver_auto_selects():
    out = solve(_problem())          # N=20 -> dense_gw, no key needed
    ref = solve(_problem(), DenseGWSolver.default_config(N))
    np.testing.assert_array_equal(np.asarray(out.value),
                                  np.asarray(ref.value))


def test_solver_requires_key_and_support():
    prob = _problem()
    with pytest.raises(ValueError, match="PRNGKey"):
        solve(prob, SparGWSolver(s=64))
    with pytest.raises(ValueError, match="support size"):
        solve(prob, SparGWSolver(), key=KEY)


def test_grid_solver_rejects_fused_unbalanced():
    prob = _problem(lam=1.0)
    with pytest.raises(NotImplementedError):
        solve(prob, GridGWSolver(s_r=8, s_c=8), key=KEY)


def test_coupling_todense_mass():
    out = solve(_problem(), SparGWSolver(s=4 * N, **FAST), key=KEY)
    dense = out.coupling.todense(N, N)
    np.testing.assert_allclose(float(dense.sum()),
                               float(out.coupling.vals.sum()), rtol=1e-6)
    assert dense.shape == (N, N)


def test_fused_features_derive_linear_term():
    """Feature geometries (no explicit M) produce the squared-euclidean M."""
    fx = jax.random.normal(jax.random.PRNGKey(3), (N, 3))
    fy = jax.random.normal(jax.random.PRNGKey(4), (N, 3))
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    Cx, Cy = _cloud(kx, N), _cloud(ky, N, scale=1.2)
    a = jnp.ones(N) / N
    M = jnp.sum((fx[:, None, :] - fy[None, :, :]) ** 2, -1)
    p_feat = QuadraticProblem(Geometry(Cx, a, features=fx),
                              Geometry(Cy, a, features=fy),
                              fused_penalty=0.6)
    p_M = QuadraticProblem(Geometry(Cx, a), Geometry(Cy, a),
                           M=M, fused_penalty=0.6)
    o1 = solve(p_feat, SparGWSolver(s=4 * N, **FAST), key=KEY)
    o2 = solve(p_M, SparGWSolver(s=4 * N, **FAST), key=KEY)
    np.testing.assert_allclose(float(o1.value), float(o2.value), rtol=1e-5)


# ---------------------------------------------------------------------------
# SPAR-UGW start
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss", ["l2", "kl", "l1"])
def test_unbalanced_start_cost_runs_at_highest(loss):
    """Alg. 3's start cost C(T⁰) sets the eq.-9 sampling law, so every
    product outside the per-step cost (``gw.cost``) is lowered at
    HIGHEST: at default precision a TPU rounds it to one bfloat16 pass."""
    prob = _problem(loss=loss, lam=1.0)
    text = jax.jit(lambda p: solve(p, SparGWSolver(s=4 * N, **FAST),
                                   key=KEY).value).lower(prob).as_text(
                                       debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    dots = re.findall(r"stablehlo\.dot_general .*precision = \[(\w+), \w+\]"
                      r".* loc\((#loc\d+)\)", text)
    start = [prec for prec, loc in dots if "gw.cost" not in locs.get(loc, "")]
    assert start and all(prec == "HIGHEST" for prec in start), dots
