"""Differentiable-GW suite (src/repro/diff/, DESIGN.md §11).

Ground truth comes from two independent references:

* **finite differences** of the solver's own value (x64, directional,
  central) — validates the Danskin envelope against the actual
  optimization landscape;
* **unrolled autodiff** (diff/unrolled.py) — backprop through every
  iteration of a faithful lax.scan replay; exact for the fixed-budget
  value function regardless of convergence.

Gradient quality is gated on convergence (an unconverged fixed point
breaks Danskin's premise), so the FD configs below run generous budgets
with tol=0/inner_tol=0; the measured rel errors are ~1e-6 (dense),
~1e-9 (lowrank, anchors init), ~1e-5 (spar vs unrolled, x64) and
~5e-4 (spar vs f32 FD) — the assertions leave real headroom.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.geometry import Geometry
from repro.api.problem import QuadraticProblem
from repro.api.solvers import DenseGWSolver, SparGWSolver
from repro.diff import envelope_loop, fgw_loss, gw_barycenter, gw_loss, \
    quadratic_loss
from repro.diff.unrolled import unrolled_value
from repro.lowrank.solver import LowRankGWSolver

REL_TOL = 1e-3


# ---------------------------------------------------------------- helpers

def _clouds(n, m, pert, seed):
    """Near-isometric pair: y = rotation of x + noise, truncated to m.

    Well-conditioned on purpose — the FD assertions need the solver to
    actually reach its fixed point inside the test budget.
    """
    key = jax.random.PRNGKey(seed)
    kx, kp = jax.random.split(key)
    x = jax.random.normal(kx, (n, 2))
    th = 0.7
    R = jnp.array([[jnp.cos(th), -jnp.sin(th)], [jnp.sin(th), jnp.cos(th)]])
    y = (x @ R.T + pert * jax.random.normal(kp, (n, 2)))[:m]
    return x, y


def _sqdist(z):
    s = jnp.sum(z * z, axis=1)
    return jnp.maximum(s[:, None] + s[None, :] - 2.0 * z @ z.T, 0.0)


def _uniform(k):
    return jnp.full((k,), 1.0 / k)


def _fd(f, x, d, h=1e-6):
    """Central directional derivative of scalar f at x along d."""
    return float((f(x + h * d) - f(x - h * d)) / (2.0 * h))


def _rel(u, v):
    return abs(u - v) / max(abs(u), abs(v), 1e-12)


def _sym_dir(rng, n):
    D = rng.standard_normal((n, n))
    return jnp.asarray((D + D.T) / 2.0)


# ------------------------------------------------- dense: FD + unrolled

class TestDenseGradient:
    """Envelope gradient of the dense prox solve vs FD and unrolling."""

    def _setup(self):
        n = 10
        x, y = _clouds(n, n, 0.1, 0)
        a, b = _uniform(n), _uniform(n)
        Cx, Cy = _sqdist(x), _sqdist(y)
        solver = DenseGWSolver(epsilon=2e-2, outer_iters=300,
                               inner_iters=400, tol=0.0, inner_tol=0.0)

        def value(Cx_):
            p = QuadraticProblem(Geometry(Cx_, a, validate=False),
                                 Geometry(Cy, b, validate=False),
                                 validate=False)
            return solver.run(p).value

        def value_unrolled(Cx_):
            p = QuadraticProblem(Geometry(Cx_, a, validate=False),
                                 Geometry(Cy, b, validate=False),
                                 validate=False)
            return unrolled_value(p, solver)

        return Cx, value, value_unrolled, n

    def test_matches_fd_and_unrolled(self):
        with jax.enable_x64():
            Cx, value, value_unrolled, n = self._setup()
            D = _sym_dir(np.random.default_rng(0), n)
            an = float(jnp.sum(jax.grad(value)(Cx) * D))
            an_unrolled = float(jnp.sum(jax.grad(value_unrolled)(Cx) * D))
            fd = _fd(value, Cx, D)
            assert _rel(an, fd) <= REL_TOL, (an, fd)
            assert _rel(an, an_unrolled) <= REL_TOL, (an, an_unrolled)

    def test_unrolled_forward_matches_solver(self):
        # faithfulness contract: same budget, same trajectory
        with jax.enable_x64():
            Cx, value, value_unrolled, _ = self._setup()
            np.testing.assert_allclose(float(value(Cx)),
                                       float(value_unrolled(Cx)), rtol=1e-10)


# ---------------------------------------------- spar: unrolled + FD

class TestSparGradient:
    """spar_gw: the envelope vs backprop through the *actual*
    ``_spar_pga_step`` (bitwise-identical forward trajectory).

    Two regimes, one per reference:

    * **unrolled parity** runs in x64 at a small budget — the measured
      gap (~1e-5) is the Danskin residual of the not-yet-settled fixed
      point, and x64 keeps the 400-step backprop accumulation from
      overflowing (the same unrolled backward is NaN in f32);
    * **FD** runs in f32 at the full production budget: the importance
      sampler's index draws shift under x64 (the importance weights
      change in the low bits), so x64 FD compares *different sparse
      patterns* and stalls at ~3e-2, while converged f32 reaches ~8e-4.
    """

    def _setup(self, outer, inner):
        n, m = 14, 11
        x, y = _clouds(n, m, 0.25, 1)
        a, b = _uniform(n), _uniform(m)
        # /10: keeps the inner Sinkhorn convergent at ε = 5e-2
        Cx, Cy = _sqdist(x) / 10.0, _sqdist(y) / 10.0
        key = jax.random.PRNGKey(5)
        solver = SparGWSolver(epsilon=5e-2, s=16 * n, outer_iters=outer,
                              inner_iters=inner, tol=0.0, inner_tol=0.0)

        def value(Cx_):
            p = QuadraticProblem(Geometry(Cx_, a, validate=False),
                                 Geometry(Cy, b, validate=False),
                                 validate=False)
            return solver.run(p, key).value

        def value_unrolled(Cx_):
            p = QuadraticProblem(Geometry(Cx_, a, validate=False),
                                 Geometry(Cy, b, validate=False),
                                 validate=False)
            return unrolled_value(p, solver, key)

        return Cx, value, value_unrolled, n

    def test_matches_unrolled(self):
        with jax.enable_x64():
            Cx, value, value_unrolled, n = self._setup(100, 300)
            D = _sym_dir(np.random.default_rng(1), n)
            an = float(jnp.sum(jax.grad(value)(Cx) * D))
            an_unrolled = float(jnp.sum(jax.grad(value_unrolled)(Cx) * D))
            assert _rel(an, an_unrolled) <= REL_TOL, (an, an_unrolled)

    def test_matches_fd(self):
        Cx, value, _, n = self._setup(400, 1000)
        D = _sym_dir(np.random.default_rng(1), n).astype(jnp.float32)
        an = float(jnp.sum(jax.grad(value)(Cx) * D))
        # large h: the value has an f32 noise floor, and FD noise
        # scales as 1/h (measured rel 5e-4 at h=5e-3, vs 8e-4 at 1e-3)
        fd = _fd(jax.jit(value), Cx, D, h=5e-3)
        assert _rel(an, fd) <= 2e-3, (an, fd)

    def test_unrolled_forward_matches_solver(self):
        with jax.enable_x64():
            Cx, value, value_unrolled, _ = self._setup(100, 300)
            np.testing.assert_allclose(float(value(Cx)),
                                       float(value_unrolled(Cx)), rtol=1e-10)

    def test_rejects_inner_tol(self):
        solver = SparGWSolver(inner_tol=1e-5)
        x, y = _clouds(8, 8, 0.2, 0)
        p = QuadraticProblem(Geometry.from_points(x, _uniform(8)),
                             Geometry.from_points(y, _uniform(8)))
        with pytest.raises(ValueError, match="inner_tol"):
            unrolled_value(p, solver, jax.random.PRNGKey(0))


# -------------------------------------------- lowrank: FD + unrolled

class TestLowRankGradient:
    def _setup(self, outer=600):
        n = 11
        x, y = _clouds(n, n, 0.25, 3)
        a, b = _uniform(n), _uniform(n)
        key = jax.random.PRNGKey(7)
        solver = LowRankGWSolver(rank=3, outer_iters=outer, inner_iters=150,
                                 tol=0.0, inner_tol=0.0, init="anchors")

        def value(x_):
            p = QuadraticProblem(Geometry.from_points(x_, a, validate=False),
                                 Geometry.from_points(y, b, validate=False),
                                 validate=False)
            return solver.run(p, key).value

        def value_unrolled(x_):
            p = QuadraticProblem(Geometry.from_points(x_, a, validate=False),
                                 Geometry.from_points(y, b, validate=False),
                                 validate=False)
            return unrolled_value(p, solver, key)

        return x, value, value_unrolled

    def test_matches_fd_and_unrolled(self):
        with jax.enable_x64():
            x, value, value_unrolled = self._setup()
            D = jnp.asarray(np.random.default_rng(2).standard_normal(x.shape))
            an = float(jnp.sum(jax.grad(value)(x) * D))
            an_unrolled = float(jnp.sum(jax.grad(value_unrolled)(x) * D))
            fd = _fd(value, x, D)
            assert _rel(an, fd) <= REL_TOL, (an, fd)
            assert _rel(an, an_unrolled) <= REL_TOL, (an, an_unrolled)

    def test_grad_never_materializes_mn(self):
        """The whole grad jaxpr — anchors init, MD loop, value, backward
        contraction — must never hold an m×n (or n×m) array."""
        m, n = 37, 41
        x, y = _clouds(m, m, 0.2, 0)[0], _clouds(n, n, 0.2, 1)[0]
        a, b = _uniform(m), _uniform(n)
        solver = LowRankGWSolver(rank=3, outer_iters=5, inner_iters=8,
                                 init="anchors")

        def value(x_):
            p = QuadraticProblem(Geometry.from_points(x_, a, validate=False),
                                 Geometry.from_points(y, b, validate=False),
                                 validate=False)
            return solver.run(p, jax.random.PRNGKey(0)).value

        jaxpr = jax.make_jaxpr(jax.grad(value))(x)
        bad = [shape for shape in _all_shapes(jaxpr.jaxpr)
               if (m, n) == shape[-2:] or (n, m) == shape[-2:]]
        assert not bad, f"m×n avals in grad jaxpr: {bad[:5]}"


def _all_shapes(jaxpr):
    """Every aval shape in a jaxpr, recursing into sub-jaxprs (scan,
    custom_vjp calls, closed calls...)."""
    for v in (*jaxpr.invars, *jaxpr.constvars, *jaxpr.outvars):
        if hasattr(v, "aval") and hasattr(v.aval, "shape"):
            yield tuple(v.aval.shape)
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                yield tuple(v.aval.shape)
        for val in eqn.params.values():
            yield from _shapes_in(val)


def _shapes_in(val):
    if hasattr(val, "jaxpr"):                      # ClosedJaxpr
        yield from _all_shapes(val.jaxpr)
    elif hasattr(val, "eqns"):                     # raw Jaxpr
        yield from _all_shapes(val)
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _shapes_in(item)


# ------------------------------------------------ composition: vmap/jit

class TestComposition:
    def _loss(self):
        n = 9
        _, y = _clouds(n, n, 0.2, 4)
        solver = DenseGWSolver(epsilon=5e-2, outer_iters=40, inner_iters=60,
                               tol=0.0, inner_tol=0.0)

        def f(x_):
            return gw_loss(x_, y, solver=solver)
        return f, n

    def _batch(self, n, B=3):
        return jnp.stack([_clouds(n, n, 0.3, 10 + i)[0] for i in range(B)])

    def test_vmap_of_grad_matches_stacked(self):
        f, n = self._loss()
        xs = self._batch(n)
        batched = jax.vmap(jax.grad(f))(xs)
        single = jnp.stack([jax.grad(f)(x) for x in xs])
        np.testing.assert_allclose(np.asarray(batched), np.asarray(single),
                                   rtol=2e-4, atol=1e-6)

    def test_grad_of_vmap_matches_stacked(self):
        f, n = self._loss()
        xs = self._batch(n)
        g = jax.grad(lambda xs_: jnp.sum(jax.vmap(f)(xs_)))(xs)
        single = jnp.stack([jax.grad(f)(x) for x in xs])
        np.testing.assert_allclose(np.asarray(g), np.asarray(single),
                                   rtol=2e-4, atol=1e-6)

    def test_jit_grad_matches_eager(self):
        f, n = self._loss()
        x = _clouds(n, n, 0.3, 20)[0]
        eager = jax.grad(f)(x)
        jitted = jax.jit(jax.grad(f))(x)
        np.testing.assert_allclose(np.asarray(jitted), np.asarray(eager),
                                   rtol=1e-4, atol=1e-6)
        assert bool(jnp.all(jnp.isfinite(jitted)))

    def test_grad_with_trace_and_health(self):
        # envelope must coexist with trace buffers and rescue machinery
        n = 8
        x, y = _clouds(n, n, 0.2, 6)
        solver = DenseGWSolver(epsilon=5e-2, outer_iters=30, inner_iters=40,
                               trace=True, max_rescues=2)

        def f(x_):
            p = QuadraticProblem(Geometry.from_points(x_, _uniform(n)),
                                 Geometry.from_points(y, _uniform(n)),
                                 validate=False)
            return quadratic_loss(p, solver)

        g = jax.grad(f)(x)
        assert bool(jnp.all(jnp.isfinite(g)))


# ------------------------------------------- fused / marginals / lam

class TestFusedAndMarginals:
    def test_fgw_feature_and_alpha_grads_match_fd(self):
        with jax.enable_x64():
            n = 10
            x, y = _clouds(n, n, 0.1, 0)
            kf = jax.random.PRNGKey(9)
            fx = jax.random.normal(kf, (n, 3))
            fy = jax.random.normal(jax.random.fold_in(kf, 1), (n, 3))
            # ε = 5e-2 (not 2e-2): the fused fixed point settles an
            # order of magnitude faster, rel ~5e-4 inside this budget
            solver = DenseGWSolver(epsilon=5e-2, outer_iters=300,
                                   inner_iters=400, tol=0.0, inner_tol=0.0)

            def f(fx_, alpha):
                return fgw_loss(x, y, fx_, fy, fused_penalty=alpha,
                                solver=solver)

            D = jnp.asarray(np.random.default_rng(3).standard_normal(
                fx.shape))
            gfx, galpha = jax.grad(f, argnums=(0, 1))(fx, 0.6)
            an_f = float(jnp.sum(gfx * D))
            fd_f = _fd(lambda z: f(z, 0.6), fx, D)
            fd_a = _fd(lambda t: f(fx, t), jnp.asarray(0.6),
                       jnp.asarray(1.0))
            assert _rel(an_f, fd_f) <= REL_TOL, (an_f, fd_f)
            assert _rel(float(galpha), fd_a) <= REL_TOL, (galpha, fd_a)

    def test_unbalanced_marginal_and_lam_grads_match_fd(self):
        """Unbalanced marginals/lam are *live* envelope paths (the KL
        penalties read (a, b) in the value recompute): exact, FD to
        ~1e-10 at any budget."""
        with jax.enable_x64():
            n = 10
            x, y = _clouds(n, n, 0.4, 11)
            Cx, Cy = _sqdist(x), _sqdist(y)
            b = _uniform(n)
            solver = DenseGWSolver(epsilon=5e-2, outer_iters=300,
                                   inner_iters=400, tol=0.0, inner_tol=0.0)

            def f(a_, lam):
                p = QuadraticProblem(Geometry(Cx, a_, validate=False),
                                     Geometry(Cy, b, validate=False),
                                     lam=lam, validate=False)
                return quadratic_loss(p, solver)

            a = _uniform(n)
            da = jnp.asarray(
                np.random.default_rng(5).standard_normal(n) * 0.3)
            ga, glam = jax.grad(f, argnums=(0, 1))(a, jnp.asarray(1.0))
            an_a = float(jnp.sum(ga * da))
            fd_a = _fd(lambda a_: f(a_, 1.0), a, da)
            fd_l = _fd(lambda t: f(a, t), jnp.asarray(1.0),
                       jnp.asarray(1.0))
            assert _rel(an_a, fd_a) <= REL_TOL, (an_a, fd_a)
            assert _rel(float(glam), fd_l) <= REL_TOL, (glam, fd_l)

    def test_balanced_marginal_certificate(self):
        """Balanced marginal_grads: primal-zero (value bit-unchanged)
        and a finite nonzero zero-sum certificate direction. FD parity
        is NOT asserted — at sparse prox fixed points the computed
        value's marginal sensitivity is support-jump dominated (see
        DESIGN.md §11); the unbalanced path above is the exact one."""
        n = 10
        x, y = _clouds(n, n, 0.6, 11)
        Cx, Cy = _sqdist(x), _sqdist(y)
        a, b = _uniform(n), _uniform(n)
        solver = DenseGWSolver(epsilon=5e-2, outer_iters=100,
                               inner_iters=150, tol=0.0, inner_tol=0.0)

        def f(a_, with_duals):
            p = QuadraticProblem(Geometry(Cx, a_, validate=False),
                                 Geometry(Cy, b, validate=False),
                                 validate=False)
            return quadratic_loss(p, solver, marginal_grads=with_duals)

        np.testing.assert_allclose(float(f(a, True)), float(f(a, False)),
                                   rtol=1e-6)
        ga = jax.grad(lambda a_: f(a_, True))(a)
        assert bool(jnp.all(jnp.isfinite(ga)))
        # a nonzero certificate, and zero along the mass gauge direction
        centered = ga - jnp.mean(ga)
        assert float(jnp.sum(jnp.abs(centered))) > 0.0

    def test_marginal_grads_guardrails(self):
        n = 6
        x, y = _clouds(n, n, 0.2, 0)
        p = QuadraticProblem(Geometry.from_points(x, _uniform(n)),
                             Geometry.from_points(y, _uniform(n)))
        with pytest.raises(ValueError, match="prox"):
            quadratic_loss(p, DenseGWSolver(reg="ent"), marginal_grads=True)
        p_unbal = QuadraticProblem(Geometry.from_points(x, _uniform(n)),
                                   Geometry.from_points(y, _uniform(n)),
                                   lam=1.0)
        with pytest.raises(ValueError, match="balanced"):
            quadratic_loss(p_unbal, DenseGWSolver(),
                           marginal_grads=True)

    def test_unbalanced_grads_finite(self):
        # unbalanced marginal/lam gradients flow through the KL terms
        n = 8
        x, y = _clouds(n, n, 0.2, 7)
        Cx, Cy = _sqdist(x), _sqdist(y)
        solver = DenseGWSolver(epsilon=5e-2, outer_iters=40, inner_iters=60)

        def f(a_, lam):
            p = QuadraticProblem(Geometry(Cx, a_, validate=False),
                                 Geometry(Cy, _uniform(n), validate=False),
                                 lam=lam, validate=False)
            return quadratic_loss(p, solver)

        ga, glam = jax.grad(f, argnums=(0, 1))(_uniform(n), jnp.asarray(1.0))
        assert bool(jnp.all(jnp.isfinite(ga)))
        assert bool(jnp.isfinite(glam))
        assert float(jnp.sum(jnp.abs(ga))) > 0.0


# ------------------------------------------------------- barycenter

class TestBarycenter:
    def test_descends_and_is_finite(self):
        x1, _ = _clouds(16, 16, 0.1, 0)
        x2, _ = _clouds(14, 14, 0.1, 1)
        solver = DenseGWSolver(epsilon=5e-2, outer_iters=60, inner_iters=80,
                               tol=0.0, inner_tol=0.0)
        res = gw_barycenter([x1, x2], n_points=12, key=jax.random.PRNGKey(2),
                            solver=solver, steps=12, lr=0.05)
        objs = np.asarray(res.objectives)
        assert res.points.shape == (12, 2)
        assert np.all(np.isfinite(objs))
        assert np.all(np.isfinite(np.asarray(res.grad_norms)))
        assert objs[-1] < objs[0], objs

    def test_needs_dim_for_cost_inputs(self):
        C = _sqdist(_clouds(8, 8, 0.2, 0)[0])
        g = Geometry(C, _uniform(8), validate=False)
        with pytest.raises(ValueError, match="dim"):
            gw_barycenter([g, g], n_points=6, key=jax.random.PRNGKey(0),
                          steps=1)


# ----------------------------------------------- learned ground cost

class TestLearnedCost:
    def test_mlp_ground_cost_trains(self):
        """fgw_loss with model-produced features: grads reach the MLP
        params and a few AdamW steps reduce the loss (worked example in
        EXPERIMENTS.md §PR10)."""
        from repro.models.layers import mlp, mlp_params
        from repro.models.module import Builder
        from repro.optim import adamw

        n = 10
        x, y = _clouds(n, n, 0.15, 8)
        params = mlp_params(Builder("init", jax.random.PRNGKey(0)), 2, 8)
        solver = DenseGWSolver(epsilon=5e-2, outer_iters=60, inner_iters=80,
                               tol=0.0, inner_tol=0.0)

        def loss_fn(p):
            return fgw_loss(x, y, mlp(p, x), mlp(p, y), fused_penalty=0.5,
                            solver=solver)

        value_and_grad = jax.jit(jax.value_and_grad(loss_fn))
        opt = adamw.init(params)
        losses = []
        p = params
        for _ in range(6):
            value, grads = value_and_grad(p)
            losses.append(float(value))
            assert all(bool(jnp.all(jnp.isfinite(g)))
                       for g in jax.tree.leaves(grads))
            p, opt, _ = adamw.update(grads, opt, p, 3e-3, weight_decay=0.0)
        assert losses[-1] < losses[0], losses


# -------------------------------------------------- envelope plumbing

class TestEnvelopePlumbing:
    def test_primal_identical_to_health_loop(self):
        """The envelope is gradient-only: forward results must be
        leaf-for-leaf identical to calling health_loop directly."""
        from repro.health.loop import health_loop

        c = jnp.asarray([1.0, -2.0, 3.0])

        def step(T):
            return 0.5 * (T + c)

        def err(T):
            return jnp.sum(jnp.abs(T - c))

        T0 = jnp.zeros(3)
        ref = health_loop(step, err, T0, 50, 1e-6)
        env = envelope_loop(step, err, T0, 50, 1e-6)
        for r, e in zip(jax.tree.leaves(ref), jax.tree.leaves(env)):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(e))

    def test_anchor_init_is_feasible(self):
        from repro.lowrank.init import anchor_init

        n, m, r = 23, 17, 4
        x, y = _clouds(n, n, 0.3, 9)[0], _clouds(m, m, 0.3, 10)[0]
        a, b = _uniform(n), _uniform(m)
        p = QuadraticProblem(Geometry.from_points(x, a, validate=False),
                             Geometry.from_points(y, b, validate=False),
                             validate=False)
        Q, R, g = anchor_init(jax.random.PRNGKey(0), p, r)
        np.testing.assert_allclose(np.asarray(Q.sum(axis=1)), np.asarray(a),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(R.sum(axis=1)), np.asarray(b),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(Q.sum(axis=0)),
                                   np.asarray(g), rtol=1e-5)
        # R's column sums inherit the anchor coupling's residual marginal
        # error (tiny budgeted r×r solve) — Dykstra's first projection
        # absorbs it; just require it to be small
        np.testing.assert_allclose(np.asarray(R.sum(axis=0)),
                                   np.asarray(g), rtol=5e-2)
        assert float(Q.min()) > 0 and float(R.min()) > 0 and float(g.min()) > 0

    def test_lowrank_init_registry_guard(self):
        x, y = _clouds(8, 8, 0.2, 0)
        p = QuadraticProblem(Geometry.from_points(x, _uniform(8)),
                             Geometry.from_points(y, _uniform(8)))
        with pytest.raises(ValueError, match="init"):
            LowRankGWSolver(init="bogus").run(p, jax.random.PRNGKey(0))
