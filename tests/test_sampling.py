"""core/sampling.py edge cases: support sizes that don't divide chunk/block
sizes, near-degenerate marginal weights, and duplicate sampled pairs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import sampling

KEY = jax.random.PRNGKey(0)


def _cloud(key, n, scale=1.0):
    x = jax.random.normal(key, (n, 2)) * scale
    return jnp.sqrt(jnp.sum((x[:, None] - x[None, :]) ** 2, -1))


def _problem(n, a=None, b=None):
    kx, ky = jax.random.split(KEY)
    if a is None:
        a = b = jnp.ones(n) / n
    return repro.QuadraticProblem(
        repro.Geometry(_cloud(kx, n), a),
        repro.Geometry(_cloud(ky, n, 1.2), b))


# ---------------------------------------------------------------------------
# s not a block/chunk multiple
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [1, 7, 37])
def test_sample_pairs_odd_sizes(s):
    n = 20
    a = b = jnp.ones(n) / n
    probs = sampling.balanced_probs(a, b)
    rows, cols = sampling.sample_pairs(KEY, probs, s)
    assert rows.shape == cols.shape == (s,)
    assert int(rows.min()) >= 0 and int(rows.max()) < n
    p = probs.pair_prob(rows, cols)
    assert bool(jnp.all(p > 0))


def test_spar_solve_with_non_chunk_multiple_support():
    """End-to-end: s=37 with cost_chunk=16 (37 % 16 != 0) must work and
    match the same solve with a divisible chunk."""
    n = 16
    prob = _problem(n)
    out_a = repro.solve(prob, repro.SparGWSolver(
        s=37, cost_chunk=16, outer_iters=3, inner_iters=10), key=KEY)
    out_b = repro.solve(prob, repro.SparGWSolver(
        s=37, cost_chunk=37, outer_iters=3, inner_iters=10), key=KEY)
    assert out_a.coupling.vals.shape == (37,)
    np.testing.assert_allclose(np.asarray(out_a.coupling.vals),
                               np.asarray(out_b.coupling.vals),
                               rtol=1e-5, atol=1e-8)


# ---------------------------------------------------------------------------
# near-degenerate marginal weights
# ---------------------------------------------------------------------------

def test_balanced_probs_near_degenerate_weights():
    """One point carries ~all mass; the rest are ~1e-12. Probabilities must
    stay finite, normalized, and (with shrink) bounded away from zero."""
    n = 30
    a = jnp.full((n,), 1e-12).at[0].set(1.0)
    a = a / a.sum()
    b = jnp.ones(n) / n
    probs = sampling.balanced_probs(a, b)
    assert bool(jnp.all(jnp.isfinite(probs.pa)))
    np.testing.assert_allclose(float(probs.pa.sum()), 1.0, rtol=1e-5)
    rows, cols = sampling.sample_pairs(KEY, probs, 64)
    assert bool(jnp.all((rows >= 0) & (rows < n)))
    # shrink enforces the regularity floor p_i >= shrink/n (H.4)
    shrunk = sampling.balanced_probs(a, b, shrink=0.1)
    assert float(shrunk.pa.min()) >= 0.1 / n - 1e-9


def test_degenerate_weights_solve_is_finite():
    n = 24
    a = jnp.full((n,), 1e-10).at[3].set(1.0)
    a = a / a.sum()
    prob = _problem(n, a=a, b=jnp.ones(n) / n)
    out = repro.solve(prob, repro.SparGWSolver(
        s=8 * n, shrink=0.1, outer_iters=5, inner_iters=50), key=KEY)
    assert np.isfinite(float(out.value))
    assert bool(jnp.all(jnp.isfinite(out.coupling.vals)))


def test_unbalanced_probs_extreme_logk():
    """unbalanced_probs takes log K; a huge dynamic range must not NaN."""
    n = 10
    a = b = jnp.ones(n) / n
    logK = jnp.linspace(-500.0, 0.0, n * n).reshape(n, n)
    P = sampling.unbalanced_probs(a, b, logK, lam=1.0, eps=1e-2)
    assert bool(jnp.all(jnp.isfinite(P)))
    np.testing.assert_allclose(float(P.sum()), 1.0, rtol=1e-5)
    rows, cols = sampling.sample_pairs_2d(KEY, P, 16)
    assert rows.shape == (16,)


# ---------------------------------------------------------------------------
# duplicate sampled pairs
# ---------------------------------------------------------------------------

def test_duplicate_pairs_semantics():
    """n tiny, s large → duplicates guaranteed. Duplicates are parallel
    importance-sampling draws: todense must merge them by summation and
    conserve the coupling mass."""
    n, s = 4, 64
    prob = _problem(n)
    out = repro.solve(prob, repro.SparGWSolver(
        s=s, outer_iters=5, inner_iters=50), key=KEY)
    rows = np.asarray(out.coupling.rows)
    cols = np.asarray(out.coupling.cols)
    assert len(set(zip(rows.tolist(), cols.tolist()))) < s   # duplicates exist
    dense = out.coupling.todense(n, n)
    np.testing.assert_allclose(float(dense.sum()),
                               float(out.coupling.vals.sum()), rtol=1e-6)
    # dense coupling ~doubly stochastic up to solver tolerance
    assert float(jnp.abs(dense.sum(1) - prob.geom_x.weights).sum()) < 0.2


def test_sample_pairs_2d_duplicates_match_flat_probs():
    n = 3
    P = jnp.arange(1.0, n * n + 1).reshape(n, n)
    P = P / P.sum()
    rows, cols = sampling.sample_pairs_2d(KEY, P, 1000)
    freq = np.zeros((n, n))
    np.add.at(freq, (np.asarray(rows), np.asarray(cols)), 1.0 / 1000)
    np.testing.assert_allclose(freq, np.asarray(P), atol=0.05)


def test_sample_pairs_2d_draws_as_choice_over_the_flat_law():
    """The 2-D draw is ``jax.random.choice`` over the row-major law: the
    same key gives the same cells, but for a draw whose point falls
    within float32 rounding of a cell's edge (the CDF is summed per row,
    then over the row totals)."""
    m, n, s = 64, 48, 5000
    for seed in range(4):
        kp, kd = jax.random.split(jax.random.PRNGKey(seed))
        P = jnp.exp(3.0 * jax.random.normal(kp, (m, n)))
        P = P / P.sum()
        rows, cols = sampling.sample_pairs_2d(kd, P, s)
        flat = jax.random.choice(kd, m * n, (s,), p=P.reshape(-1))
        assert int(jnp.sum(rows * n + cols != flat)) <= s // 1000
