"""Sinkhorn solver unit + property tests (hypothesis)."""
import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _optional import given, settings, st  # guarded hypothesis import

from repro.core.sinkhorn import (
    segment_logsumexp,
    sinkhorn,
    sinkhorn_log,
    sinkhorn_unbalanced,
    sinkhorn_unbalanced_log,
    sparse_sinkhorn,
    sparse_sinkhorn_logdomain,
    sparse_sinkhorn_unbalanced_log,
)
from repro.obs import registry

# the module, not the ``repro.core.sinkhorn`` function of the same name
sk = importlib.import_module("repro.core.sinkhorn")

KEY = jax.random.PRNGKey(0)


def _simplex(key, n):
    x = jax.random.uniform(key, (n,)) + 0.1
    return x / x.sum()


def test_sinkhorn_marginals():
    m, n = 24, 17
    a = _simplex(KEY, m)
    b = _simplex(jax.random.PRNGKey(1), n)
    K = jax.random.uniform(jax.random.PRNGKey(2), (m, n)) + 0.05
    T = sinkhorn(a, b, K, 200)
    np.testing.assert_allclose(np.array(T.sum(1)), np.array(a), rtol=1e-4)
    np.testing.assert_allclose(np.array(T.sum(0)), np.array(b), rtol=1e-4)


def test_log_domain_matches_plain():
    m, n = 16, 16
    a = _simplex(KEY, m)
    b = _simplex(jax.random.PRNGKey(1), n)
    K = jax.random.uniform(jax.random.PRNGKey(2), (m, n)) + 0.05
    T1 = sinkhorn(a, b, K, 60)
    T2 = sinkhorn_log(a, b, jnp.log(K), 60)
    np.testing.assert_allclose(np.array(T1), np.array(T2), atol=1e-5)


def test_log_domain_survives_small_epsilon():
    """Plain domain underflows at eps=1e-3 with O(1) costs; log domain must
    still satisfy marginals."""
    m = 32
    a = _simplex(KEY, m)
    b = _simplex(jax.random.PRNGKey(1), m)
    C = jax.random.uniform(jax.random.PRNGKey(2), (m, m)) * 5.0
    T = sinkhorn_log(a, b, -C / 1e-3, 300)
    assert np.isfinite(np.array(T)).all()
    np.testing.assert_allclose(np.array(T.sum(0)), np.array(b), rtol=1e-3)


def test_unbalanced_log_matches_plain():
    m, n = 12, 14
    a = jax.random.uniform(KEY, (m,)) + 0.2
    b = jax.random.uniform(jax.random.PRNGKey(1), (n,)) + 0.2
    K = jax.random.uniform(jax.random.PRNGKey(2), (m, n)) + 0.1
    T1 = sinkhorn_unbalanced(a, b, K, 1.0, 0.1, 80)
    T2 = sinkhorn_unbalanced_log(a, b, jnp.log(K), 1.0, 0.1, 80)
    np.testing.assert_allclose(np.array(T1), np.array(T2), atol=1e-5)


def test_sparse_matches_dense_on_full_support():
    """COO Sinkhorn on the full index set == dense Sinkhorn."""
    m, n = 9, 7
    a = _simplex(KEY, m)
    b = _simplex(jax.random.PRNGKey(1), n)
    K = jax.random.uniform(jax.random.PRNGKey(2), (m, n)) + 0.05
    rows, cols = jnp.meshgrid(jnp.arange(m), jnp.arange(n), indexing="ij")
    rows, cols = rows.reshape(-1), cols.reshape(-1)
    vals = K[rows, cols]
    T_dense = sinkhorn(a, b, K, 100)
    t_sparse = sparse_sinkhorn(a, b, rows, cols, vals, m, n, 100)
    np.testing.assert_allclose(np.array(T_dense[rows, cols]),
                               np.array(t_sparse), rtol=1e-5, atol=1e-8)
    t_log = sparse_sinkhorn_logdomain(a, b, rows, cols, jnp.log(vals), m, n,
                                      100)
    np.testing.assert_allclose(np.array(t_sparse), np.array(t_log),
                               rtol=1e-4, atol=1e-7)


def test_segment_logsumexp_matches_dense():
    vals = jnp.array([0.0, 1.0, -2.0, 3.0, 0.5])
    segs = jnp.array([0, 0, 2, 2, 2])
    out = segment_logsumexp(vals, segs, 4)
    expect0 = np.logaddexp(0.0, 1.0)
    expect2 = np.log(np.exp(-2.0) + np.exp(3.0) + np.exp(0.5))
    assert np.allclose(out[0], expect0)
    assert np.allclose(out[2], expect2)
    assert out[1] < -1e29 and out[3] < -1e29  # empty segments


@pytest.mark.optional_dep("hypothesis")
@settings(max_examples=15, deadline=None)
@given(st.integers(4, 20), st.integers(4, 20), st.integers(0, 1000))
def test_property_marginals_and_nonnegativity(m, n, seed):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    a = _simplex(k1, m)
    b = _simplex(k2, n)
    K = jax.random.uniform(k3, (m, n)) + 0.05
    T = sinkhorn(a, b, K, 150)
    T = np.array(T)
    assert (T >= -1e-9).all()
    np.testing.assert_allclose(T.sum(0), np.array(b), rtol=5e-3)
    # scaling invariance: gamma*K gives the same coupling
    T2 = np.array(sinkhorn(a, b, 3.7 * K, 150))
    np.testing.assert_allclose(T, T2, rtol=1e-4, atol=1e-8)


# ---------------------------------------------------------------------------
# Log-domain sparse Sinkhorn: the dense-cell and COO layouts agree
# ---------------------------------------------------------------------------

def _coo_case(support, seed, m=12, n=10, s=80):
    """A COO problem: ``full`` is the whole grid; ``duplicates`` the whole
    grid plus s repeated draws, shuffled; ``dead`` draws s entries on the
    top-left block only, the last 3 rows and 2 columns padded with 1e-30
    weights and no support (as a served bucket's padding)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    a = _simplex(k[0], m)
    b = _simplex(k[1], n)
    r, c = jnp.meshgrid(jnp.arange(m), jnp.arange(n), indexing="ij")
    rows, cols = r.reshape(-1), c.reshape(-1)
    if support == "duplicates":
        rows = jnp.concatenate([rows, jax.random.randint(k[2], (s,), 0, m)])
        cols = jnp.concatenate([cols, jax.random.randint(k[3], (s,), 0, n)])
        perm = jax.random.permutation(k[0], rows.shape[0])
        rows, cols = rows[perm], cols[perm]
    elif support == "dead":
        lm, ln = m - 3, n - 2
        rows = jax.random.randint(k[2], (s,), 0, lm)
        cols = jax.random.randint(k[3], (s,), 0, ln)
        a = jnp.concatenate([_simplex(k[0], lm), jnp.full((3,), 1e-30)])
        b = jnp.concatenate([_simplex(k[1], ln), jnp.full((2,), 1e-30)])
    logvals = jax.random.normal(k[4], rows.shape) * 3.0
    return (a, b, rows, cols, logvals), m, n


@pytest.mark.parametrize("tol", [0.0, 1e-6])
@pytest.mark.parametrize("support", ["duplicates", "dead", "full"])
@pytest.mark.parametrize("rho", [None, 0.8], ids=["balanced", "unbalanced"])
def test_sparse_log_layouts_agree(rho, support, tol):
    """Both layouts of the log-domain sparse Sinkhorn return the same COO
    coupling, on a jitted 2-lane vmap whose lanes differ."""
    lanes = [_coo_case(support, seed) for seed in (0, 1)]
    (_, m, n) = lanes[0]
    args = [jnp.stack(x) for x in zip(*(lane[0] for lane in lanes))]
    out = {}
    for name, layout in (("coo", sk._sinkhorn_coo),
                         ("dense", sk._sinkhorn_dense)):
        fn = partial(layout, m=m, n=n, iters=100, tol=tol, rho=rho)
        out[name] = np.asarray(jax.jit(jax.vmap(fn))(*args))
    assert np.isfinite(out["dense"]).all()
    np.testing.assert_allclose(out["dense"], out["coo"], rtol=1e-5)


@pytest.mark.parametrize("eps", [1e-4, 1e-5])
def test_sparse_log_layouts_agree_at_tiny_epsilon(eps):
    """The tiny-ε case of test_health: potentials near 1/ε, duplicates and
    empty cells, the while path."""
    n, s = 16, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    rows = jax.random.randint(k1, (s,), 0, n)
    cols = jax.random.randint(k2, (s,), 0, n)
    C = jax.random.uniform(k3, (s,)) * 4.0
    a = jnp.full((n,), 1e-4).at[0].set(1.0 - (n - 1) * 1e-4)
    b = jnp.ones(n) / n
    t_coo, t_dense = (np.asarray(layout(a, b, rows, cols, -C / eps, n, n,
                                        200, 1e-9))
                      for layout in (sk._sinkhorn_coo, sk._sinkhorn_dense))
    assert np.isfinite(t_dense).all()
    np.testing.assert_allclose(t_dense, t_coo, rtol=1e-5)


def _layout_counts():
    reg = registry()
    return {layout: reg.counter("repro_sinkhorn_layout_total",
                                layout=layout).value
            for layout in ("dense", "coo")}


@pytest.mark.parametrize("m,n,layout", [
    (512, 512, "dense"), (1024, 1024, "dense"), (2048, 2048, "dense"),
    (4096, 4096, "dense"), (4097, 4096, "coo"), (1 << 13, 1 << 12, "coo")])
@pytest.mark.parametrize("balanced", [True, False],
                         ids=["balanced", "unbalanced"])
def test_sparse_log_layout_follows_the_cell_grid(m, n, layout, balanced):
    """The served buckets (512, 1024, 2048) keep a dense grid; past 2**24
    cells a lane the solver stays on COO. Read from the layout counter,
    which counts traces (an unusual iteration count keeps this trace out
    of any other test's jit cache)."""
    s = 16 * max(m, n)
    f32, i32 = jnp.float32, jnp.int32
    args = [jax.ShapeDtypeStruct((m,), f32), jax.ShapeDtypeStruct((n,), f32),
            jax.ShapeDtypeStruct((s,), i32), jax.ShapeDtypeStruct((s,), i32),
            jax.ShapeDtypeStruct((s,), f32)]
    if balanced:
        fn = partial(sparse_sinkhorn_logdomain, m=m, n=n, iters=7)
    else:
        fn = partial(sparse_sinkhorn_unbalanced_log, lam=1.0, eps=0.1, m=m,
                     n=n, iters=7)
    before = _layout_counts()
    jax.eval_shape(fn, *args)
    after = _layout_counts()
    other = "coo" if layout == "dense" else "dense"
    assert after[layout] == before[layout] + 1
    assert after[other] == before[other]


def test_sparse_log_public_entry_is_the_dense_layout_at_small_size():
    (a, b, rows, cols, logvals), m, n = _coo_case("dead", 2)
    np.testing.assert_array_equal(
        np.asarray(sparse_sinkhorn_logdomain(a, b, rows, cols, logvals, m, n,
                                             60)),
        np.asarray(jax.jit(partial(sk._sinkhorn_dense, m=m, n=n, iters=60))(
            a, b, rows, cols, logvals)))


@pytest.mark.parametrize("support", ["duplicates", "dead", "full"])
def test_sparse_log_dense_layout_backward_keeps_no_grid_per_trip(support):
    """Reverse mode through the dense layout's fixed-length loop (the
    unrolled-autodiff path) saves each trip's potentials, not the trip's
    (m, n) grid of terms, and gives the COO layout's gradient: finite
    with dead rows and columns too."""
    (a, b, rows, cols, logvals), m, n = _coo_case(support, 3, m=64, n=48,
                                                  s=600)
    iters = 200
    w = jax.random.uniform(jax.random.PRNGKey(4), logvals.shape)

    def grad_of(layout):
        return jax.jit(jax.grad(lambda lv: jnp.sum(
            w * layout(a, b, rows, cols, lv, m, n, iters))))

    dense = grad_of(sk._sinkhorn_dense)
    temp = dense.lower(logvals).compile().memory_analysis().temp_size_in_bytes
    assert temp < iters * m * n * 4, temp
    g_dense = np.asarray(dense(logvals))
    g_coo = np.asarray(grad_of(sk._sinkhorn_coo)(logvals))
    assert np.isfinite(g_dense).all()
    np.testing.assert_allclose(g_dense, g_coo, rtol=1e-4, atol=1e-7)
