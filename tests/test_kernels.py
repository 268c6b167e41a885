"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gw_cost.ops import gw_cost
from repro.kernels.gw_cost.ref import gw_cost_ref
from repro.kernels.sinkhorn.ops import sinkhorn as sinkhorn_kernel
from repro.kernels.sinkhorn.ref import sinkhorn_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("loss", ["l1", "l2", "kl"])
@pytest.mark.parametrize("shape", [(32, 32, 32, 32), (64, 48, 40, 56),
                                   (33, 17, 65, 9), (128, 96, 64, 80)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gw_cost_sweep(loss, shape, dtype):
    K, L, M, P = shape
    k1, k2, k3 = jax.random.split(KEY, 3)
    A = (jax.random.uniform(k1, (K, L)) + 0.1).astype(dtype)
    B = (jax.random.uniform(k2, (M, P)) + 0.1).astype(dtype)
    T = jax.random.uniform(k3, (L, P)).astype(dtype)
    got = gw_cost(A, B, T, loss)
    ref = gw_cost_ref(A.astype(jnp.float32), B.astype(jnp.float32),
                      T.astype(jnp.float32), loss)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.array(got), np.array(ref), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("shape", [(2, 128, 4, 2, 32), (1, 256, 8, 8, 64),
                                   (2, 64, 6, 3, 16), (1, 512, 2, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, dtype):
    B, S, H, K, hd = shape
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(k2, (B, S, K, hd)).astype(dtype)
    v = jax.random.normal(k3, (B, S, K, hd)).astype(dtype)
    got = flash_attention(q, k, v)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32))
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.array(got, np.float32), np.array(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("mn", [(64, 48), (128, 128), (96, 32)])
@pytest.mark.parametrize("iters", [10, 50])
def test_sinkhorn_kernel_sweep(mn, iters):
    m, n = mn
    k1 = jax.random.PRNGKey(m * n + iters)
    a = jnp.ones(m) / m
    b = jnp.ones(n) / n
    K = jax.random.uniform(k1, (m, n)) + 0.01
    got = sinkhorn_kernel(a, b, K, iters=iters)
    ref = sinkhorn_ref(a, b, K, iters)
    np.testing.assert_allclose(np.array(got), np.array(ref), rtol=1e-4,
                               atol=1e-8)


def test_sinkhorn_kernel_fallback_above_vmem_budget():
    m = n = 2048                      # 16 MiB f32 > 8 MiB budget -> jnp path
    a = jnp.ones(m) / m
    b = jnp.ones(n) / n
    K = jax.random.uniform(KEY, (m, n)) + 0.01
    T = sinkhorn_kernel(a, b, K, iters=3)
    assert np.isfinite(np.array(T)).all()


@pytest.mark.parametrize("shape", [(2, 32, 8, 16, 8), (3, 64, 4, 32, 16),
                                   (1, 16, 6, 8, 4)])
def test_ssd_intra_kernel_sweep(shape):
    """Mamba2 SSD intra-chunk kernel vs oracle (grid over batch*chunks and
    head tiles)."""
    from repro.kernels.ssd.ops import ssd_intra
    from repro.kernels.ssd.ref import ssd_intra_ref
    G, k, H, P, N = shape
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    xdt = jax.random.normal(k1, (G, k, H, P))
    cs = -jax.random.uniform(k2, (G, k, H)).cumsum(axis=1)   # decaying
    Bm = jax.random.normal(k3, (G, k, N))
    Cm = jax.random.normal(k4, (G, k, N))
    got = ssd_intra(xdt, cs, Bm, Cm)
    ref = jax.vmap(ssd_intra_ref)(xdt, cs, Bm, Cm)
    np.testing.assert_allclose(np.array(got), np.array(ref), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# roofline peaks: keyed by device kind, no default
# ---------------------------------------------------------------------------

def test_roofline_peaks_by_device_kind():
    gwbench = str(Path(__file__).resolve().parents[1] / "benchmarks"
                  / "gwbench")
    if gwbench not in sys.path:
        sys.path.insert(0, gwbench)
    from peaks import peaks
    v5e = peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    for kind in ("cpu", "TPU v4", "unrecorded"):
        with pytest.raises(ValueError, match="no published peaks"):
            peaks(kind)
