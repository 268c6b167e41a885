"""Parity (interpret mode) + regression tests for the fused spar_cost
kernel family, and for the kernels/dispatch.py layer."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spar_gw import spar_gw
from repro.kernels import dispatch
from repro.kernels.gw_cost.ops import gw_cost
from repro.kernels.sinkhorn.ops import sinkhorn as sinkhorn_kernel
from repro.kernels.spar_cost.ops import (
    make_spar_cost_fn,
    resolve_impl,
    spar_cost_fused,
    spar_matvec,
)
from repro.kernels.spar_cost.ops import _fused_setup
from repro.kernels.spar_cost.ref import materialize_loss, spar_cost_ref
from repro.kernels.spar_cost.spar_cost import (
    K_BLOCK,
    _vmem_limit,
    row_panels,
    spar_cost_pallas,
)

KEY = jax.random.PRNGKey(0)


def _support(m, n, s, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    Cx = jax.random.uniform(ks[0], (m, m)) + 0.1        # >0 so kl is finite
    Cy = jax.random.uniform(ks[1], (n, n)) + 0.1
    rows = jax.random.randint(ks[2], (s,), 0, m)
    cols = jax.random.randint(ks[3], (s,), 0, n)
    t = jax.random.uniform(ks[4], (s,))
    return Cx, Cy, rows, cols, t


# ---------------------------------------------------------------------------
# kernel parity vs the jnp lax.map oracle (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss", ["l1", "l2", "kl"])
@pytest.mark.parametrize("s", [64, 96, 100, 33])   # incl. non-block-multiples
def test_fused_kernel_matches_oracle(loss, s):
    Cx, Cy, rows, cols, t = _support(50, 60, s, seed=s)
    ref = spar_cost_ref(Cx, Cy, rows, cols, t, loss, chunk=32)
    got = spar_cost_fused(Cx, Cy, rows, cols, t, loss=loss, block=32,
                          interpret=True)
    np.testing.assert_allclose(np.array(got), np.array(ref), rtol=1e-4,
                               atol=1e-5)


# the k-blocks the chip takes: (8, 128) and (8, 256) panel tiles; s is a
# multiple of neither block, nor of the 256 l-block
@pytest.mark.parametrize("loss", ["l1", "l2", "kl"])
@pytest.mark.parametrize("bk", [1024, 2048])
def test_fused_kernel_chip_k_blocks_match_oracle(loss, bk):
    s = 2500
    Cx, Cy, rows, cols, t = _support(40, 70, s, seed=bk)
    off = jax.random.normal(jax.random.PRNGKey(bk + 1), (s,))
    ref = spar_cost_ref(Cx, Cy, rows, cols, t, loss, chunk=512) + off
    got = spar_cost_fused(Cx, Cy, rows, cols, t, off, loss=loss, block=bk,
                          interpret=True)
    np.testing.assert_allclose(np.array(got), np.array(ref), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("bk", [1024, 2048])
def test_fused_kernel_chip_k_blocks_duplicate_pairs(bk):
    s = 1100
    Cx, Cy, _, _, t = _support(30, 30, s, seed=9)
    rows = jax.random.randint(KEY, (s,), 0, 30)
    cols = rows[::-1]
    rows = rows.at[100:400].set(rows[0])                # repeated pairs
    cols = cols.at[100:400].set(cols[0])
    for loss in ("l1", "l2", "kl"):
        ref = spar_cost_ref(Cx, Cy, rows, cols, t, loss, chunk=512)
        got = spar_cost_fused(Cx, Cy, rows, cols, t, loss=loss, block=bk,
                              interpret=True)
        np.testing.assert_allclose(np.array(got), np.array(ref), rtol=1e-4,
                                   atol=1e-5)


def test_row_panels_are_sublane_dense_transposed_rows():
    C = jnp.arange(5 * 5, dtype=jnp.float32).reshape(5, 5)
    idx = jax.random.randint(KEY, (32,), 0, 5)
    P = np.array(row_panels(C, idx, 16))
    assert P.shape == (2, 5, 8, 2)
    for k in range(32):
        kb, a, b = k // 16, (k % 16) // 2, k % 2
        np.testing.assert_array_equal(P[kb, :, a, b], np.array(C[idx[k]]))


def test_fused_setup_pads_k_and_l_apart():
    """k pads to the k-block, the l-walk only to the 256 l-block: padding
    k to 2048 adds no l-steps."""
    s = 2500
    Cx, Cy, rows, cols, _ = _support(40, 70, s)
    rows_l, cols_l, XT, YT = _fused_setup(Cx, Cy, rows, cols, 2048)
    assert rows_l.shape == cols_l.shape == (2560,)
    assert XT.shape == (2, 40, 8, 256) and YT.shape == (2, 70, 8, 256)


VMEM_BYTES = 128 * 2**20             # one v5e TensorCore's VMEM


# the served buckets, and 4096, the largest whose panels fit VMEM
@pytest.mark.parametrize("bucket", [512, 1024, 2048, 4096])
def test_k_block_panels_fit_vmem_by_bucket(bucket):
    limit = _vmem_limit(bucket, bucket, K_BLOCK)
    # both panels' k-blocks, double-buffered, fit under the limit, and the
    # limit under the chip's VMEM
    assert 2 * 2 * bucket * K_BLOCK * 4 < limit < VMEM_BYTES


# compiled for the chip, a k-block must fill whole (8, 128) tiles and its
# panels must fit VMEM: 2048 at bucket 4096 does not, nor 1024 at 8192,
# nor 512 anywhere
@pytest.mark.parametrize("m,bk", [(4096, 2048), (8192, 1024), (64, 512)])
def test_fused_kernel_refuses_chip_k_blocks_it_cannot_hold(m, bk):
    panel = jax.ShapeDtypeStruct((1, m, 8, bk // 8), jnp.float32)
    vec = jax.ShapeDtypeStruct((256,), jnp.float32)
    with pytest.raises(ValueError, match="k-block"):
        spar_cost_pallas.lower(panel, panel, vec.update(dtype=jnp.int32),
                               vec.update(dtype=jnp.int32), vec,
                               jax.ShapeDtypeStruct((bk,), jnp.float32),
                               interpret=False)


def test_row_panels_refuse_k_block_off_the_sublanes():
    with pytest.raises(ValueError, match="multiple of 8"):
        row_panels(jnp.eye(4), jnp.zeros(12, jnp.int32), 12)


@pytest.mark.parametrize("loss", ["l1", "l2", "kl"])
def test_materialized_matvec_matches_oracle(loss):
    s = 100
    Cx, Cy, rows, cols, t = _support(40, 40, s, seed=7)
    ref = spar_cost_ref(Cx, Cy, rows, cols, t, loss, chunk=64)
    Lmat = materialize_loss(Cx, Cy, rows, cols, loss, chunk=64)
    got = spar_matvec(Lmat, t, block=32, interpret=True)
    np.testing.assert_allclose(np.array(got), np.array(ref), rtol=1e-4,
                               atol=1e-5)


def test_duplicate_pairs_are_parallel_entries():
    """Duplicate (row, col) draws are legitimate parallel COO entries —
    every impl must treat them independently (gather semantics)."""
    s = 64
    Cx, Cy, _, _, t = _support(30, 30, s, seed=3)
    rows = jnp.zeros((s,), jnp.int32).at[1:].set(
        jax.random.randint(KEY, (s - 1,), 0, 30))
    cols = rows[::-1]                                   # forced duplicates
    rows = rows.at[10:20].set(rows[0])                  # repeated pairs
    cols = cols.at[10:20].set(cols[0])
    for loss in ("l1", "l2"):
        ref = spar_cost_ref(Cx, Cy, rows, cols, t, loss, chunk=16)
        got = spar_cost_fused(Cx, Cy, rows, cols, t, loss=loss, block=16,
                              interpret=True)
        np.testing.assert_allclose(np.array(got), np.array(ref), rtol=1e-4,
                                   atol=1e-5)


def test_affine_epilogue_offset():
    """out = L @ t + off — the epilogue that forms logK on-chip."""
    s = 96
    Cx, Cy, rows, cols, t = _support(25, 35, s, seed=11)
    off = jax.random.normal(jax.random.PRNGKey(12), (s,))
    ref = spar_cost_ref(Cx, Cy, rows, cols, t, "l2", chunk=32) + off
    got = spar_cost_fused(Cx, Cy, rows, cols, t, off, loss="l2", block=32,
                          interpret=True)
    np.testing.assert_allclose(np.array(got), np.array(ref), rtol=1e-4,
                               atol=1e-5)
    Lmat = materialize_loss(Cx, Cy, rows, cols, "l2", chunk=32)
    got2 = spar_matvec(Lmat, t, off, block=32, interpret=True)
    np.testing.assert_allclose(np.array(got2), np.array(ref), rtol=1e-4,
                               atol=1e-5)


def test_make_spar_cost_fn_impls_agree():
    s = 80
    Cx, Cy, rows, cols, t = _support(30, 45, s, seed=5)
    off = jnp.linspace(-1.0, 1.0, s)
    outs = {}
    for impl in ("jnp", "pallas", "materialized"):
        fn = make_spar_cost_fn(Cx, Cy, rows, cols, "l2", impl=impl,
                               chunk=32)
        outs[impl] = np.array(fn(t, off))
    np.testing.assert_allclose(outs["pallas"], outs["jnp"], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(outs["materialized"], outs["jnp"], rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# solver-level regression: cost_impl must not change the estimate
# ---------------------------------------------------------------------------

def test_spar_gw_pallas_and_materialized_match():
    n = 32
    x = jax.random.normal(KEY, (n, 2))
    Cx = jnp.sqrt(jnp.sum((x[:, None] - x[None, :]) ** 2, -1))
    y = jax.random.normal(jax.random.PRNGKey(1), (n, 2)) * 1.3
    Cy = jnp.sqrt(jnp.sum((y[:, None] - y[None, :]) ** 2, -1))
    a = b = jnp.ones(n) / n
    kw = dict(s=8 * n, loss="l2", epsilon=1e-2, outer_iters=5,
              inner_iters=20)
    key = jax.random.PRNGKey(42)
    v_jnp, (_, _, T_jnp) = spar_gw(key, a, b, Cx, Cy, cost_impl="jnp", **kw)
    v_pal, (_, _, T_pal) = spar_gw(key, a, b, Cx, Cy, cost_impl="pallas",
                                   **kw)
    v_mat, (_, _, T_mat) = spar_gw(key, a, b, Cx, Cy,
                                   cost_impl="materialized", **kw)
    np.testing.assert_allclose(float(v_pal), float(v_jnp), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(float(v_mat), float(v_jnp), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.array(T_pal), np.array(T_mat), rtol=1e-4,
                               atol=1e-7)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------

def test_no_import_time_interpret_globals():
    """Acceptance: no per-ops.py _INTERPRET globals remain — backend is
    resolved at call time inside kernels/dispatch.py."""
    for mod in ("repro.kernels.gw_cost.ops", "repro.kernels.sinkhorn.ops",
                "repro.kernels.flash_attention.ops", "repro.kernels.ssd.ops",
                "repro.kernels.spar_cost.ops"):
        assert not hasattr(importlib.import_module(mod), "_INTERPRET"), mod


def test_interpret_mode_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert dispatch.interpret_mode() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert dispatch.interpret_mode() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "auto")
    assert dispatch.interpret_mode() == (jax.default_backend() != "tpu")
    # explicit override beats the env
    assert dispatch.interpret_mode(True) is True


def test_pad_unpad_roundtrip():
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    xp, shape = dispatch.pad_to_multiple(x, (8, 128))
    assert xp.shape == (8, 128)
    np.testing.assert_array_equal(np.array(dispatch.unpad(xp, shape)),
                                  np.array(x))


def test_resolve_impl_auto_gate():
    # 4·s² ≤ 512 MiB holds up to s = 11,585
    assert resolve_impl("auto", 11585) == "materialized"
    assert resolve_impl("auto", 11586) in ("pallas", "jnp")
    assert resolve_impl("jnp", 10**9) == "jnp"


def _jaxpr_text(fn, *shapes):
    return str(jax.make_jaxpr(fn)(*(jnp.ones(sh) for sh in shapes)))


# per variable the parent's kernels read for sizing: a hostile value, and
# the reading of the entry point it reached
_SIZING_READINGS = {
    "REPRO_BLOCK_SPAR_COST": ("8", lambda: _jaxpr_text(
        lambda L, t: spar_matvec(L, t, interpret=True), (64, 64), (64,))),
    "REPRO_BLOCK_GW_COST": ("8", lambda: _jaxpr_text(
        lambda A, B, T: gw_cost(A, B, T, interpret=True),
        (64, 64), (64, 64), (64, 64))),
    "REPRO_SPAR_MATERIALIZE_BUDGET": ("0", lambda: resolve_impl("auto",
                                                                1000)),
    "REPRO_VMEM_BUDGET": ("0", lambda: "pallas_call" in _jaxpr_text(
        lambda a, b, K: sinkhorn_kernel(a, b, K, iters=1, interpret=True),
        (1024,), (1024,), (1024, 1024))),
}


@pytest.mark.parametrize("var", sorted(_SIZING_READINGS))
def test_kernel_sizing_reads_no_process_env(var, monkeypatch):
    """Kernel sizes are the families' own constants: no process variable
    changes the program a kernel entry point traces."""
    hostile, read = _SIZING_READINGS[var]
    monkeypatch.delenv(var, raising=False)
    want = read()
    monkeypatch.setenv(var, hostile)
    assert read() == want
