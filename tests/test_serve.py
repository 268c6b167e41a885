"""Serving layer (repro.serve): bucketing, padding inertness, the
content-hash geometry cache, batched lane isolation, per-request
fallback, and server observability."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import DenseGWSolver, Geometry, QuadraticProblem
from repro.health import DIVERGED, STALLED, FaultSpec
from repro.serve import (
    DEFAULT_BUCKETS,
    PAD_WEIGHT,
    GeometryCache,
    GWServer,
    RequestResult,
    ServeConfig,
    batch_signature,
    bucket_for,
    next_pow2,
    pad_geometry,
    pad_problem,
    percentiles,
)
from repro.serve.batching import MIN_LANES

KEY = jax.random.PRNGKey(0)

BASE = DenseGWSolver(tol=1e-6, inner_tol=1e-8, outer_iters=10)
CLEAN = dataclasses.replace(BASE, max_rescues=0,
                            fault=FaultSpec(at_iter=-1, kind="nan"))
POISONED = dataclasses.replace(BASE, max_rescues=0,
                               fault=FaultSpec(at_iter=2, kind="nan"))


def _geom(seed: int, n: int, scale: float = 1.0) -> Geometry:
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 2)) * scale
    C = jnp.sqrt(jnp.sum((x[:, None] - x[None, :]) ** 2, -1))
    return Geometry(C, jnp.ones(n) / n)


def _problem(seed: int, m: int, n: int = None) -> QuadraticProblem:
    n = m if n is None else n
    return QuadraticProblem(_geom(seed, m), _geom(seed + 50, n, scale=1.2))


def _bits(tree_a, tree_b) -> bool:
    la, ta = jax.tree.flatten(tree_a)
    lb, tb = jax.tree.flatten(tree_b)
    return ta == tb and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# bucket policy
# ---------------------------------------------------------------------------

def test_bucket_for_picks_smallest_fitting_bucket():
    assert bucket_for(1) == 16
    assert bucket_for(16) == 16
    assert bucket_for(17) == 24
    assert bucket_for(100) == 128
    assert bucket_for(512) == 512


def test_bucket_for_beyond_largest_uses_next_pow2():
    assert bucket_for(513) == 1024
    assert bucket_for(2000) == 2048


def test_bucket_for_rejects_nonpositive():
    with pytest.raises(ValueError):
        bucket_for(0)


def test_next_pow2_has_min_lanes_floor():
    # width-1 stacks are forbidden: XLA lowers a degenerate batch-1
    # dot_general differently from every width >= 2 (and from eager), so
    # a width floor is what makes per-lane bits width-invariant
    assert MIN_LANES >= 2
    assert next_pow2(1) == MIN_LANES
    assert next_pow2(2) == 2
    assert next_pow2(3) == 4
    assert next_pow2(8) == 8
    assert next_pow2(9) == 16


# ---------------------------------------------------------------------------
# padding
# ---------------------------------------------------------------------------

def test_pad_geometry_shapes_and_values():
    g = _geom(0, 14)
    p = pad_geometry(g, 16)
    assert p.cost.shape == (16, 16) and p.weights.shape == (16,)
    np.testing.assert_array_equal(np.asarray(p.cost)[:14, :14],
                                  np.asarray(g.cost))
    assert np.all(np.asarray(p.cost)[14:, :] == 0.0)
    np.testing.assert_array_equal(np.asarray(p.weights)[:14],
                                  np.asarray(g.weights))
    assert np.all(np.asarray(p.weights)[14:] == np.float32(PAD_WEIGHT))


def test_pad_geometry_noop_at_size_and_rejects_overflow():
    g = _geom(0, 16)
    assert pad_geometry(g, 16) is g
    with pytest.raises(ValueError):
        pad_geometry(g, 12)


def test_pad_weight_survives_float32():
    # the PR-3 lesson: the pad weight must stay a *normal* float32 (XLA
    # CPU flushes subnormals to zero, which re-enters log/clamp paths as
    # full-mass garbage)
    assert np.float32(PAD_WEIGHT) > np.finfo(np.float32).tiny


def test_padded_solve_matches_unpadded_values():
    prob = _problem(0, 14)
    padded = pad_problem(prob, 16, 16)
    out_ref = repro.solve(prob, CLEAN)
    out_pad = repro.solve(padded, CLEAN, validate=False)
    np.testing.assert_allclose(float(out_pad.value), float(out_ref.value),
                               rtol=1e-4)
    T_pad = np.asarray(out_pad.coupling_dense(16, 16))
    T_ref = np.asarray(out_ref.coupling_dense(14, 14))
    # the ~1e-30 pad mass perturbs float32 iterates; ten outer iterations
    # amplify that to ~1e-4 in individual coupling entries (entries are
    # O(1/n) ~ 0.07 here, so this is still <1% of entry scale)
    np.testing.assert_allclose(T_pad[:14, :14], T_ref, atol=5e-4)
    # padded rows carry ~PAD_WEIGHT of mass, invisible at float32
    assert float(T_pad[14:, :].sum()) < 1e-6


# ---------------------------------------------------------------------------
# batch signatures
# ---------------------------------------------------------------------------

def test_batch_signature_groups_same_shape_and_config():
    a = (pad_problem(_problem(0, 14), 16, 16), CLEAN, None)
    b = (pad_problem(_problem(9, 12), 16, 16), CLEAN, None)
    assert batch_signature(a) == batch_signature(b)


def test_batch_signature_separates_shapes_and_solver_knobs():
    p16 = (pad_problem(_problem(0, 14), 16, 16), CLEAN, None)
    p24 = (pad_problem(_problem(0, 14), 24, 24), CLEAN, None)
    assert batch_signature(p16) != batch_signature(p24)
    other = dataclasses.replace(CLEAN, outer_iters=11)
    assert batch_signature(p16) != batch_signature(
        (p16[0], other, None))


# ---------------------------------------------------------------------------
# Geometry.content_hash
# ---------------------------------------------------------------------------

def test_content_hash_construction_path_invariant():
    rng = np.random.default_rng(0)
    C = np.asarray(rng.random((8, 8)), np.float32)
    w = np.full(8, 1 / 8, np.float32)
    h_np = Geometry(C, w).content_hash()
    h_jnp = Geometry(jnp.asarray(C), jnp.asarray(w)).content_hash()
    h_F = Geometry(np.asfortranarray(C), w).content_hash()
    assert h_np == h_jnp == h_F


def test_content_hash_from_points_matches_explicit_ctor():
    rng = np.random.default_rng(1)
    p = np.asarray(rng.random((9, 3)), np.float32)
    w = np.full(9, 1 / 9, np.float32)
    assert (Geometry.from_points(p, w).content_hash()
            == Geometry(None, w, points=p).content_hash())


def test_content_hash_sensitivity():
    rng = np.random.default_rng(2)
    C = np.asarray(rng.random((8, 8)), np.float32)
    w = np.full(8, 1 / 8, np.float32)
    base = Geometry(C, w).content_hash()
    assert Geometry(C.astype(np.float64), w).content_hash() != base
    w2 = w.copy()
    w2[0] += np.float32(1e-6)
    assert Geometry(C, w2, validate=False).content_hash() != base
    C2 = C.copy()
    C2[3, 4] += np.float32(1e-6)
    assert Geometry(C2, w).content_hash() != base


def test_content_hash_point_cloud_never_materializes_cost(monkeypatch):
    rng = np.random.default_rng(3)
    p = np.asarray(rng.random((50, 3)), np.float32)
    g = Geometry.from_points(p, np.full(50, 1 / 50, np.float32))

    def boom(self):
        raise AssertionError("content_hash materialized the n x n cost")

    monkeypatch.setattr(Geometry, "cost_matrix", property(boom))
    assert isinstance(g.content_hash(), str)


def test_content_hash_memoized_and_rejects_tracers():
    g = _geom(0, 8)
    assert g.content_hash() is g.content_hash()

    def inside(c):
        Geometry(c, jnp.ones(8) / 8, validate=False).content_hash()
        return c

    with pytest.raises(ValueError, match="concrete"):
        jax.jit(inside)(g.cost)


# ---------------------------------------------------------------------------
# GeometryCache
# ---------------------------------------------------------------------------

def test_cache_counters_and_artifact_reuse():
    cache = GeometryCache(8)
    g = _geom(0, 14)
    a1 = cache.padded(g, 16)
    a2 = cache.padded(g, 16)
    assert a1 is a2
    assert (cache.hits, cache.misses) == (1, 1)
    # same content, different object -> still a hit
    g2 = Geometry(jnp.asarray(np.asarray(g.cost)), g.weights)
    assert cache.padded(g2, 16) is a1
    assert cache.hits == 2


def test_cache_lru_eviction():
    cache = GeometryCache(2)
    gs = [_geom(s, 12) for s in range(3)]
    for g in gs:
        cache.padded(g, 16)
    assert len(cache) == 2 and cache.evictions == 1
    cache.padded(gs[0], 16)          # was evicted -> miss again
    assert cache.misses == 4
    stats = cache.stats()
    assert stats["entries"] == 2 and stats["hit_rate"] == 0.0


def test_cache_lowrank_factors_and_anchors():
    rng = np.random.default_rng(4)
    pts = np.asarray(rng.random((12, 2)), np.float32)
    g = Geometry.from_points(jnp.asarray(pts),
                             jnp.full(12, 1 / 12, jnp.float32))
    cache = GeometryCache(8)
    fac = cache.lowrank_factors(g)
    np.testing.assert_allclose(np.asarray(fac.todense()),
                               np.asarray(g.cost_matrix), atol=1e-5)
    idx1 = cache.anchors(g, 4)
    idx2 = GeometryCache(8).anchors(g, 4)    # fresh cache, same geometry
    assert _bits(idx1, idx2)                 # pure function of the geometry
    with pytest.raises(ValueError, match="point-cloud"):
        cache.lowrank_factors(_geom(0, 8))


def test_cache_warm_populates_all_artifacts():
    rng = np.random.default_rng(5)
    pts = np.asarray(rng.random((10, 2)), np.float32)
    g = Geometry.from_points(jnp.asarray(pts),
                             jnp.full(10, 1 / 10, jnp.float32))
    cache = GeometryCache(8)
    cache.warm(g, buckets=(16, 24), k=3)
    assert len(cache) == 4 and cache.hits == 0
    cache.warm(g, buckets=(16, 24), k=3)     # all hits now
    assert cache.hits == 4


# ---------------------------------------------------------------------------
# percentiles
# ---------------------------------------------------------------------------

def test_percentiles_basic_and_empty():
    p = percentiles(list(range(1, 101)))
    assert p["p50"] == pytest.approx(50.5)
    assert p["p50"] <= p["p95"] <= p["p99"] <= 100
    empty = percentiles([])
    assert all(np.isnan(v) for v in empty.values())


# ---------------------------------------------------------------------------
# server end-to-end
# ---------------------------------------------------------------------------

def test_server_results_match_eager_solve():
    srv = GWServer(ServeConfig(max_batch=4, max_wait_s=60.0,
                               on_failure="none"))
    probs = [_problem(s, 12 + s) for s in range(3)]
    rids = [srv.submit(p, CLEAN) for p in probs]
    for res, prob in zip(srv.results(rids), probs):
        ref = repro.solve(prob, CLEAN)
        np.testing.assert_allclose(res.value, float(ref.value), rtol=1e-4)
        m, n = prob.shape
        np.testing.assert_allclose(np.asarray(res.coupling_dense()),
                                   np.asarray(ref.coupling_dense(m, n)),
                                   atol=1e-5)
        assert res.shape == (m, n) and not res.failed


def test_server_lifecycle_poll_and_stats():
    srv = GWServer(ServeConfig(max_batch=8, max_wait_s=60.0,
                               on_failure="none"))
    rid = srv.submit(_problem(0, 14), CLEAN)
    assert srv.poll(rid) == "queued"
    srv.flush()
    assert srv.poll(rid) in ("running", "done")
    res = srv.result(rid)
    assert srv.poll(rid) == "done"
    assert res is srv.result(rid)            # idempotent
    stats = srv.stats()
    assert stats["n_completed"] == 1 and stats["n_batches"] == 1
    assert stats["mean_batch_lanes"] >= MIN_LANES   # filler lane added
    assert np.isfinite(stats["latency_p99_ms"])
    with pytest.raises(KeyError):
        srv.result(999)


def test_server_eager_key_validation():
    srv = GWServer()
    with pytest.raises(ValueError, match="PRNG key"):
        srv.submit(_problem(0, 14), "spar_gw")


def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(on_failure="retry")
    with pytest.raises(ValueError):
        ServeConfig(max_batch=0)


def test_server_multi_bucket_routing():
    srv = GWServer(ServeConfig(max_batch=8, max_wait_s=60.0,
                               on_failure="none"))
    rids = [srv.submit(_problem(s, n), CLEAN)
            for s, n in enumerate((12, 20, 14, 28))]
    res = srv.results(rids)
    assert [r.padded_shape for r in res] == [(16, 16), (24, 24), (16, 16),
                                             (32, 32)]
    # 3 buckets: (16,16) holds two requests, the others one + filler
    assert srv.stats()["n_batches"] == 3


# ---------------------------------------------------------------------------
# lane isolation: the serving-boundary acceptance criterion
# ---------------------------------------------------------------------------

def test_poisoned_lane_isolated_and_mates_bitwise_solo():
    """One FaultSpec-poisoned request in a bucket must (a) come back
    DIVERGED itself and (b) leave every bucket-mate bitwise identical to
    the mate's solo (eager, unbatched) solve."""
    seeds = [0, 1, 2, 5]
    probs = [_problem(s, 14) for s in seeds]
    solvers = [CLEAN, POISONED, CLEAN, CLEAN]
    srv = GWServer(ServeConfig(max_batch=4, max_wait_s=60.0,
                               on_failure="none"))
    rids = [srv.submit(p, s) for p, s in zip(probs, solvers)]
    res = srv.results(rids)

    assert res[1].status_name == "DIVERGED" and res[1].failed
    assert srv.stats()["n_batches"] == 1     # one bucket held all four

    # solo references: one fresh server, one request per batch (submit ->
    # result immediately, so nothing shares a bucket)
    solo_srv = GWServer(ServeConfig(max_batch=4, max_wait_s=60.0,
                                    on_failure="none"))
    for i in (0, 2, 3):
        solo = solo_srv.result(solo_srv.submit(probs[i], CLEAN))
        assert not res[i].failed
        assert _bits(res[i].output.value, solo.output.value)
        assert _bits(res[i].output.coupling_dense(16, 16),
                     solo.output.coupling_dense(16, 16))


def test_filler_lanes_do_not_change_request_bits():
    # lane 1 holding a disarmed filler replica vs lane 1 holding a real
    # different request: lane 0's bits must not change (even when lane 0
    # itself is the poisoned, diverging one)
    prob = _problem(3, 13)
    srv_solo = GWServer(ServeConfig(max_batch=8, max_wait_s=60.0,
                                    on_failure="none"))
    solo = srv_solo.result(srv_solo.submit(prob, POISONED))
    srv_pair = GWServer(ServeConfig(max_batch=2, max_wait_s=60.0,
                                    on_failure="none"))
    rid0 = srv_pair.submit(prob, POISONED)
    rid1 = srv_pair.submit(_problem(8, 15), CLEAN)
    paired = srv_pair.results([rid0, rid1])[0]
    assert solo.status_name == paired.status_name == "DIVERGED"
    assert _bits(solo.output.value, paired.output.value)
    assert _bits(solo.output.coupling, paired.output.coupling)


# ---------------------------------------------------------------------------
# per-request fallback
# ---------------------------------------------------------------------------

def test_poisoned_request_falls_back_mates_untouched():
    persistent = dataclasses.replace(
        BASE, max_rescues=0,
        fault=FaultSpec(at_iter=1, kind="nan", persistent=True))
    probs = [_problem(s, 14) for s in (0, 1, 2, 5)]
    solvers = [CLEAN, persistent, CLEAN, CLEAN]
    srv = GWServer(ServeConfig(max_batch=4, max_wait_s=60.0,
                               on_failure="fallback"))
    rids = [srv.submit(p, s, key=jax.random.PRNGKey(100 + i))
            for i, (p, s) in enumerate(zip(probs, solvers))]
    res = srv.results(rids)

    # the poisoned request recovered through the ladder, at its own shape
    assert res[1].failed and res[1].fell_back
    assert int(np.asarray(res[1].status.code)) < STALLED
    assert np.isfinite(res[1].value)
    assert res[1].coupling_dense().shape == (14, 14)
    assert srv.stats()["n_fallbacks"] == 1

    # mates stayed on the batched path, bitwise equal to solo
    for i in (0, 2, 3):
        assert not res[i].fell_back
        padded = pad_problem(probs[i], 16, 16)
        ref = CLEAN.run(padded, jax.random.PRNGKey(100 + i))
        assert _bits(res[i].output.value, ref.value)


def test_keyless_dense_fallback_returns_batched_output():
    # with no PRNG key the ladder has no key-free rungs besides the
    # primary -> fallback cannot recover; the batched DIVERGED output is
    # returned honestly (failed=True, fell_back=False)
    persistent = dataclasses.replace(
        BASE, max_rescues=0,
        fault=FaultSpec(at_iter=1, kind="nan", persistent=True))
    srv = GWServer(ServeConfig(max_batch=2, max_wait_s=60.0,
                               on_failure="fallback"))
    res = srv.result(srv.submit(_problem(0, 14), persistent))
    assert res.failed and not res.fell_back
    assert res.status_name == "DIVERGED"


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

def _run_child(code: str, *args: str, env_extra=None, drop=()):
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(PYTHONPATH=str(root / "src"), PYTHONHASHSEED="0",
               **(env_extra or {}))
    proc = subprocess.run([sys.executable, "-c", code, *args],
                          capture_output=True, text=True, env=env, cwd=root,
                          timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_compilation_cache_skips_recompile_in_fresh_process(tmp_path):
    """Two identical server processes sharing JAX_COMPILATION_CACHE_DIR:
    the first populates it, the second (fresh process, cold in-memory
    caches) deserializes every executable — no new cache entries, and
    the helper leaves JAX's reading of the variable as it is."""
    import textwrap

    code = textwrap.dedent("""
        import sys
        import jax, jax.numpy as jnp
        import repro
        from repro.serve import GWServer, ServeConfig, enable_compilation_cache

        assert enable_compilation_cache() == sys.argv[1]
        server = GWServer(ServeConfig(max_batch=1))
        n = 20
        x = jax.random.normal(jax.random.PRNGKey(0), (n, 2))
        y = jax.random.normal(jax.random.PRNGKey(1), (n, 2))
        a = jnp.ones(n) / n
        p = repro.QuadraticProblem(repro.Geometry.from_points(x, a),
                                   repro.Geometry.from_points(y, a))
        solver = repro.DenseGWSolver(outer_iters=5, inner_iters=10)
        res = server.result(server.submit(p, solver))
        assert not res.failed, res.status_name
        print("VALUE", float(res.value))
    """)

    def run_once():
        out = _run_child(code, str(tmp_path), env_extra={
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        value = [ln for ln in out.splitlines() if ln.startswith("VALUE")][0]
        entries = sorted(p.name for p in tmp_path.rglob("*") if p.is_file())
        return value, entries

    value1, entries1 = run_once()
    assert entries1, "first run persisted no executables"
    value2, entries2 = run_once()
    assert value2 == value1
    assert entries2 == entries1, (
        f"second process recompiled: {set(entries2) - set(entries1)}")


def test_compilation_cache_defaults_to_fixed_checkout_dir():
    """Without JAX_COMPILATION_CACHE_DIR the cache lives at one fixed
    directory inside the checkout (no temp name, pid or time in it)."""
    import pathlib

    from repro.serve.server import DEFAULT_CACHE_DIR

    code = ("from repro.serve import enable_compilation_cache\n"
            "print(enable_compilation_cache())\n")
    out = [_run_child(code, drop=("JAX_COMPILATION_CACHE_DIR",))
           for _ in range(2)]
    root = pathlib.Path(__file__).resolve().parent.parent
    assert DEFAULT_CACHE_DIR == root / ".jax_cache"
    assert out[0] == out[1] == f"{DEFAULT_CACHE_DIR}\n"


# ---------------------------------------------------------------------------
# dispatch errors reach the caller
# ---------------------------------------------------------------------------

def _failing_exec(*args):
    raise RuntimeError("compile failed: injected")


def test_timer_flush_dispatch_error_reaches_result():
    """A bucket flushed by the background timer that fails to compile:
    its requests carry the exception and result() raises it."""
    import time

    from repro.obs.span import spans

    probs = [_problem(s, 14) for s in range(2)]
    srv = GWServer(ServeConfig(max_batch=8, max_wait_s=0.2,
                               on_failure="none"))
    srv._exec = _failing_exec
    rids = [srv.submit(p, CLEAN) for p in probs]
    reqs = [srv._requests[rid] for rid in rids]
    deadline = time.time() + 30
    while (any(r.state != "done" for r in reqs)   # no server call: only
           and time.time() < deadline):           # the timer can flush
        time.sleep(0.01)
    srv.close()
    assert any(r["name"] == "serve.dispatch" and r.get("source") == "timer"
               for r in spans())
    for rid in rids:
        assert srv.poll(rid) == "done"
        with pytest.raises(RuntimeError, match="injected"):
            srv.result(rid)


def test_explicit_flush_dispatch_error_reaches_result():
    srv = GWServer(ServeConfig(max_batch=8, max_wait_s=60.0,
                               flush_thread=False, on_failure="none"))
    srv._exec = _failing_exec
    rid = srv.submit(_problem(0, 14), CLEAN)
    srv.flush()                              # the flusher itself survives
    with pytest.raises(RuntimeError, match="injected"):
        srv.result(rid)
    with pytest.raises(RuntimeError, match="injected"):
        srv.results([rid])


# ---------------------------------------------------------------------------
# request- and batch-scoped spans
# ---------------------------------------------------------------------------

def test_spans_follow_each_request_through_its_batch():
    """Two full batches and one timer flush with a filler lane: every real
    request has exactly one ``serve.queue`` and one ``serve.collect``,
    tied by ``rid`` and ``batch`` to the ``serve.batch`` that carried it;
    filler lanes have neither, and a repeated ``result`` adds none."""
    import time

    from repro.obs.span import clear_spans, spans

    srv = GWServer(ServeConfig(max_batch=2, max_wait_s=0.1,
                               on_failure="none"))
    cfg = srv.config
    try:
        srv.config = dataclasses.replace(cfg, max_wait_s=3600.0)  # no timer
        clear_spans()
        rids = [srv.submit(_problem(s, 14), CLEAN) for s in range(4)]
        srv.config = cfg
        rids.append(srv.submit(_problem(4, 14), CLEAN))
        last = srv._requests[rids[-1]]
        deadline = time.time() + 30
        while last.state == "queued" and time.time() < deadline:
            time.sleep(0.01)                 # only the timer can flush it
        for rid in rids:
            srv.result(rid)
        recs = spans()
        n_before = len(recs)
        srv.result(rids[0])                  # cached: records nothing
        assert len(spans()) == n_before
    finally:
        srv.close()

    def named(name):
        return [r for r in recs if r["name"] == name]

    batches = {r["batch"]: r for r in named("serve.batch")}
    assert len(batches) == 3
    assert sorted((b["real"], b["lanes"]) for b in batches.values()) == [
        (1, 2), (2, 2), (2, 2)]
    for name in ("serve.queue", "serve.block", "serve.collect"):
        assert sorted(r["rid"] for r in named(name)) == rids, name
    queue = {r["rid"]: r for r in named("serve.queue")}
    block = {r["rid"]: r for r in named("serve.block")}
    collect = {r["rid"]: r for r in named("serve.collect")}
    for rid in rids:
        bid = queue[rid]["batch"]
        assert block[rid]["batch"] == collect[rid]["batch"] == bid
        assert queue[rid]["start_ns"] <= queue[rid]["end_ns"] \
            <= batches[bid]["start_ns"]
        assert collect[rid]["start_ns"] >= block[rid]["end_ns"]
        assert "error" not in queue[rid]
    assert [queue[rid]["source"] for rid in rids] == ["full"] * 4 + ["timer"]
    # each batch carried as many requests as it had real lanes
    for bid, b in batches.items():
        assert sum(q["batch"] == bid for q in queue.values()) == b["real"]
    assert queue[0]["batch"] == queue[1]["batch"] != queue[2]["batch"]
    # a flush run by a full submit stays its child: admission subtracts it
    submits = {r["rid"]: r for r in named("serve.submit")}
    for r in named("serve.batch") + named("serve.dispatch"):
        if r["batch"] != queue[4]["batch"]:
            owner = submits[rids[2 * r["batch"] + 1]]
            assert r["parent"] == "serve.submit"
            assert r["parent_id"] == owner["id"]
        else:
            assert r["parent"] is None
    for r in named("serve.pad"):
        assert r["parent_id"] == submits[r["rid"]]["id"]


def test_failed_flush_ends_each_queue_span_with_an_error():
    from repro.obs.span import clear_spans, spans

    srv = GWServer(ServeConfig(max_batch=8, max_wait_s=60.0,
                               flush_thread=False, on_failure="none"))
    srv._exec = _failing_exec
    clear_spans()
    rids = [srv.submit(_problem(s, 14), CLEAN) for s in range(2)]
    srv.flush()
    queue = [r for r in spans() if r["name"] == "serve.queue"]
    batch = [r for r in spans() if r["name"] == "serve.batch"]
    assert sorted(r["rid"] for r in queue) == rids
    assert all(r["error"] is True and r["batch"] == batch[0]["batch"]
               and r["end_ns"] >= batch[0]["start_ns"] for r in queue)
    assert srv._requests[rids[0]].queue_wait_s == 0.0   # never dispatched
    assert not [r for r in spans() if r["name"] == "serve.collect"]


def test_queue_wait_metric_is_the_queue_span():
    """``ServeMetrics`` times the wait from enqueue to the flush's start,
    the ``serve.queue`` span, not to the end of dispatch."""
    from repro.obs.span import clear_spans, spans

    srv = GWServer(ServeConfig(max_batch=2, max_wait_s=60.0,
                               flush_thread=False, on_failure="none"))
    clear_spans()
    rids = [srv.submit(_problem(s, 14), CLEAN) for s in range(2)]
    srv.results(rids)
    waits = sorted(r["duration_s"] for r in spans()
                   if r["name"] == "serve.queue")
    assert len(waits) == 2
    assert sorted(srv.metrics.queue_waits_s) == waits
