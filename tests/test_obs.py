"""Unified telemetry layer: convergence traces (in-jit, vmap-safe),
lifecycle spans, the process metrics registry, and the exporters."""
import dataclasses
import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import obs
from repro.health import CONVERGED, DIVERGED, MAXITER, FaultSpec, health_loop
from repro.obs.registry import MetricsRegistry
from repro.serve import GWServer, ServeConfig

KEY = jax.random.PRNGKey(0)
N = 24


def _problem(seed=0, n=N, loss="l2"):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))

    def cloud(key, scale):
        x = jax.random.normal(key, (n, 2)) * scale
        return jnp.sqrt(jnp.sum((x[:, None] - x[None, :]) ** 2, -1))

    a = jnp.ones(n) / n
    return repro.QuadraticProblem(repro.Geometry(cloud(kx, 1.0), a),
                                  repro.Geometry(cloud(ky, 1.2), a),
                                  loss="l2")


# ---------------------------------------------------------------------------
# Convergence traces: health_loop unit behavior
# ---------------------------------------------------------------------------

def test_trace_off_is_bitwise_identical():
    """trace=False must be the exact pre-obs loop: same bits, no trace."""
    step = lambda T: 0.9 * T + 0.1           # noqa: E731
    err = lambda T: jnp.sum(jnp.abs(T - 1))  # noqa: E731
    plain = health_loop(step, err, jnp.zeros(4), 30, 1e-6)
    traced = health_loop(step, err, jnp.zeros(4), 30, 1e-6, trace=True)
    assert plain.trace is None
    assert traced.trace is not None
    np.testing.assert_array_equal(np.asarray(plain.iterate),
                                  np.asarray(traced.iterate))
    np.testing.assert_array_equal(np.asarray(plain.errors),
                                  np.asarray(traced.errors), strict=True)
    assert int(plain.n_iters) == int(traced.n_iters)
    assert int(plain.status.code) == int(traced.status.code)


def test_trace_length_equals_n_iters_converged():
    step = lambda T: 0.5 * T + 0.5           # noqa: E731 — fast contraction
    err = lambda T: jnp.sum(jnp.abs(T - 1))  # noqa: E731
    res = health_loop(step, err, jnp.zeros(4), 100, 1e-6, trace=True)
    assert int(res.status.code) == CONVERGED
    n = int(res.n_iters)
    assert 0 < n < 100
    assert obs.n_valid(res.trace) == n
    # recorded prefix is finite, the rest stays NaN fill
    assert np.all(np.isfinite(np.asarray(res.trace.err)[:n]))
    assert np.all(np.isnan(np.asarray(res.trace.err)[n:]))


def test_trace_length_equals_n_iters_maxiter():
    step = lambda T: T + 1.0                 # noqa: E731 — never settles
    err = lambda T: jnp.float32(0.0)         # noqa: E731
    res = health_loop(step, err, jnp.zeros(2), 7, 1e-9, trace=True)
    assert int(res.status.code) == MAXITER
    assert int(res.n_iters) == 7
    assert obs.n_valid(res.trace) == 7


def test_trace_records_rescue_forensics():
    """A rescue iteration keeps its record: the bad mass, the scale that
    failed, rescued=1; the next attempt runs at the escalated scale."""
    step = lambda T: 0.9 * T + 0.1           # noqa: E731
    err = lambda T: jnp.sum(jnp.abs(T - 1))  # noqa: E731
    res = health_loop(step, err, jnp.zeros(4), 10, 0.0, max_rescues=2,
                      fault=FaultSpec(at_iter=2, kind="nan"), trace=True)
    tr = res.trace
    rescued = np.asarray(tr.rescued)
    assert rescued[2] == 1.0 and np.nansum(rescued) == 1.0
    assert not np.isfinite(np.asarray(tr.mass)[2])   # the poisoned attempt
    scale = np.asarray(tr.scale)
    assert scale[2] == 1.0                  # scale in effect when it failed
    assert scale[3] == 2.0                  # escalated after the rescue
    # err/objective/delta describe accepted steps only: NaN at the rescue
    assert np.isnan(np.asarray(tr.err)[2])
    assert int(res.status.n_rescues) == 1


def test_trace_objective_column():
    step = lambda T: 0.5 * T + 0.5           # noqa: E731
    err = lambda T: jnp.sum(jnp.abs(T - 1))  # noqa: E731
    obj = lambda T: jnp.sum(T)               # noqa: E731
    with_obj = health_loop(step, err, jnp.zeros(4), 50, 1e-6, trace=True,
                           obj_fn=obj)
    n = int(with_obj.n_iters)
    assert np.all(np.isfinite(np.asarray(with_obj.trace.objective)[:n]))
    without = health_loop(step, err, jnp.zeros(4), 50, 1e-6, trace=True)
    assert np.all(np.isnan(np.asarray(without.trace.objective)))
    # trace_to_dict maps the NaN objective column to None, not NaN
    doc = obs.trace_to_dict(without.trace)
    assert doc["objective"] == [None] * doc["n_iters"]
    json.dumps(doc)


def test_trace_vmap_lane_isolation():
    """One poisoned lane dies with its own forensic trace; its healthy
    peer's buffers are untouched (the health layer's masking contract)."""
    def run(at_iter):
        step = lambda T: 0.9 * T + 0.1           # noqa: E731
        err = lambda T: jnp.sum(jnp.abs(T - 1))  # noqa: E731
        res = health_loop(step, err, jnp.zeros(4), 10, 0.0,
                          fault=FaultSpec(at_iter=at_iter, kind="nan"),
                          trace=True)
        return res.trace, res.status.code, res.n_iters

    traces, codes, n_iters = jax.jit(jax.vmap(run))(
        jnp.array([-1, 3], jnp.int32))
    assert traces.err.shape == (2, 10)
    assert int(codes[0]) == MAXITER and int(codes[1]) == DIVERGED
    # healthy lane: full-length, everywhere-finite record
    assert np.all(np.isfinite(np.asarray(traces.mass)[0]))
    assert np.nansum(np.asarray(traces.rescued)[0]) == 0.0
    # poisoned lane: dead at iter 3 — 4 consumed iterations, bad mass at 3
    assert int(n_iters[1]) == 4
    lane1 = jax.tree.map(lambda x: x[1], traces)
    assert obs.n_valid(lane1) == 4
    assert not np.isfinite(np.asarray(traces.mass)[1, 3])
    assert np.all(np.isnan(np.asarray(traces.err)[1, 4:]))


# ---------------------------------------------------------------------------
# Convergence traces: through the solver stack
# ---------------------------------------------------------------------------

def test_solver_trace_off_bitwise_identical():
    problem = _problem()
    base = repro.DenseGWSolver(outer_iters=8, tol=0.0, inner_tol=1e-8)
    out_off = repro.solve(problem, base, validate=False)
    out_on = repro.solve(problem, dataclasses.replace(base, trace=True),
                         validate=False)
    assert out_off.trace is None
    np.testing.assert_array_equal(np.asarray(out_off.coupling_dense(N, N)),
                                  np.asarray(out_on.coupling_dense(N, N)))
    assert float(out_off.value) == float(out_on.value)


@pytest.mark.parametrize("name,kw", [
    ("dense_gw", dict(outer_iters=8, inner_tol=1e-8)),
    ("spar_gw", dict(s=8 * N, outer_iters=8, inner_tol=1e-8)),
    ("grid_gw", dict(s_r=12, s_c=12, outer_iters=8, inner_tol=1e-8)),
    ("lowrank_gw", dict(outer_iters=20)),
])
def test_every_family_produces_a_trace(name, kw):
    problem = _problem()
    solver = dataclasses.replace(
        repro.get_solver(name).default_config(N), trace=True, **kw)
    key = KEY if getattr(type(solver), "requires_key", False) else None
    out = repro.solve(problem, solver, key=key, validate=False)
    assert out.trace is not None
    n = int(out.n_iters)
    assert obs.n_valid(out.trace) == n > 0
    # every family supplies an obj_fn: the objective column is populated
    assert np.all(np.isfinite(np.asarray(out.trace.objective)[:n]))
    doc = obs.trace_to_dict(out.trace, n)
    assert doc["n_iters"] == n and len(doc["err"]) == n
    json.dumps(doc)


def test_solver_trace_under_jit_vmap():
    problem = _problem()
    solver = repro.SparGWSolver(s=8 * N, outer_iters=6, tol=0.0,
                                inner_tol=1e-8, trace=True)
    keys = jax.random.split(KEY, 2)
    out = jax.jit(jax.vmap(lambda k: solver.run(problem, k)))(keys)
    assert out.trace.err.shape == (2, 6)
    assert np.all(np.isfinite(np.asarray(out.trace.err)))
    # distinct supports -> distinct per-lane trajectories
    assert not np.array_equal(np.asarray(out.trace.err)[0],
                              np.asarray(out.trace.err)[1])


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_attrs():
    obs.clear_spans()
    with obs.span("outer", tag="a"):
        with obs.span("inner") as sp:
            sp["extra"] = 42
    recs = obs.spans()
    by_name = {r["name"]: r for r in recs}
    assert by_name["outer"]["depth"] == 0 and by_name["outer"]["tag"] == "a"
    assert by_name["inner"]["depth"] == 1
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["inner"]["extra"] == 42
    # start order: outer first despite completing last
    assert [r["name"] for r in recs] == ["outer", "inner"]
    bd = obs.span_breakdown(recs)
    assert bd["outer"]["count"] == 1
    assert bd["outer"]["total_s"] >= by_name["inner"]["duration_s"]


def test_span_stack_is_thread_local():
    obs.clear_spans()
    ready = threading.Barrier(2)

    def work(tag):
        ready.wait()
        with obs.span("t", tag=tag):
            pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = [r for r in obs.spans() if r["name"] == "t"]
    assert len(recs) == 2
    # neither thread saw the other's span as its parent
    assert all(r["depth"] == 0 and r["parent"] is None for r in recs)


def test_record_lands_in_ring_with_id_and_no_annotation(monkeypatch):
    from repro.obs.span import now_ns, record

    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    obs.configure(xla_annotations=True)
    try:
        obs.clear_spans()
        t0 = now_ns()
        with obs.span("open"):       # a record made inside a span
            rec = record("waited", t0, t0 + 2_500_000, rid=7)
    finally:
        obs.configure(None)
    by_name = {r["name"]: r for r in obs.spans()}
    assert entered == ["open"]       # the span's annotation, not the record's
    got = by_name["waited"]
    assert got == rec
    assert isinstance(got["id"], int) and got["id"] != by_name["open"]["id"]
    assert got["parent_id"] is None and got["parent"] is None
    assert got["depth"] == 0 and got["rid"] == 7
    assert (got["start_ns"], got["end_ns"]) == (t0, t0 + 2_500_000)
    assert got["duration_s"] == pytest.approx(2.5e-3)


def test_parent_id_follows_the_enclosing_span_across_threads():
    obs.clear_spans()
    ready = threading.Barrier(2)

    def work(tag):
        with obs.span("root", tag=tag):
            ready.wait()             # both roots open at once
            with obs.span("mid", tag=tag):
                with obs.span("leaf", tag=tag):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = obs.spans()
    assert len({r["id"] for r in recs}) == len(recs) == 6
    by_id = {r["id"]: r for r in recs}
    for r in recs:
        if r["name"] == "root":
            assert r["parent_id"] is None
            continue
        parent = by_id[r["parent_id"]]
        assert parent["name"] == r["parent"]
        assert parent["tag"] == r["tag"]          # never the other thread's
        assert parent["thread"] == r["thread"]
        assert parent["start_ns"] <= r["start_ns"] <= r["end_ns"] \
            <= parent["end_ns"]


def test_span_ns_stamps_agree_with_seconds():
    import time

    from repro.obs.span import now_ns
    obs.clear_spans()
    before = now_ns()
    with obs.span("outer"):
        time.sleep(0.01)
        with obs.span("inner"):
            time.sleep(0.002)
    after = now_ns()
    recs = obs.spans()
    outer = recs[0]
    assert before <= outer["start_ns"] <= outer["end_ns"] <= after
    for r in recs:
        assert r["duration_s"] == pytest.approx(
            (r["end_ns"] - r["start_ns"]) * 1e-9, abs=1e-12)
    # start_s is the same reading on a process-relative zero
    assert recs[1]["start_s"] - outer["start_s"] == pytest.approx(
        (recs[1]["start_ns"] - outer["start_ns"]) * 1e-9, abs=1e-9)
    assert outer["duration_s"] >= 0.012


def test_solve_emits_lifecycle_spans():
    obs.clear_spans()
    problem = _problem(seed=3)
    repro.solve(problem,
                repro.DenseGWSolver(tol=1e-6, inner_tol=1e-8,
                                    outer_iters=10),
                on_failure="raise")
    names = [r["name"] for r in obs.spans()]
    assert "solve" in names and "solve.dispatch" in names
    disp = [r for r in obs.spans() if r["name"] == "solve.dispatch"]
    assert all(r["parent"] == "solve" for r in disp)
    assert all("compiled" in r for r in disp)


# ---------------------------------------------------------------------------
# Registry + exporters
# ---------------------------------------------------------------------------

def test_registry_primitives():
    reg = MetricsRegistry()
    c = reg.counter("r_total", "help", solver="dense")
    c.inc()
    c.inc(2)
    assert reg.counter("r_total", solver="dense") is c   # get-or-create
    assert c.value == 3.0
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("r_gauge")
    g.set(1.5)
    g.inc(0.5)
    assert g.value == 2.0
    with pytest.raises(ValueError):
        reg.gauge("r_total")        # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("bad name")


def test_histogram_and_snapshot_roundtrip():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 3 and h.bucket_counts == [1, 2]    # cumulative
    assert h.percentiles((50,))["p50"] == pytest.approx(0.5)
    reg.gauge("g").set(float("nan"))          # must not break JSON
    snap = json.loads(json.dumps(reg.snapshot()))
    row = snap["metrics"]["lat_seconds"]["series"][0]
    assert row["count"] == 3 and row["n_seen"] == 3
    assert snap["metrics"]["g"]["series"][0]["value"] is None


def test_reservoir_bounded_exact_then_sampled():
    r = obs.Reservoir(cap=16, seed=1)
    for i in range(16):
        r.add(float(i))
    assert sorted(r) == [float(i) for i in range(16)]    # exact below cap
    for i in range(1000):
        r.add(float(i))
    assert len(r) == 16 and r.n_seen == 1016             # bounded forever


def test_serve_metrics_latency_store_is_bounded():
    from repro.serve.metrics import ServeMetrics, percentiles
    m = ServeMetrics(sample_cap=8)
    for _ in range(50):
        t = m.record_submit()
        m.record_result(t, 0.0, failed=False, fell_back=False)
    assert len(m.latencies_s) == 8 and m.latencies_s.n_seen == 50
    assert m.summary()["n_completed"] == 50
    # the PR-7 shim: serve.metrics.percentiles is the obs definition
    assert percentiles is obs.percentiles


def test_percentiles_empty_is_nan():
    p = obs.percentiles([])
    assert all(np.isnan(v) for v in p.values())


def test_prometheus_text_validates():
    reg = MetricsRegistry()
    reg.counter("x_total", "things", kind='we"ird\n').inc(3)
    reg.histogram("x_seconds", "latency", buckets=(0.1, 1.0)).observe(0.2)
    text = reg.prometheus_text()
    n = obs.validate_exposition(text)
    # 1 counter sample + (2 buckets + +Inf + sum + count)
    assert n == 6
    assert "# TYPE x_seconds histogram" in text
    assert 'x_seconds_bucket{le="+Inf"} 1' in text
    with pytest.raises(ValueError):
        obs.validate_exposition("no trailing newline")
    with pytest.raises(ValueError):
        obs.validate_exposition("}bad{ 1\n")


def test_write_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c_total").inc()
    path = tmp_path / "metrics.jsonl"
    reg.write_jsonl(path, extra={"run": "a"})
    reg.write_jsonl(path)
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0])["run"] == "a"
    assert "c_total" in json.loads(lines[1])["metrics"]


def test_http_exporter():
    reg = MetricsRegistry()
    reg.counter("http_test_total").inc()
    server = obs.serve_metrics_http(0, reg=reg)      # ephemeral port
    host, port = server.server_address[:2]
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "http_test_total 1.0" in body
        obs.validate_exposition(body)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{host}:{port}/nope", timeout=5)
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# report(): one JSON document
# ---------------------------------------------------------------------------

def test_report_ties_everything_together():
    obs.clear_spans()
    problem = _problem(seed=5)
    solver = repro.DenseGWSolver(outer_iters=8, tol=0.0, inner_tol=1e-8,
                                 trace=True)
    out = repro.solve(problem, solver, on_failure="raise")
    doc = obs.report(out, solver="dense_gw")
    assert set(doc) == {"solve", "spans", "breakdown", "metrics"}
    assert doc["solve"]["solver"] == "dense_gw"
    assert doc["solve"]["n_iters"] == 8
    assert len(doc["solve"]["trace"]["err"]) == 8
    assert doc["breakdown"]["by_name"]["solve.dispatch"]["count"] >= 1
    assert doc["breakdown"]["compile_s"] + doc["breakdown"]["dispatch_s"] > 0
    assert "repro_solves_total" in doc["metrics"]["metrics"]
    json.dumps(doc)                      # the whole point: one JSON doc
    # argument-less report() describes the solve note_solve() stashed
    assert obs.report()["solve"]["n_iters"] == 8


# ---------------------------------------------------------------------------
# GWServer: flusher thread + Prometheus surface
# ---------------------------------------------------------------------------

def test_flusher_thread_fires_on_wall_clock():
    """A lone queued request must dispatch within ~max_wait_s with no
    further server calls — proven by the timer-tagged dispatch span."""
    obs.clear_spans()
    server = GWServer(ServeConfig(max_batch=8, max_wait_s=0.05,
                                  on_failure="none"))
    try:
        problem = _problem(seed=7, n=12)
        solver = repro.DenseGWSolver(outer_iters=4, inner_tol=1e-6)
        rid = server.submit(problem, solver)
        import time
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 5.0:
            timer_spans = [r for r in obs.spans()
                           if r["name"] == "serve.dispatch"
                           and r.get("source") == "timer"]
            if timer_spans:
                break
            time.sleep(0.02)
        assert timer_spans, "flusher thread never dispatched the bucket"
        res = server.result(rid)
        assert res.status_name in ("CONVERGED", "MAXITER")
    finally:
        server.close()


def test_flush_thread_off_is_cooperative():
    server = GWServer(ServeConfig(max_batch=8, max_wait_s=60.0,
                                  flush_thread=False, on_failure="none"))
    try:
        assert server._flusher is None
        rid = server.submit(_problem(seed=8, n=12),
                            repro.DenseGWSolver(outer_iters=4,
                                                inner_tol=1e-6))
        assert server.poll(rid) == "queued"      # nobody flushes for us
        res = server.result(rid)                 # result() forces the flush
        assert np.isfinite(res.value)
    finally:
        server.close()


def test_server_metrics_text_is_valid_exposition():
    server = GWServer(ServeConfig(max_batch=2, max_wait_s=60.0,
                                  on_failure="none"))
    try:
        solver = repro.DenseGWSolver(outer_iters=4, inner_tol=1e-6)
        rids = [server.submit(_problem(seed=9 + i, n=12), solver)
                for i in range(2)]
        server.results(rids)
        text = server.metrics_text()
        assert obs.validate_exposition(text) > 0
        assert "repro_serve_requests_total" in text
        assert "repro_serve_latency_seconds_bucket" in text
    finally:
        server.close()
