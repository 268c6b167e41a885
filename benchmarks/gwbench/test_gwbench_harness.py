"""Tests of the benchmark harness that need no chip: discovery by name,
the limits of ``BENCHMARK.json``, generators, the problems a config poses,
the closed loop, the work count, the trace reduction, the peaks table,
and the command's refusal off the TPU."""
import dataclasses
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import peaks  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


# -- discovery ----------------------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == c.workload["config"]
    assert c.traffic["in_flight"] >= 1
    assert c.limits, "a cell compares at least one number"
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "a cell reports at least one per-layer metric"


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_reader_declares_what_benchmark_says(metric):
    mod = harness.load_layer_metric(metric["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        metric["unit"], metric["layer"], metric["moves"])
    assert callable(mod.read)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no_such_cell")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_name_a_cell_uses_has_its_file(cell):
    c = harness.load_cell(cell)
    assert callable(harness.plugin(
        "geometries", c.config["geometry"]["generator"]).__dict__.get(
            "pair" if c.traffic["pool"]["kind"] == "fresh_pairs"
            else "collection"))
    assert callable(harness.plugin("pools", c.traffic["pool"]["kind"]).build)
    assert callable(c.loop.run) and callable(c.loop.widths)
    assert c.traffic["order"] in harness.ORDERS
    fam = c.family
    assert set(c.limits) <= set(fam.NUMBERS), "a limit names a number"
    for measure, how in fam.NUMBERS.values():
        assert how in harness.AGGREGATE
    for m in c.end_to_end:
        assert harness.plugin("end_to_end", m["name"]).UNIT == m["unit"]


@pytest.mark.parametrize("kind,field", [("loops", "loop"),
                                        ("pools", "pool"),
                                        ("order", "order")])
def test_a_traffic_naming_what_has_no_file_is_refused(kind, field):
    c = harness.load_cell("moon_spar_n1000")
    c.traffic["pool"].update(n=20, size=2)
    if field == "loop":
        c.traffic["loop"] = "open"
        with pytest.raises(FileNotFoundError, match="no loops named 'open'"):
            c.loop
        return
    if field == "pool":
        c.traffic["pool"]["kind"] = "zipf_catalog"
    else:
        c.traffic["order"] = "zipf"
    with pytest.raises((FileNotFoundError, ValueError)):
        harness.build_traffic(c.config, c.traffic, 1)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_pins_the_solver_semantics(cfg):
    c = json.loads((ROOT / cfg["file"]).read_text())
    solver = c["solver"]
    for k in ("family", "epsilon", "outer_iters", "inner_iters", "reg",
              "loss", "tol"):
        assert k in solver, k
    if solver["family"] == "spar_gw":
        assert solver["s_per_n"] == 16
    assert c["reduced"] == cfg["reduced"]


# -- BENCHMARK.json against the contract's limits -----------------------------

def test_benchmark_json_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(one_line(w) and not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    for p in BENCH["paths"]:
        assert PATH.match(p) and (ROOT / p).is_dir()
    assert all(any(w.startswith(p) for p in BENCH["paths"])
               for w in BENCH["command"][1:] if "/" in w)


def test_benchmark_entries_have_exactly_the_allowed_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith(tuple(BENCH["paths"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"])


def test_names_and_units_use_the_allowed_characters():
    groups = [BENCH["configs"], BENCH["workloads"], BENCH["end_to_end"],
              BENCH["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for f in HERE.rglob("*"):
        if "__pycache__" not in f.parts and f.is_file():
            rel = f.relative_to(ROOT).as_posix()
            assert PATH.match(rel), rel


def test_every_cell_reports_setup_and_a_config_is_used():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in BENCH["workloads"]}


# -- generators ---------------------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_traffic_is_a_function_of_the_seed(cell):
    c = harness.load_cell(cell)
    c.traffic["pool"].update(n=40, size=3) if "n" in c.traffic["pool"] \
        else c.config["geometry"].update(count=12)
    a = harness.build_traffic(c.config, c.traffic, 2**31 + 5)
    b = harness.build_traffic(c.config, c.traffic, 2**31 + 5)
    other = harness.build_traffic(c.config, c.traffic, 7)
    assert np.array_equal(a.order, b.order)
    assert np.array_equal(a.key_seeds, b.key_seeds)
    for (x, w), (y, v) in zip(a.geoms, b.geoms):
        assert np.array_equal(x, y) and np.array_equal(w, v)
    assert not all(np.array_equal(x, y)
                   for (x, _), (y, _) in zip(a.geoms, other.geoms))
    # the seed reorders and redraws; it never changes the sizes
    assert sorted(len(w) for _, w in a.geoms) == \
        sorted(len(w) for _, w in other.geoms)
    for C, w in a.geoms:
        assert np.all(w > 0) and abs(float(w.sum()) - 1.0) < 1e-5
        assert np.allclose(C, C.T)


def test_graph_sizes_follow_the_configuration():
    c = harness.load_cell("mutag_allpairs").config["geometry"]
    graphs = harness.plugin("geometries", "graphs")
    sizes = graphs.graph_sizes(c)
    assert len(sizes) == c["count"] == 188
    assert min(sizes) == c["nodes_min"] and max(sizes) == c["nodes_max"]
    assert abs(np.mean(sizes) - c["nodes_mean"]) < 0.1
    g = graphs.collection(c, np.random.default_rng(0))
    edges = np.mean([A.sum() / 2 for A, _ in g])
    assert abs(edges - c["edges_mean"]) < 0.5
    for A, _ in g:           # connected: every node has an edge
        assert np.all(A.sum(1) > 0)


# -- the problem a configuration poses ----------------------------------------

def _small(cell: str):
    c = harness.load_cell(cell)
    if "n" in c.traffic["pool"]:
        c.traffic["pool"].update(n=40, size=3)
    else:
        c.config["geometry"].update(count=12)
    return c


def _testdata_cell(name: str, n: int = 24):
    """A cell of the test config ``testdata/<name>.json`` on the closed
    two-client Moon traffic, at n points a side."""
    base = harness.load_cell("moon_spar_n1000")
    base.traffic["pool"].update(n=n, size=2)
    config = json.loads((HERE / "testdata" / f"{name}.json").read_text())
    return dataclasses.replace(base, name=name, config=config, limits={})


# sha256 of each cell's traffic at seed 2**31 + 5 (geometries, pairs,
# order, key seeds), at the sizes of ``_small``, as the benchmark made it
# before a config could carry a problem block
TRAFFIC_DIGESTS = {
    "moon_spar_n1000":
        "ee718d9786fc321725f38929eebe10f41e636402e91dc2c6ddc139ac10cbf6f8",
    "moon_spar_n500":
        "ee718d9786fc321725f38929eebe10f41e636402e91dc2c6ddc139ac10cbf6f8",
    "mutag_allpairs":
        "7d06c52a1fa2344a3f0edee9cabc7053e53e19b318ba502f07bed51d2164989a",
}


@pytest.mark.parametrize("cell", sorted(TRAFFIC_DIGESTS))
def test_existing_traffic_is_unchanged(cell):
    c = _small(cell)
    t = harness.build_traffic(c.config, c.traffic, 2**31 + 5)
    h = hashlib.sha256()
    for C, w in t.geoms:
        h.update(C.tobytes())
        h.update(w.tobytes())
    h.update(repr(t.pairs).encode())
    h.update(t.order.tobytes())
    h.update(t.key_seeds.tobytes())
    assert h.hexdigest() == TRAFFIC_DIGESTS[cell]
    assert t.problem_terms == {}
    assert [(s["relation"], s["weights"]) for s in t.sides] == t.geoms


@pytest.mark.parametrize("cell", ["moon_spar_n1000", "mutag_allpairs"])
def test_existing_configs_build_bitwise_the_same_problems(cell):
    """``Client.make`` gives what it gave before a config could pose more
    than a balanced problem: ``QuadraticProblem(Geometry(C, w),
    Geometry(C', w'), loss=...)``, the same treedef and the same leaves."""
    import jax
    import jax.numpy as jnp

    harness.add_src_to_path()
    import repro

    c = _small(cell)
    t = harness.build_traffic(c.config, c.traffic, 2**31 + 5)
    client = harness.Client(c, t)
    for i in range(len(t.pairs)):
        problem, solver, key = client.request(i)
        (Cx, a), (Cy, b) = (t.geoms[j] for j in t.pair_of(i))
        want = repro.QuadraticProblem(
            repro.Geometry(jnp.asarray(Cx), jnp.asarray(a), validate=False),
            repro.Geometry(jnp.asarray(Cy), jnp.asarray(b), validate=False),
            loss=c.config["solver"]["loss"], validate=False)
        got_leaves, got_def = jax.tree.flatten(problem)
        want_leaves, want_def = jax.tree.flatten(want)
        assert got_def == want_def
        assert len(got_leaves) == len(want_leaves) == 4
        for x, y in zip(got_leaves, want_leaves):
            assert x.dtype == y.dtype and np.array_equal(x, y)
        assert not (problem.is_unbalanced or problem.is_fused
                    or problem.geom_x.is_point_cloud)
        assert solver == repro.get_solver(c.config["solver"]["family"])(
            **harness.solver_fields(c.config, max(problem.shape)))
        if key is not None:
            assert np.array_equal(key, jax.random.PRNGKey(t.key_seed(i)))


@pytest.mark.parametrize("name,unbalanced,fused,points", [
    ("moon_ugw", True, False, False),
    ("moon_cloud", False, False, True),
    ("moon_fgw_cloud", False, True, True),
])
def test_a_config_poses_its_whole_problem(name, unbalanced, fused, points):
    harness.add_src_to_path()
    c = _testdata_cell(name)
    t = harness.build_traffic(c.config, c.traffic, 2**31 + 7)
    assert t.problem_terms == c.config.get("problem", {})
    x, y = t.geometry_data(0)
    assert ("points" in x) is points and ("features" in y) is fused
    problem, _, _ = harness.Client(c, t).request(0)
    assert problem.is_unbalanced is unbalanced
    assert problem.is_fused is fused
    assert problem.geom_x.is_point_cloud is points
    assert problem.geom_y.is_point_cloud is points
    if unbalanced:
        assert problem.lam == c.config["problem"]["lam"]
    if fused:
        assert problem.fused_penalty == c.config["problem"]["fused_penalty"]
        assert problem.geom_x.features.shape == (24, 5)
    if points:
        assert problem.geom_x.cost is None
        assert np.array_equal(problem.geom_x.points, x["points"])
    else:
        assert t.problem_data(0)[0] is x["relation"]
    problem.check()      # what the program itself accepts


@pytest.mark.parametrize("name,family", [
    ("moon_ugw", "spar_gw.unbalanced"),
    ("moon_cloud", "spar_gw"),
    ("moon_fgw_cloud", "spar_gw.fused"),
])
def test_family_file_follows_the_variant_of_the_problem(name, family):
    c = _testdata_cell(name)
    assert harness.family_name(c.config) == family
    c.config["problem"] = {"lam": 0.5, "fused_penalty": 0.5}
    assert harness.family_name(c.config) == "spar_gw.fused.unbalanced"


@pytest.mark.parametrize("problem,match", [
    ({"lambda": 1.0}, "unknown problem terms"),
    ({"M": [[0.0]]}, "unknown problem terms"),
    ({"lam": 0.0}, "lam must be > 0"),
    ({"fused_penalty": 1.5}, "fused_penalty must lie in"),
    ({"fused_penalty": 0.5}, "go together"),
])
def test_a_bad_problem_block_is_refused(problem, match):
    c = _testdata_cell("moon_ugw")
    c.config["problem"] = problem
    with pytest.raises(ValueError, match=match):
        harness.build_traffic(c.config, c.traffic, 1)


def test_features_without_fused_penalty_are_refused():
    c = _testdata_cell("moon_fgw_cloud")
    del c.config["problem"]
    with pytest.raises(ValueError, match="go together"):
        harness.build_traffic(c.config, c.traffic, 1)


@pytest.mark.parametrize("side,match", [
    ({"relation": np.eye(2), "weights": np.ones(2) / 2, "labels": 1},
     "unknown geometry keys"),
    ({"relation": np.eye(2)}, "needs weights"),
    ({"weights": np.ones(2) / 2, "features": np.eye(2)}, "needs weights"),
])
def test_a_bad_geometry_side_is_refused(side, match):
    with pytest.raises(ValueError, match=match):
        harness.as_side(side)


# -- the closed loop ----------------------------------------------------------

class FakeServer:
    """Answers requests in batches of ``lanes``: the first ``result`` of a
    batch completes all of it. Logs every call."""

    def __init__(self, lanes):
        self.lanes, self.log, self.next, self.ready = lanes, [], 0, set()

    def submit(self, problem, solver, key=None):
        rid, self.next = self.next, self.next + 1
        self.log.append(("submit", rid))
        return rid

    def poll(self, rid):
        return "done" if rid in self.ready else "running"

    def result(self, rid):
        self.log.append(("result", rid))
        first = rid - rid % self.lanes
        self.ready |= set(range(first, first + self.lanes))
        return rid


class FakeClient:
    def request(self, i):
        return None, None, None


def test_closed_loop_refills_a_batch_together():
    loop = harness.plugin("loops", "closed")
    t = iter(range(10**6))
    server = FakeServer(lanes=2)
    t0, done = loop.run(server, FakeClient(), {"in_flight": 4}, 20,
                        clock=lambda: next(t))
    # both answers of a batch are taken before either successor is sent
    first = server.log.index(("submit", 4))
    assert server.log[4:first] == [("result", 0), ("result", 1)]
    assert server.log[first + 1] == ("submit", 5)
    # every request sent is answered; the window ends on whole batches
    sent = [r for op, r in server.log if op == "submit"]
    assert sorted(d.index for d in done) == sorted(sent) == list(range(
        len(sent)))
    assert len(sent) % 2 == 0 and all(d.finished >= d.submitted
                                      for d in done)


def test_closed_loop_widths():
    loop = harness.plugin("loops", "closed")
    cfg = type("Cfg", (), {"max_batch": 8})
    assert loop.widths({"in_flight": 64}, cfg) == [2, 4, 8]
    assert loop.widths({"in_flight": 2}, cfg) == [2]
    cfg.max_batch = 2
    assert loop.widths({"in_flight": 64}, cfg) == [2]


# -- work count, peaks, order statistics --------------------------------------

def test_cost_contraction_work_by_hand():
    w = peaks.cost_contraction_work(16000, 1000, 1000)
    assert w.flops == 4 * 16000 * 16000 == 1.024e9
    # Cx, Cy: 2 * 1000**2 floats; rows, cols, t, off, out: 5 * 16000
    assert w.bytes == 4 * (2 * 1000**2) + 4 * 5 * 16000 == 8_320_000
    t, bound = peaks.least_time_s(w, peaks.peaks("TPU v5 lite"))
    assert bound == "bytes"
    assert t == pytest.approx(8_320_000 / 819e9)
    small = peaks.cost_contraction_work(1, 1, 1)
    assert small == peaks.Work(flops=4.0, bytes=4.0 * 2 + 20.0)


def test_peaks_unknown_device_is_an_error():
    assert peaks.peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")


def test_percentile_and_quartile_spread():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert stats.percentile(list(range(101)), 95) == 95.0
    assert np.isnan(stats.percentile([], 95))
    # statistics.quantiles, exclusive method: q1 = 1.75, q3 = 5.25 for 1..6
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6]) == \
        pytest.approx((5.25 - 1.75) / 3.5)


# -- trace reduction ----------------------------------------------------------

def test_trace_reduction_on_recorded_trace():
    rec = json.loads((HERE / "testdata" / "trace_small.json").read_text())
    red = trace_reduce.reduce(rec["trace"], rec["attribute"])
    exp = rec["expected"]
    assert red["window_s"] == pytest.approx(exp["window_s"])
    assert red["busy_s"] == pytest.approx(exp["busy_s"])
    for g, v in exp["attributed"].items():
        assert red["attributed"][g]["seconds"] == pytest.approx(v["seconds"])
        assert red["attributed"][g]["events"] == v["events"]
    assert [g[0] for g in red["idle_gaps"]][:len(exp["gap_labels"])] == \
        exp["gap_labels"]


def test_union_gaps_and_labels_by_hand():
    busy = trace_reduce.union([(0, 10), (5, 20), (30, 40), (45, 60)], 2, 50)
    assert busy == [(2, 20), (30, 40), (45, 50)]
    assert trace_reduce.gaps(busy, 0, 55) == [(0, 2), (20, 30), (40, 45),
                                              (50, 55)]
    host = [["serve.submit", 0, 100], ["serve.pad", 10, 20]]
    assert trace_reduce.label_at(15, host) == "serve.pad"
    assert trace_reduce.label_at(50, host) == "serve.submit"
    assert trace_reduce.label_at(150, host) == "client"
    trace = {"window": [0, 100], "host": host,
             "ops": [["a", 0, 30, ""], ["_fused_kernel", 20, 20, ""],
                     ["b", 60, 10, "jit(f)/kernels/spar_cost/x"],
                     ["c", 90, 50, ""]]}
    red = trace_reduce.reduce(trace, {"spar_cost": ["_fused_kernel",
                                                    "kernels/spar_cost/"]})
    assert red["busy_s"] == pytest.approx(60e-9)     # [0,40] [60,70] [90,100]
    assert red["attributed"]["spar_cost"] == {"seconds": pytest.approx(30e-9),
                                              "events": 2}
    assert red["idle_gaps"][0][1] == pytest.approx(20e-9)
    # the kernel starts inside "a" [0, 30], so it counts as nested in it;
    # "c" is clipped at the window's end
    assert dict(red["device_ops"]) == pytest.approx(
        {"a": 10e-9, "_fused_kernel": 20e-9, "b": 10e-9, "c": 10e-9})


def test_op_names_and_self_time_of_nested_ops():
    assert trace_reduce.short_name(
        "%spar_cost_pallas.7 = f32[2,1,16128]{2,1,0} custom-call(%x)") == \
        "spar_cost_pallas.7"
    assert trace_reduce.short_name("wrapped_sqrt") == "wrapped_sqrt"
    ops = [["while.1", 0, 100, ""], ["fusion.2", 10, 20, ""],
           ["while.3", 40, 50, ""], ["fusion.4", 45, 10, ""]]
    assert trace_reduce.self_times(ops, 0, 100) == {
        "while.1": 30, "fusion.2": 20, "while.3": 40, "fusion.4": 10}


# -- the command --------------------------------------------------------------

def _run(cwd, script, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, "--workload", "moon_spar_n1000", "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def _last_line_is_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].lstrip().startswith("{")


def test_command_refuses_without_a_tpu():
    p = _run(ROOT, "benchmarks/gwbench/run.py")
    assert p.returncode != 0
    assert not _last_line_is_result(p.stdout)
    assert "no TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "benchmarks/gwbench/run.py")
    assert p.returncode != 0
    assert not _last_line_is_result(p.stdout)
