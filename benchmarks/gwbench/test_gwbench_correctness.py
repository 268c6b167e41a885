"""The check that decides ``correct``, shown to fail where it must.

At sizes a CPU test run holds, with each cell's own limits:

* the control -- the plain reference one precision lower (bfloat16) in
  the program's place -- fails at least one of the cell's numbers, while
  the program passes them all;
* a config that poses more than a balanced problem (``testdata/``: an
  unbalanced lam, fused features, point clouds) is served through the
  harness's own ``Client``, ``warm_up`` and loop with healthy answers;
* a whole run (``run.measure``, the chip check skipped) comes out
  ``correct`` on the program as it is and not correct with the timed
  path broken underneath: a step that returns its state unchanged, half
  of each batch left out (copied from lane 0), an answer altered where it
  is produced (its value scaled by 1.05).
"""
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

harness.add_src_to_path()

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def small_cell(name: str):
    """The cell at a size a test holds: its own limits and solver."""
    cell = harness.load_cell(name)
    if "n" in cell.traffic["pool"]:
        cell.traffic["pool"].update(n=48, size=3)
    else:
        cell.config["geometry"].update(count=14)
        cell.traffic["check_sample"] = 64
    return cell


@pytest.fixture
def no_persistent_cache(monkeypatch):
    import repro.serve
    monkeypatch.setattr(repro.serve, "enable_compilation_cache",
                        lambda: None)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(name):
    from repro.serve import GWServer

    cell = small_cell(name)
    traffic = harness.build_traffic(cell.config, cell.traffic, 2**31 + 11)
    client = harness.Client(cell, traffic)
    server = GWServer(harness.serve_config(cell.traffic))
    try:
        _, done = cell.loop.run(server, client, cell.traffic, 0.5)
    finally:
        server.close()
    assert done and not any(harness.failed(d) for d in done)
    checked = harness.served_answers(cell, done)
    program = harness.compared_numbers(cell, traffic, checked, cell.limits)
    ok, rows = harness.judge(program, cell.limits)
    assert ok, rows
    control = harness.compared_numbers(
        cell, traffic, checked, cell.limits,
        answers=harness.control_answers(cell, traffic, checked))
    ok, rows = harness.judge(control, cell.limits)
    assert not ok, rows


@pytest.mark.parametrize("name", ["moon_ugw", "moon_cloud",
                                  "moon_fgw_cloud"])
def test_a_config_with_a_problem_block_is_served(name, no_persistent_cache):
    """The harness serves what a config poses with no code of its own:
    warm-up and the closed loop go through ``Client.make``."""
    from repro.serve import GWServer

    cell = dataclasses.replace(
        small_cell("moon_spar_n1000"), name=name, limits={},
        config=json.loads((HERE / "testdata" / f"{name}.json").read_text()))
    cell.traffic["pool"].update(n=24, size=2)
    traffic = harness.build_traffic(cell.config, cell.traffic, 2**31 + 13)
    client = harness.Client(cell, traffic)
    server = GWServer(harness.serve_config(cell.traffic))
    try:
        harness.warm_up(server, client, cell)
        _, done = cell.loop.run(server, client, cell.traffic, 0.5)
    finally:
        server.close()
    assert done and not any(harness.failed(d) for d in done), [
        (d.error, d.result and d.result.status_name) for d in done]
    problem = client.request(0)[0]
    terms = cell.config.get("problem", {})
    assert problem.is_unbalanced is ("lam" in terms)
    assert problem.is_fused is ("fused_penalty" in terms)
    for d in done:
        T = np.asarray(d.result.output.coupling[2])
        assert np.all(np.isfinite(T)) and np.all(T >= 0)
        mass = float(T.sum())
        # a balanced coupling carries unit mass, less the tail mass of
        # columns that the sampled support misses (about 1e-3 at n = 24);
        # an unbalanced one is rescaled, and with lam = 1 keeps most of it
        assert (0.5 < mass < 1.5) if "lam" in terms else \
            abs(mass - 1.0) < 1e-2


def test_blocked_reference_contraction_matches_whole():
    """Past ``WHOLE_LOSS_BYTES`` (s = 32000, not 16000) the spar reference
    contracts its support loss matrix block by block over the rows; it
    gives what the whole product gives, to float32 rounding, also where
    the blocks do not divide s."""
    import jax.numpy as jnp

    fam = harness.plugin("families", "spar_gw")
    assert 4 * 16000**2 <= fam.WHOLE_LOSS_BYTES < 4 * 32000**2
    rng = np.random.default_rng(5)
    spec = harness.load_cell("moon_spar_n1000").config["geometry"]
    (Cx, a), (Cy, b) = harness.plugin("geometries", "moon").pair(spec, 30,
                                                                  rng)
    rows, cols = rng.integers(0, 30, 200), rng.integers(0, 30, 200)
    solver = {"epsilon": 0.01, "outer_iters": 5, "inner_iters": 20}
    whole, blocked = (
        fam._spar(jnp.asarray(Cx), jnp.asarray(a), jnp.asarray(Cy),
                  jnp.asarray(b), jnp.asarray(rows, jnp.int32),
                  jnp.asarray(cols, jnp.int32), solver["epsilon"], m=30,
                  n=30, outer=5, inner=20, block=64, blocked=flag,
                  dtype=jnp.float32) for flag in (False, True))
    assert float(whole[0]) > 0
    np.testing.assert_allclose(float(blocked[0]), float(whole[0]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(blocked[1]), np.asarray(whole[1]),
                               rtol=1e-4, atol=1e-7)


def _wrap_exec(monkeypatch, alter):
    """Break the server's batched executable: ``alter(out, lanes)``."""
    from repro.serve import server as srv

    init = srv.GWServer.__init__

    def broken_init(self, *a, **kw):
        init(self, *a, **kw)
        exe = self._exec

        def run(p, s, k):
            out = exe(p, s, k)
            return alter(out, jax.tree.leaves(p)[0].shape[0])
        self._exec = run

    monkeypatch.setattr(srv.GWServer, "__init__", broken_init)


def _stuck_step(monkeypatch):
    from repro.api import solvers

    loop = solvers.pga_loop

    def stuck(step_fn, err_fn, T0, *a, **kw):
        return loop(lambda T, *_: T, err_fn, T0, *a, **kw)

    monkeypatch.setattr(solvers, "pga_loop", stuck)


def _half_batch(monkeypatch):
    def left_out(out, lanes):
        half = lanes // 2
        return jax.tree.map(lambda x: x.at[half:].set(x[0]), out)
    _wrap_exec(monkeypatch, left_out)


def _altered_answer(monkeypatch):
    def altered(out, lanes):
        return dataclasses.replace(out, value=out.value * 1.05)
    _wrap_exec(monkeypatch, altered)


FAULTS = {"none": None, "stuck_step": _stuck_step,
          "half_batch": _half_batch, "altered_answer": _altered_answer}


@pytest.mark.parametrize("name", CELLS)
def test_warm_up_dispatches_each_width_once(name, no_persistent_cache):
    """Every (signature, width) of the window goes out exactly once, as
    one whole group, whatever the flush timer would have done."""
    from repro.serve import GWServer
    from repro.serve.batching import bucket_for

    cell = small_cell(name)
    traffic = harness.build_traffic(cell.config, cell.traffic, 2**31 + 17)
    client = harness.Client(cell, traffic)
    # a timer that would split every group if the warm-up let it run
    cell.traffic["serve"] = dict(cell.traffic["serve"], max_wait_s=1e-3)
    server = GWServer(harness.serve_config(cell.traffic))
    lanes = []
    exe = server._exec

    def run(p, s, k):
        lanes.append(jax.tree.leaves(p)[0].shape[0])
        return exe(p, s, k)
    server._exec = run
    try:
        harness.warm_up(server, client, cell)
    finally:
        server.close()
    assert server.config.max_wait_s == 1e-3, "the config is restored"
    widths = cell.loop.widths(cell.traffic, server.config)
    sizes = [len(w) for _, w in traffic.geoms]
    sigs = {(bucket_for(sizes[i], server.config.buckets),
             bucket_for(sizes[j], server.config.buckets))
            for i, j in traffic.pairs}
    assert sorted(lanes[:len(sigs) * len(widths)]) == sorted(
        widths * len(sigs))


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_run_is_correct_only_when_the_timed_path_is_sound(
        name, fault, monkeypatch, no_persistent_cache):
    import run

    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    cell = small_cell(name)
    out = run.measure(cell, 2**31 + 29, 0.5, False, jax.devices(),
                      t_start=time.perf_counter())
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["correct"] is (fault == "none"), out["checks"]
    assert {m["name"] for m in cell.end_to_end} == set(out["metrics"])
    assert all(np.isfinite(m["value"]) for m in out["metrics"].values())
