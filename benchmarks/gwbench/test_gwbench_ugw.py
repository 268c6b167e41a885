"""The check of the unbalanced Moon cell (``moon_ugw_n1000``) at a size a
CPU test run holds: Algorithm 3 at n = 24 (``testdata/moon_ugw.json``),
padded by the server to bucket 32 as n = 1000 is to 1024.

* the served answers, and the same problems solved unpadded through
  ``repro.solve``, agree with ``families/spar_gw.unbalanced.py`` within
  the cell's own limits (that the bfloat16 control fails them is
  ``test_gwbench_correctness.py``'s, as for every cell);
* the 2-D support check passes every served support and fails almost
  every entry of a support drawn from the balanced law or another key;
* padding to the bucket leaves the answer as it was, to float32 rounding.
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

harness.add_src_to_path()

CELL = "moon_ugw_n1000"
N, BUCKET = 24, 32


@pytest.fixture(scope="module")
def solved():
    """Four requests (two pairs, two keys each), served at bucket 32 and
    solved unpadded with the same keys; each answer in ``served`` form."""
    import repro
    from repro.serve import GWServer

    cell = dataclasses.replace(
        harness.load_cell(CELL),
        config=json.loads((HERE / "testdata" / "moon_ugw.json").read_text()))
    cell.traffic["pool"].update(n=N, size=2)
    cell.traffic["serve"] = dict(cell.traffic["serve"], buckets=[BUCKET])
    traffic = harness.build_traffic(cell.config, cell.traffic, 2**31 + 41)
    client = harness.Client(cell, traffic)
    requests = [client.request(i) for i in range(4)]
    server = GWServer(harness.serve_config(cell.traffic))
    try:
        rids = [server.submit(*r) for r in requests]
        results = server.results(rids)
    finally:
        server.close()
    assert not any(r.failed or r.fell_back for r in results)
    assert {r.padded_shape for r in results} == {(BUCKET, BUCKET)}
    fam = cell.family
    served = [(i, fam.served(r)) for i, r in enumerate(results)]
    unpadded = []
    for i, (problem, solver, key) in enumerate(requests):
        out = repro.solve(problem, solver, key=key)
        rows, cols, vals = (np.asarray(x) for x in out.coupling)
        unpadded.append((i, {"value": float(out.value), "rows": rows,
                             "cols": cols, "vals": vals, "grid": (N, N)}))
    return cell, traffic, {"served": served, "unpadded": unpadded}


@pytest.mark.parametrize("path", ["served", "unpadded"])
def test_program_agrees_with_the_reference(solved, path):
    cell, traffic, answers = solved
    checked = answers[path]
    names = sorted(cell.family.NUMBERS)
    program = harness.compared_numbers(cell, traffic, checked, names)
    ok, rows = harness.judge(program, cell.limits)
    assert ok, rows
    # the draw's float32 cumulative sum against the float64 one
    assert program["support_drift"] < 1e-6


def _balanced_draw(key, a, b, s):
    """A support from the balanced product law sqrt(a_i b_j) with the same
    key, as Algorithm 2 draws it."""
    kr, kc = jax.random.split(key)
    pa, pb = np.sqrt(a) / np.sqrt(a).sum(), np.sqrt(b) / np.sqrt(b).sum()
    return (np.asarray(jax.random.choice(kr, len(a), (s,), p=pa)),
            np.asarray(jax.random.choice(kc, len(b), (s,), p=pb)))


@pytest.mark.parametrize("wrong", ["none", "balanced_law", "other_key"])
def test_support_check_fails_a_draw_of_another_law_or_key(solved, wrong):
    cell, traffic, answers = solved
    fam = cell.family
    for i, ans in answers["served"]:
        _, a, _, b = traffic.problem_data(i)
        s = len(ans["rows"])
        key = jax.random.PRNGKey(traffic.key_seed(i))
        rows, cols = ans["rows"], ans["cols"]
        if wrong == "balanced_law":
            padded = np.full(BUCKET, np.float32(fam.PAD_WEIGHT))
            pa, pb = padded.copy(), padded.copy()
            pa[:N], pb[:N] = a, b
            rows, cols = _balanced_draw(key, pa, pb, s)
        elif wrong == "other_key":
            key = jax.random.PRNGKey(traffic.key_seed(i) + 1)
        cdf = fam.law_cdf(traffic, i, ans["grid"], cell.config["solver"])
        bad, _ = fam.support_outside_band(key, cdf, BUCKET, rows, cols, s)
        if wrong == "none":
            assert bad == 0
        else:
            assert bad > 0.9 * s, (wrong, bad, s)


def test_padding_leaves_the_unbalanced_answer_unchanged(solved):
    """The pad rows and columns (weight 1e-30) read into the eq.-9 law,
    the KL terms and the mass, and leave the answer as it was. A padded
    draw sums its law over more cells in another order, so a pair whose
    uniform falls within float32 rounding of a cell's edge may land in the
    next cell (both draws pass the support check, above); every other pair
    is the same, and where the whole support is, so are the value and the
    coupling."""
    _, _, answers = solved
    same = 0
    for (_, pad), (_, raw) in zip(answers["served"], answers["unpadded"]):
        moved = (pad["rows"] != raw["rows"]) | (pad["cols"] != raw["cols"])
        assert moved.sum() <= 0.01 * len(moved)
        if moved.any():
            continue
        same += 1
        np.testing.assert_allclose(pad["value"], raw["value"], rtol=1e-5)
        np.testing.assert_allclose(pad["vals"], raw["vals"], rtol=1e-4,
                                   atol=1e-6 * raw["vals"].max())
    assert same >= 2
