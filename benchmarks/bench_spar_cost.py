"""COO spar_cost impl shoot-out: jnp ``lax.map`` baseline vs the fused
Pallas path vs the materialized-support fast mode (kernels/spar_cost).

Two views per (n, s) cell:
  * per-iteration cost-assembly call (the O(s²) hot path in isolation) —
    steady-state, support setup hoisted exactly as in the solvers;
  * end-to-end ``spar_gw`` (materialization amortized over outer_iters).

Also exercises the dispatch micro-autotune hook (block-size sweep for the
materialized matvec kernel) and dumps the records to artifacts/autotune/
for ``benchmarks/roofline.py`` to report.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

import repro
from benchmarks.common import FULL, record, timed
from benchmarks.datasets import moon
from repro.core import sampling
from repro.kernels import dispatch
from repro.kernels.spar_cost.ops import make_spar_cost_fn, spar_matvec
from repro.kernels.spar_cost.ref import materialize_loss

IMPLS = ("jnp", "pallas", "materialized")


def _support(key, a, b, Cx, Cy, s):
    probs = sampling.balanced_probs(a, b)
    rows, cols = sampling.sample_pairs(key, probs, s)
    t = a[rows] * b[cols]
    return rows, cols, t


def bench_cell(n: int, ratio: int, reps: int, loss: str = "l2"):
    s = ratio * n
    a, b, Cx, Cy = moon(n)
    a, b = jnp.asarray(a), jnp.asarray(b)
    Cx, Cy = jnp.asarray(Cx), jnp.asarray(Cy)
    key = jax.random.PRNGKey(0)
    rows, cols, t = _support(key, a, b, Cx, Cy, s)

    times = {}
    # --- per-iteration cost assembly (support setup hoisted, as in solvers)
    for impl in IMPLS:
        cost_fn = make_spar_cost_fn(Cx, Cy, rows, cols, loss, impl=impl,
                                    chunk=1024)
        f = jax.jit(lambda tv, off: cost_fn(tv, off))
        sec, out = timed(f, t, jnp.zeros((s,)), reps=reps)
        assert bool(jnp.isfinite(out).all())
        times[impl] = sec
        record(f"spar_cost/n{n}/s{ratio}n/{impl}", sec * 1e6)
    base = times["jnp"]
    for impl in ("pallas", "materialized"):
        record(f"spar_cost/n{n}/s{ratio}n/{impl}_speedup",
               times[impl] * 1e6, f"x{base / max(times[impl], 1e-12):.2f}")

    # --- end-to-end solver wall-clock (compiled path per impl, paper
    # defaults: 20 outer iterations amortize the one-time materialization)
    problem = repro.QuadraticProblem(repro.Geometry(Cx, a),
                                     repro.Geometry(Cy, b), loss=loss)
    gw_times = {}
    for impl in IMPLS:
        solver = repro.SparGWSolver(s=s, epsilon=1e-2, outer_iters=20,
                                    inner_iters=50, cost_impl=impl)
        sec, out = timed(
            lambda k, solver=solver: repro.solve(problem, solver, key=k,
                                                 validate=False),
            key, reps=max(reps // 2, 1))
        gw_times[impl] = sec
        record(f"spar_gw/n{n}/s{ratio}n/{impl}", sec * 1e6,
               f"value={float(out.value):.5f}")
    base = gw_times["jnp"]
    record(f"spar_gw/n{n}/s{ratio}n/best_speedup",
           min(gw_times.values()) * 1e6,
           f"x{base / max(min(gw_times.values()), 1e-12):.2f}")
    return times, gw_times


def tune_matvec_block(n: int, ratio: int):
    """Dispatch micro-autotune demo: block sweep for the matvec kernel."""
    s = ratio * n
    a, b, Cx, Cy = moon(n)
    a, b = jnp.asarray(a), jnp.asarray(b)
    Cx, Cy = jnp.asarray(Cx), jnp.asarray(Cy)
    rows, cols, t = _support(jax.random.PRNGKey(1), a, b, Cx, Cy, s)
    Lmat = materialize_loss(Cx, Cy, rows, cols, "l2")
    reps = 2 if dispatch.backend() == "tpu" else 1   # interpret mode is slow
    best = dispatch.autotune(
        "spar_cost", (64, 128, 256),
        lambda blk: spar_matvec(Lmat, t, block=blk), reps=reps)
    record(f"spar_cost/autotune/n{n}/s{ratio}n", 0.0, f"block={best}")
    path = dispatch.dump_autotune_records()
    if path is not None:
        record("spar_cost/autotune/dump", 0.0, str(path))


def main(quick: bool = False):
    n = 200 if (FULL or not quick) else 64
    reps = 10 if FULL else (2 if quick else 6)
    ratios = (4,) if quick else (4, 16)
    for ratio in ratios:
        bench_cell(n, ratio, reps)
    tune_matvec_block(n, ratios[0])


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / few reps (CI smoke)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(quick=args.quick)
