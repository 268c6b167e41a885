"""Benchmark runner — one function per paper table/figure, plus a
registry-driven single-solver mode.

Prints ``name,us_per_call,derived`` CSV (harness contract). Set
REPRO_BENCH_FULL=1 for paper-scale sizes.

Modes:
  python benchmarks/run.py                      # full paper suite
  python benchmarks/run.py --solver spar_gw     # one registered solver
  python benchmarks/run.py --solver all         # every registered solver
  python benchmarks/run.py --solver quantized_gw --quick   # CI smoke
(the --solver path benchmarks through repro.solve, so any solver added
via @register_solver is benchmarkable with no further CLI work).

Solver mode also writes the machine-readable perf trajectory to
``BENCH_PR3.json`` (override with --json): one record per (solver, n)
with wall time, GW value, and convergence info, so per-PR perf history
is diffable instead of scraped from CSV logs.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def run_solver_mode(names, n: int, loss: str, reps: int,
                    json_path: str) -> None:
    import repro
    from benchmarks.common import bench_solver, merge_bench_json

    if names == ["all"]:
        names = list(repro.available_solvers())
    unknown = [x for x in names if x not in repro.available_solvers()]
    if unknown:
        raise SystemExit(
            f"unknown solver(s) {unknown}; available: "
            f"{', '.join(repro.available_solvers())}")
    print("name,us_per_call,derived")
    results = []
    failures = []
    for name in names:
        # a failing solver records a failure row and the suite moves on —
        # one broken rung must not abort the whole benchmark run
        try:
            sec, out, pcts, compile_s = bench_solver(name, n=n, loss=loss,
                                                     reps=reps)
        except Exception as exc:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
            results.append({
                "solver": name,
                "dataset": "moon",
                "loss": loss,
                "n": n,
                "status": "failed",
                "error": f"{type(exc).__name__}: {exc}",
            })
            continue
        status = (out.status.describe() if out.status is not None
                  else "UNKNOWN")
        results.append({
            "solver": name,
            "dataset": "moon",
            "loss": loss,
            "n": n,
            "wall_time_s": round(sec, 6),
            "compile_s": round(compile_s, 6),
            "steady_s": round(sec, 6),
            "p50_s": round(pcts["p50"], 6),
            "p95_s": round(pcts["p95"], 6),
            "p99_s": round(pcts["p99"], 6),
            "value": float(out.value),
            "converged": bool(out.converged),
            "n_iters": int(out.n_iters),
            "status": status,
        })
    if json_path:
        merge_bench_json(json_path, "moon", results)
    if failures:
        print("FAILED:", failures, file=sys.stderr)
        raise SystemExit(1)


_SUITE = ("bench_fig2", "bench_fig3_ugw", "bench_fig4_sensitivity",
          "bench_fig5_scaling", "bench_fig6_fgw", "bench_grid_vs_coo",
          "bench_tables23_graphs", "bench_multiscale", "bench_lowrank",
          "bench_serve", "bench_diff")


def run_full_suite() -> None:
    import importlib

    print("name,us_per_call,derived")
    failures = []
    for name in _SUITE:
        try:
            importlib.import_module(f"benchmarks.{name}").main()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
    if failures:
        print("FAILED:", failures, file=sys.stderr)
        raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--solver", nargs="+", default=None, metavar="NAME",
                    help="benchmark the named registered solver(s) through "
                         "repro.solve ('all' = every registered solver); "
                         "omit for the full paper suite")
    ap.add_argument("--n", type=int, default=None,
                    help="problem size (default 120, or 60 with --quick)")
    ap.add_argument("--loss", default="l2", help="ground loss")
    ap.add_argument("--reps", type=int, default=None,
                    help="timing reps (default 3, or 1 with --quick)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke defaults: n=60, 1 rep (explicit --n/"
                         "--reps still win)")
    ap.add_argument("--json", default="BENCH_PR3.json", metavar="PATH",
                    help="machine-readable output for solver mode "
                         "('' disables)")
    args = ap.parse_args()
    if args.n is None:
        args.n = 60 if args.quick else 120
    if args.reps is None:
        args.reps = 1 if args.quick else 3
    if args.solver:
        run_solver_mode(args.solver, args.n, args.loss, args.reps, args.json)
    else:
        run_full_suite()


if __name__ == "__main__":
    main()
