"""Readings that the limits of a cell's check are set from.

    python3 benchmarks/gwbench/calibrate.py --workload <cell> \
        --seeds 11,12,... --control 3 --seconds 10 [--out FILE]

In one process on the chip: set up the cell once, then for each seed
serve a window of ``--seconds`` at the cell's own load and compare the
served answers with the reference, exactly as a run does. For the first
``--control`` seeds the control (the reference one precision lower, in
the program's place) is compared on the same requests. Prints one JSON
line per seed and a summary: the lower reading of each number (the
largest over the program's seeds) and the upper one (the smallest over
the control's), for every number the solver family knows, whether a
limit reads it or not. ``run.py`` never runs this.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    sys.path.insert(0, str(HERE))
    import run

    run.prepare_environment(trace=False)
    import harness

    harness.add_src_to_path()
    cell = harness.load_cell(args.workload)
    run.tpu_devices(int(cell.workload["chips"]))
    from repro.serve import GWServer, enable_compilation_cache

    enable_compilation_cache()
    names = sorted(cell.family.NUMBERS)
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = []
    for n, seed in enumerate(seeds):
        traffic = harness.build_traffic(cell.config, cell.traffic, seed)
        client = harness.Client(cell, traffic)
        # a server per seed, closed before the reference runs, as in a
        # run: a server keeps every request it answered, and with it the
        # request's geometries on the device
        server = GWServer(harness.serve_config(cell.traffic))
        harness.warm_up(server, client, cell)
        _, done = cell.loop.run(server, client, cell.traffic, args.seconds)
        checked = harness.served_answers(cell, harness.sample_for_check(
            done, cell.traffic["check_sample"], seed))
        n_done, n_failed = len(done), sum(1 for d in done
                                          if harness.failed(d))
        server.close()
        del server, client, done
        gc.collect()
        rec = {"seed": seed, "completed": n_done, "checked": len(checked),
               "failed": n_failed,
               "program": harness.compared_numbers(cell, traffic, checked,
                                                   names)}
        if n < args.control:
            rec["control"] = harness.compared_numbers(
                cell, traffic, checked, names,
                answers=harness.control_answers(cell, traffic, checked))
        print(json.dumps(rec), flush=True)
        lines.append(rec)
    summary = {"workload": args.workload, "seeds": len(lines),
               "lower": {k: max(r["program"][k] for r in lines)
                         for k in names},
               "upper": {k: min((r["control"][k] for r in lines
                                 if "control" in r), default=None)
                         for k in names}}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(
            "\n".join(json.dumps(x) for x in lines + [summary]) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
