"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/gwbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one chip. It builds the cell's data from ``--seed``, turns on
JAX's persistent compilation cache in ``.jax_cache/`` of the checkout,
warms up every executable the traffic uses (all of that is ``setup_s``),
offers the traffic's load to ``GWServer`` for ``--seconds`` by the loop
that its traffic file names, and then checks the served answers against
the plain reference of the solver family (``families/<family>.py``). With
``--trace 1`` the window is traced by the profiler and the per-layer
metrics are reported instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit
(also the last lines of standard error). Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
COST_KERNELS = HERE / "cost_kernels.json"


def log(msg: str) -> None:
    print(f"gwbench: {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def prepare_environment(trace: bool) -> None:
    """Before JAX loads: the compile cache at a fixed path inside the
    checkout, libtpu's logs off, host spans into the profiler's trace."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if trace:
        os.environ["REPRO_OBS_XLA"] = "1"
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))


def tpu_devices(chips: int):
    """The TPU devices the cell asks for, or exit non-zero."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"JAX finds no TPU (platform {devices[0].platform!r})")
        sys.exit(3)
    if len(devices) < chips:
        log(f"the cell needs {chips} chips, JAX finds {len(devices)}")
        sys.exit(3)
    return devices[:chips]


def device_info(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


class CompileCounter:
    """Counts JAX's compile events (tracing, lowering, backend compile,
    persistent-cache reads) while ``armed``."""

    def __init__(self):
        import jax

        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and ("/jax/core/compile" in event
                           or "compilation_cache" in event):
            self.n += 1


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float = T_START) -> dict:
    """Set up, serve the window, check the answers; the result line."""
    import jax

    import harness
    import peaks
    import stats
    import trace_reduce
    from repro.obs.span import clear_spans, spans
    from repro.serve import GWServer, enable_compilation_cache

    enable_compilation_cache()
    compiles = CompileCounter()
    t_data = time.perf_counter()
    traffic = harness.build_traffic(cell.config, cell.traffic, seed)
    client = harness.Client(cell, traffic)
    server = GWServer(harness.serve_config(cell.traffic))
    t_warm = time.perf_counter()
    harness.warm_up(server, client, cell)
    jax.effects_barrier()
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s:.3f} s: start-up {t_data - t_start:.3f}, data "
        f"{t_warm - t_data:.3f}, warm-up {t_start + setup_s - t_warm:.3f}")

    window = min(seconds, cell.traffic["trace_seconds"]) if trace \
        else seconds
    tdir = tempfile.mkdtemp(prefix="gwbench_trace_") if trace else None
    server.reset_stats()
    clear_spans()
    compiles.armed = True
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        ann.__enter__()
    cpu0 = time.process_time()
    t0, done = cell.loop.run(server, client, cell.traffic, window)
    cpu_s = time.process_time() - cpu0
    if trace:
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles.armed = False
    t_end = max(d.finished for d in done)
    window_spans = spans()
    lanes, filler = server.metrics.n_lanes, server.metrics.n_filler_lanes
    log(f"compiles inside the window: {compiles.n} (dispatches that "
        f"compiled: {sum(1 for r in window_spans if r.get('compiled'))})")
    lat = [1e3 * (d.finished - d.submitted) for d in done]
    slow = max(done, key=lambda d: d.finished - d.submitted)
    log("latency ms: " + ", ".join(
        f"p{q} {stats.percentile(lat, q):.1f}" for q in (50, 90, 95, 99, 100))
        + f", mean {sum(lat) / len(lat):.1f}; slowest: admission "
        f"{1e3 * (slow.admitted - slow.submitted):.1f}, then "
        f"{1e3 * (slow.finished - slow.admitted):.1f}; lanes {lanes}, "
        f"filler {filler}; host CPU {cpu_s:.2f} s over "
        f"{t_end - t0:.2f} s")
    dev = device_info(devices)

    attempted = len(done)
    n_failed = sum(1 for d in done if harness.failed(d))
    solved = [d for d in done if d.result is not None]
    checked = harness.served_answers(cell, harness.sample_for_check(
        done, cell.traffic["check_sample"], seed))
    server.close()
    del server, client
    gc.collect()

    out = {"correct": False, "attempted": attempted, "failed": n_failed}
    if trace:
        path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
        red = trace_reduce.reduce(
            trace_reduce.load_xspace(path),
            json.loads(COST_KERNELS.read_text()))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = SimpleNamespace(
            cell=cell, solves=len(solved), lanes=lanes, filler_lanes=filler,
            spans=window_spans, window_s=red["window_s"],
            busy_s=red["busy_s"], attributed=red["attributed"],
            peaks=peaks.peaks(dev["kind"]))
        metrics = {}
        for m in cell.per_layer:
            v = harness.load_layer_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out.update(metrics=metrics, device=dev, breakdown={
            "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]})
    else:
        ctx = SimpleNamespace(t0=t0, t_end=t_end, solved=solved,
                              setup_s=setup_s)
        out.update(metrics={
            m["name"]: {"value": harness.plugin("end_to_end",
                                                m["name"]).read(ctx),
                        "unit": m["unit"]}
            for m in cell.end_to_end}, device=dev)
    del done, solved

    numbers = harness.compared_numbers(cell, traffic, checked, cell.limits)
    numbers["failed"] = float(n_failed)
    ok, rows = harness.judge(numbers, dict(cell.limits, failed=0.0))
    out["correct"] = bool(ok and attempted > 0 and len(checked) > 0)
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    prepare_environment(bool(args.trace))
    import harness

    harness.add_src_to_path()
    cell = harness.load_cell(args.workload)
    devices = tpu_devices(int(cell.workload["chips"]))
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
