"""Serve GW requests at the paper's sizes through GWServer on one TPU chip.

    python chip_smoke.py

Drives the main path once, in this one process: ``GWServer`` with
auto-selected solvers (``repro.select_solver``) serves

  * 2 ``spar_gw`` requests at n = 1000 (bucket 1024, s = 16n = 16000),
  * 2 ``spar_gw`` requests at n = 2000 (bucket 2048, s = 32000),
  * 2 ``dense_gw`` requests at n = 200 (bucket 256),

on the paper's Moon geometries made from fixed seeds, each bucket twice:
cold (its executable compiles) and warm (steady). At these supports the
O(s²) cost assembly runs in the gather-fused Pallas kernel
(``kernels/spar_cost``), compiled by Mosaic.

Checks, any of which failing exits non-zero before the last line:

  * the device is a TPU and Pallas kernels are not interpreted;
  * every request comes back CONVERGED or MAXITER, finite, not fallen
    back, and its warm value equals its cold value bit for bit;
  * each spar_gw executable holds the kernel (``tpu_custom_call``);
  * each spar_gw value matches ``repro.solve`` of the same padded
    problem with the same key and ``cost_impl="jnp"`` — the kernel
    against its oracle on the same sampled support — within ORACLE_RTOL.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The persistent compilation cache is on (``JAX_COMPILATION_CACHE_DIR``
where set, else ``.jax_cache`` in this checkout), so a second run
reports less compile time than the first.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Kernel vs jnp oracle on the same support, relative difference of the
# served value. Both sides accumulate in float32 (the kernel on the VPU,
# the oracle's matvec pinned to Precision.HIGHEST), so they differ only in
# summation order; 20 proximal PGA iterations carry that rounding into
# the coupling. Measured on a v5e chip: at most 2.85e-7 over the four
# spar_gw requests below (CHANGES.md). The gate leaves ~35x headroom; a
# bf16 matvec or a wrong gather misses it by orders of magnitude.
ORACLE_RTOL = 1e-5

# (n, expected solver) per bucket, PER_BUCKET requests each: a full batch
REQUESTS = ((1000, "spar_gw"), (2000, "spar_gw"), (200, "dense_gw"))
PER_BUCKET = 2
HEALTHY = ("CONVERGED", "MAXITER")


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check_device():
    """The TPU, or exit: never run this on the CPU or in interpret mode."""
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    if os.environ.get("REPRO_PALLAS_INTERPRET", "auto").strip().lower() \
            not in ("auto", "0", "false", "no", "off"):
        fail("REPRO_PALLAS_INTERPRET forces Pallas interpret mode")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from repro.kernels import dispatch

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if dispatch.interpret_mode():
        fail("Pallas kernels would run in interpret mode")
    return devices


def make_requests(sizes=REQUESTS):
    """Moon problems (paper §6.1) from fixed seeds, one PRNG key each."""
    import jax
    import jax.numpy as jnp

    import repro
    from benchmarks.datasets import moon

    out = []
    for n, expected in sizes:
        for i in range(PER_BUCKET):
            a, b, Cx, Cy = moon(n, seed=10 * n + i)
            problem = repro.QuadraticProblem(
                repro.Geometry(jnp.asarray(Cx), jnp.asarray(a)),
                repro.Geometry(jnp.asarray(Cy), jnp.asarray(b)))
            out.append((problem, expected, jax.random.PRNGKey(n + i)))
    return out


def serve(server, requests):
    """Serve the requests one bucket at a time (a bucket's latency is its
    own, not queued behind another's); per-request results, and per
    bucket the seconds of the dispatch that compiled (0.0 if none)."""
    from repro.obs.span import clear_spans, spans

    results, compile_s = [], []
    for i in range(0, len(requests), PER_BUCKET):
        clear_spans()
        rids = [server.submit(p, key=k)
                for p, _, k in requests[i:i + PER_BUCKET]]
        results += server.results(rids)
        compile_s.append(sum(r["duration_s"] for r in spans()
                             if r["name"] == "serve.dispatch"
                             and r.get("compiled")))
    return results, compile_s


def executable_text(server, padded, solver, key, lanes: int = 2) -> str:
    """Compiled HLO of the server's batched executable for this padded
    problem's bucket (a persistent-cache hit after the served dispatch)."""
    from repro.serve.batching import stack_items

    item = (padded, solver, key)
    stacked = stack_items([item] * lanes)
    return server._exec.lower(*stacked).compile().as_text()


def main(sizes=REQUESTS) -> None:
    devices = check_device()
    import jax
    import numpy as np

    import repro
    from repro.kernels.spar_cost.ops import resolve_impl
    from repro.serve import GWServer, ServeConfig, enable_compilation_cache
    from repro.serve.batching import pad_problem

    dev = devices[0]
    cache_dir = enable_compilation_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compilation cache: {cache_dir}")

    requests = make_requests(sizes)
    # full buckets flush on their second submit; no timer flush splits them
    server = GWServer(ServeConfig(max_batch=PER_BUCKET, max_wait_s=600.0))
    t0 = time.perf_counter()
    cold, cold_compile = serve(server, requests)
    warm, warm_compile = serve(server, requests)
    served_s = time.perf_counter() - t0
    server.close()
    if any(warm_compile):
        fail(f"warm round compiled: {warm_compile}")

    kernel_in_hlo = {}
    worst_rel = 0.0
    for i, ((problem, expected, key), rc, rw) in enumerate(
            zip(requests, cold, warm)):
        solver = repro.select_solver(problem)
        name = type(solver).name
        impl = (resolve_impl(solver.cost_impl, solver.s)
                if name == "spar_gw" else "-")
        line = (f"request {rc.rid}: shape={rc.shape} bucket={rc.padded_shape}"
                f" solver={name} cost_impl={impl} value={rc.value!r}"
                f" status={rc.status_name} fell_back={rc.fell_back}"
                f" latency_cold_s={rc.latency_s:.3f}"
                f" bucket_compile_s={cold_compile[i // PER_BUCKET]:.3f}"
                f" latency_warm_s={rw.latency_s:.3f}")
        if name == "spar_gw":
            padded = pad_problem(problem, *rc.padded_shape)
            oracle_solver = dataclasses.replace(solver, cost_impl="jnp")
            ref = repro.solve(padded, oracle_solver, key=key)
            ref_value = float(np.asarray(ref.value))
            rel = abs(rc.value - ref_value) / abs(ref_value)
            worst_rel = max(worst_rel, rel)
            if rc.padded_shape not in kernel_in_hlo:
                kernel_in_hlo[rc.padded_shape] = "tpu_custom_call" in \
                    executable_text(server, padded, solver, key)
            line += (f" oracle_jnp={ref_value!r} rel_diff={rel:.3e}"
                     f" tpu_custom_call="
                     f"{kernel_in_hlo[rc.padded_shape]}")
        print(line, flush=True)

        if name != expected:
            fail(f"request {rc.rid}: selected {name}, expected {expected}")
        for r in (rc, rw):
            if r.status_name not in HEALTHY or r.failed or r.fell_back \
                    or not np.isfinite(r.value):
                fail(f"request {r.rid}: status={r.status_name} "
                     f"failed={r.failed} fell_back={r.fell_back} "
                     f"value={r.value}")
        if rw.value != rc.value:
            fail(f"request {rc.rid}: warm value {rw.value!r} != cold "
                 f"{rc.value!r}")
        if name == "spar_gw":
            if impl != "pallas":
                fail(f"request {rc.rid}: cost_impl resolved to {impl!r}")
            if not kernel_in_hlo[rc.padded_shape]:
                fail(f"bucket {rc.padded_shape}: no tpu_custom_call in HLO")
            if not rel <= ORACLE_RTOL:
                fail(f"request {rc.rid}: kernel vs oracle rel diff "
                     f"{rel:.3e} > {ORACLE_RTOL:.0e}")

    n_cache = sum(1 for p in Path(cache_dir).rglob("*") if p.is_file())
    print(f"compile_s per bucket (cold round): "
          f"{[round(c, 3) for c in cold_compile]}; total "
          f"{sum(cold_compile):.3f}; served both rounds in {served_s:.3f}s; "
          f"worst kernel/oracle rel diff {worst_rel:.3e} "
          f"(gate {ORACLE_RTOL:.0e}); cache files: {n_cache}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
