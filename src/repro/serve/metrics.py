"""Server observability — request/batch counters + latency percentiles.

One ``ServeMetrics`` instance rides on each :class:`~repro.serve.server
.GWServer`; every counter is cheap host-side bookkeeping (no device
syncs), and :meth:`summary` flattens everything — including the geometry
cache's hit/miss/eviction stats — into one JSON-ready dict, which is what
``benchmarks/bench_serve.py`` records into ``BENCH_PR7.json`` and the
serve-smoke CI job asserts on.

Latency samples live in bounded :class:`~repro.obs.registry.Reservoir`
stores (exact percentiles up to ``sample_cap`` = 8192 samples, unbiased
uniform reservoir sampling beyond — the PR-7 append-only lists grew
without bound on long-lived servers). Every counter and latency is also
mirrored into the process-wide obs registry under ``repro_serve_*`` /
``repro_cache_*`` names, so the Prometheus exporter
(``GWServer.metrics_text()`` / ``launch/serve.py --metrics-port``) sees
server traffic without a second bookkeeping path.

``percentiles`` moved to ``repro.obs.registry`` with the unified
telemetry layer; it is re-exported here (same name, same behavior) for
the PR-7 callers.
"""
from __future__ import annotations

import time
from typing import Optional

from repro.obs.registry import (  # noqa: F401 — re-exported shims
    DEFAULT_QS,
    DEFAULT_RESERVOIR_CAP,
    Reservoir,
    percentiles,
    registry,
)


class ServeMetrics:
    """Counters + bounded latency recorder for one server instance.

    sample_cap — reservoir size for latency/queue-wait samples: exact
    percentiles up to this many completed requests, a uniform sample of
    the full history beyond (default 8192; memory stays O(cap) forever).
    """

    def __init__(self, sample_cap: int = DEFAULT_RESERVOIR_CAP):
        self.n_submitted = 0
        self.n_completed = 0
        self.n_failed = 0        # unhealthy after the batched attempt
        self.n_fallbacks = 0     # per-request fallback re-solves taken
        self.n_batches = 0
        self.n_lanes = 0         # total dispatched lanes incl. filler
        self.n_filler_lanes = 0
        self.sample_cap = sample_cap
        self.latencies_s = Reservoir(sample_cap)
        self.queue_waits_s = Reservoir(sample_cap)
        self._t0 = time.perf_counter()

    # -- recording ----------------------------------------------------------

    def record_submit(self) -> float:
        self.n_submitted += 1
        registry().counter("repro_serve_requests_total",
                           "requests submitted to GWServer").inc()
        return time.perf_counter()

    def record_batch(self, n_real: int, n_lanes: int) -> None:
        self.n_batches += 1
        self.n_lanes += n_lanes
        self.n_filler_lanes += n_lanes - n_real
        reg = registry()
        reg.counter("repro_serve_batches_total",
                    "vmapped batches dispatched").inc()
        reg.counter("repro_serve_lanes_total",
                    "dispatched lanes incl. filler").inc(n_lanes)
        reg.counter("repro_serve_filler_lanes_total",
                    "pow2-padding filler lanes dispatched").inc(
                        n_lanes - n_real)

    def record_result(self, submitted_at: float, queue_wait: float,
                      failed: bool, fell_back: bool) -> float:
        """Count one answered request; returns its latency in seconds.

        submitted_at — ``record_submit``'s reading; the latency runs from
                       it to now
        queue_wait   — seconds from the request's append to its bucket to
                       the start of the flush that took it (the
                       duration of its ``serve.queue`` span), so stacking
                       and dispatch are not counted as waiting
        """
        latency = time.perf_counter() - submitted_at
        self.n_completed += 1
        self.latencies_s.add(latency)
        self.queue_waits_s.add(queue_wait)
        if failed:
            self.n_failed += 1
        if fell_back:
            self.n_fallbacks += 1
        reg = registry()
        reg.histogram("repro_serve_latency_seconds",
                      "submit-to-result request latency").observe(latency)
        reg.histogram("repro_serve_queue_wait_seconds",
                      "wait in the bucket: enqueue to the start of its "
                      "flush").observe(queue_wait)
        if failed:
            reg.counter("repro_serve_failed_total",
                        "requests unhealthy after the batched attempt").inc()
        if fell_back:
            reg.counter("repro_serve_fallbacks_total",
                        "per-request solo fallback re-solves").inc()
        return latency

    # -- reporting ----------------------------------------------------------

    def summary(self, cache_stats: Optional[dict] = None) -> dict:
        elapsed = time.perf_counter() - self._t0
        lat = percentiles(self.latencies_s)
        out = {
            "n_submitted": self.n_submitted,
            "n_completed": self.n_completed,
            "n_failed": self.n_failed,
            "n_fallbacks": self.n_fallbacks,
            "n_batches": self.n_batches,
            "mean_batch_lanes": (self.n_lanes / self.n_batches
                                 if self.n_batches else 0.0),
            "filler_lane_frac": (self.n_filler_lanes / self.n_lanes
                                 if self.n_lanes else 0.0),
            "throughput_rps": (self.n_completed / elapsed
                               if elapsed > 0 else 0.0),
            "latency_p50_ms": lat["p50"] * 1e3,
            "latency_p95_ms": lat["p95"] * 1e3,
            "latency_p99_ms": lat["p99"] * 1e3,
            "queue_wait_p50_ms": percentiles(
                self.queue_waits_s, (50,))["p50"] * 1e3,
        }
        if cache_stats is not None:
            out.update({f"cache_{k}": v for k, v in cache_stats.items()})
        return out
