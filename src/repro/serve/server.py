"""``GWServer`` — the batched, cached, observable solve front door.

Request lifecycle (DESIGN.md §9):

    server = GWServer()
    rid = server.submit(problem, solver="dense_gw", key=key)   # enqueue
    server.poll(rid)        # "queued" | "running" | "done"
    res = server.result(rid)            # blocks; RequestResult

``submit`` resolves the solver (same rules as ``repro.solve``), pads both
geometries to size buckets through the :class:`GeometryCache`, and
enqueues the request under its **batch signature** (padded pytree
structure + leaf avals). A bucket flushes when it reaches
``max_batch`` requests or its oldest request is older than
``max_wait_s`` — enforced by a background flusher thread (daemon, ticks
at ``max_wait_s / 4``; disable with ``ServeConfig(flush_thread=False)``
to fall back to the PR-7 cooperative mode where the deadline is only
checked on submit/poll/result/flush calls). Server state is guarded by
one re-entrant lock, so submits and timer flushes interleave safely.

A flush stacks the bucket into one vmapped jit call — filler lanes
(replicas of lane 0 with fault hooks disarmed) round the lane count up to
a power of two so partial flushes reuse full-batch executables. Dispatch
is **asynchronous**: the jitted call returns device futures immediately
(input stack buffers are donated), so the next bucket accumulates while
XLA computes; ``result`` blocks on the batch and slices out one lane.

Failure semantics are **per request**: each lane carries its own
:class:`~repro.health.status.SolveStatus` (the health layer's vmap
lane-isolation guarantee — one poisoned request cannot touch its
bucket-mates' bits), and a lane that comes back DIVERGED/STALLED is —
under ``on_failure="fallback"`` — re-solved solo through
``repro.solve(..., on_failure="fallback")``, walking the PR-6 solver
ladder for that request only.

Spans (``repro.obs.span``) follow one request by its ``rid`` and one
flush by its ``batch`` (a per-server counter): ``serve.submit`` and its
``serve.pad`` (``rid``); ``serve.queue`` (``rid``, ``batch``,
``source``), a record-only span from the request's append to its bucket
to the start of the flush that took it; ``serve.batch`` (stacking) and
``serve.dispatch`` (``batch``); ``serve.block`` (``rid``, ``batch``), the
wait on the device; ``serve.collect`` (``rid``, ``batch``), the lane
slice, health checks and result building, with any ``serve.fallback``
inside it. Filler lanes get no spans of their own.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
import warnings
import weakref
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.api.problem import QuadraticProblem
from repro.api.solve import select_solver
from repro.api.solvers import get_solver
from repro.health.status import STALLED, STATUS_NAMES
from repro.serve.batching import (
    DEFAULT_BUCKETS,
    batch_signature,
    bucket_for,
    disarm_fault,
    next_pow2,
    pad_problem,
    stack_items,
)
from repro.obs.registry import registry
from repro.obs.span import now_ns, record, span
from repro.serve.cache import GeometryCache
from repro.serve.metrics import ServeMetrics


@dataclass(frozen=True)
class ServeConfig:
    """Server policy knobs.

    buckets       — geometry-size buckets requests are padded up to
    max_batch     — flush a bucket once it holds this many requests
    max_wait_s    — flush a non-empty bucket once its oldest request has
                    waited this long (enforced by the flusher thread;
                    with ``flush_thread=False``, checked cooperatively on
                    every server call)
    flush_thread  — run a background daemon thread that ticks every
                    ``max_wait_s / 4`` and flushes overdue buckets, so
                    ``max_wait_s`` is honored in wall-clock time even
                    when no server call arrives
    cache_entries — GeometryCache capacity (artifacts, LRU)
    on_failure    — per-request policy for unhealthy lanes: "none"
                    returns the DIVERGED/STALLED output as-is (inspect
                    ``RequestResult.status``); "fallback" re-solves the
                    request solo via ``repro.solve(on_failure=
                    "fallback")`` (the PR-6 solver ladder)
    donate        — donate the stacked problem buffers to the executor
                    (they are per-flush temporaries; donation lets XLA
                    reuse them for outputs)

    The persistent compilation cache is process-wide, not a server knob:
    entry points call :func:`enable_compilation_cache` once at start-up.
    """
    buckets: Tuple[int, ...] = DEFAULT_BUCKETS
    max_batch: int = 8
    max_wait_s: float = 0.02
    flush_thread: bool = True
    cache_entries: int = 128
    on_failure: str = "fallback"
    donate: bool = True

    def __post_init__(self):
        if self.on_failure not in ("none", "fallback"):
            raise ValueError(
                f"on_failure must be 'none' or 'fallback', got "
                f"{self.on_failure!r}")
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")


# the checkout's own cache directory (listed in .gitignore); a fixed path,
# because the cache key includes it — a directory that moves never hits
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it
    stands and no directory is set here; otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR`. A fresh process serving the same bucket
    shapes then deserializes executables instead of recompiling them (the
    dominant cold-start cost). The thresholds are zeroed so even
    sub-second solver compiles are persisted — a GW serving process
    compiles a handful of large executables, not thousands of tiny ones.
    Process-wide: it flips ``jax.config`` for every jit in the process.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


@dataclass
class RequestResult:
    """One request's outcome.

    output is the per-lane ``GWOutput`` at the *padded* bucket shape
    (``padded_shape``) — or, when ``fell_back``, the fallback solve's
    output at the original shape. ``coupling_dense()`` always returns the
    original-shape coupling.
    """
    rid: int
    value: float
    output: Any
    status: Any                       # per-request SolveStatus
    status_name: str
    failed: bool                      # unhealthy after the batched attempt
    fell_back: bool                   # recovered via the solver ladder
    shape: Tuple[int, int]            # original (m, n)
    padded_shape: Tuple[int, int]
    latency_s: float

    def coupling_dense(self):
        m, n = self.shape
        dense = self.output.coupling_dense(*(
            self.shape if self.fell_back else self.padded_shape))
        return dense[:m, :n]


@dataclass
class _Request:
    rid: int
    problem: QuadraticProblem         # original, unpadded
    solver: Any
    key: Any
    item: Any                         # (padded problem, solver, key)
    sig: Any
    shape: Tuple[int, int]
    padded_shape: Tuple[int, int]
    submitted_at: float
    enqueued_ns: int = 0              # appended to its bucket (span clock)
    queue_wait_s: float = 0.0         # its serve.queue span's duration
    state: str = "queued"             # queued -> running -> done
    batch: Any = None
    lane: int = -1
    result: Optional[RequestResult] = None
    error: Optional[BaseException] = None   # the batch failed to dispatch


@dataclass
class _Batch:
    out: Any                          # stacked GWOutput (device futures)
    rids: List[int]                   # real lanes, in lane order
    n_lanes: int
    id: int                           # the per-server batch counter


def _run_lane(problem, solver, key):
    return solver.run(problem, key)


def _flusher_main(server_ref, interval_s: float,
                  stop: threading.Event) -> None:
    """Wall-clock flusher loop: pump overdue buckets every ``interval_s``.

    Holds only a weakref to the server so an abandoned (un-``close``d)
    server can still be garbage collected; the loop exits when the
    server dies or ``stop`` is set. A bucket that fails to dispatch hands
    its exception to its requests (``_flush_bucket``), which ``result``
    raises — so nothing needs catching here.
    """
    while not stop.wait(interval_s):
        server = server_ref()
        if server is None:
            return
        server._pump(source="timer")
        del server


class GWServer:
    """Batched, cached, observable front door over the solver registry."""

    def __init__(self, config: Optional[ServeConfig] = None):
        self.config = config or ServeConfig()
        self.cache = GeometryCache(self.config.cache_entries)
        self.metrics = ServeMetrics()
        self._requests: Dict[int, _Request] = {}
        self._queues: Dict[Any, List[int]] = {}
        self._rids = itertools.count()      # next() is atomic under the GIL
        self._batch_ids = itertools.count()
        self._lock = threading.RLock()
        donate = (0,) if self.config.donate else ()
        self._exec = jax.jit(jax.vmap(_run_lane), donate_argnums=donate)
        self._flusher_stop = threading.Event()
        self._flusher: Optional[threading.Thread] = None
        if self.config.flush_thread and self.config.max_wait_s > 0:
            self._flusher = threading.Thread(
                target=_flusher_main,
                args=(weakref.ref(self), self.config.max_wait_s / 4,
                      self._flusher_stop),
                name="gwserver-flusher", daemon=True)
            self._flusher.start()

    def close(self) -> None:
        """Stop the background flusher thread (idempotent). Queued
        requests stay retrievable via ``result``/``results``."""
        self._flusher_stop.set()
        if self._flusher is not None:
            self._flusher.join(timeout=1.0)
            self._flusher = None

    def __del__(self):
        try:
            self._flusher_stop.set()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    # -- submit -------------------------------------------------------------

    def submit(self, problem: QuadraticProblem,
               solver: Union[str, Any, None] = None,
               key: Optional[jax.Array] = None,
               validate: bool = True) -> int:
        """Enqueue one solve request; returns its request id."""
        rid = next(self._rids)
        with span("serve.submit", rid=rid):
            if solver is None:
                solver = select_solver(problem)
            elif isinstance(solver, str):
                solver = get_solver(solver).default_config(
                    max(problem.shape))
            if key is None and getattr(type(solver), "requires_key", False):
                raise ValueError(
                    f"{type(solver).__name__} needs a PRNG key: "
                    f"submit(problem, solver, key=jax.random.PRNGKey(seed))")
            if validate and not getattr(problem, "_validated", False):
                problem.check()
            m, n = problem.shape
            mb = bucket_for(m, self.config.buckets)
            nb = bucket_for(n, self.config.buckets)
            with span("serve.pad", rid=rid):
                padded = pad_problem(
                    problem, mb, nb,
                    geom_x=self.cache.padded(problem.geom_x, mb),
                    geom_y=self.cache.padded(problem.geom_y, nb))
            item = (padded, solver, key)
            sig = batch_signature(item)
            with self._lock:
                req = _Request(rid=rid, problem=problem, solver=solver,
                               key=key, item=item, sig=sig, shape=(m, n),
                               padded_shape=(mb, nb),
                               submitted_at=self.metrics.record_submit())
                self._requests[rid] = req
                req.enqueued_ns = now_ns()
                self._queues.setdefault(sig, []).append(rid)
                if len(self._queues[sig]) >= self.config.max_batch:
                    self._flush_bucket(sig, source="full")
                else:
                    self._pump()
            return rid

    # -- flushing -----------------------------------------------------------

    def _pump(self, source: str = "call") -> None:
        """Flush every bucket whose oldest request exceeded max_wait_s.
        ``source`` tags the dispatch span: "call" for cooperative checks
        on server calls, "timer" for the background flusher thread."""
        with self._lock:
            now = time.perf_counter()
            for sig in list(self._queues):
                rids = self._queues[sig]
                if rids and (now - self._requests[rids[0]].submitted_at
                             >= self.config.max_wait_s):
                    self._flush_bucket(sig, source=source)

    def flush(self) -> None:
        """Dispatch every non-empty bucket immediately."""
        with self._lock:
            for sig in list(self._queues):
                if self._queues[sig]:
                    self._flush_bucket(sig, source="flush")

    def _flush_bucket(self, sig, source: str = "call") -> None:
        """Stack one bucket and dispatch it. Each real request's wait,
        from its append to the bucket to the start of this flush's
        ``serve.batch`` span, is recorded as a ``serve.queue`` span."""
        with self._lock:
            rids = self._queues.pop(sig, [])
            if not rids:
                return
            bid = next(self._batch_ids)
            items = [self._requests[rid].item for rid in rids]
            n_lanes = next_pow2(len(items))
            if len(items) < n_lanes:
                p0, s0, k0 = items[0]
                items.extend([(p0, disarm_fault(s0), k0)]
                             * (n_lanes - len(items)))
            try:
                with span("serve.batch", batch=bid, lanes=n_lanes,
                          real=len(rids)) as sp:
                    started_ns = sp["start_ns"]
                    stacked_p, stacked_s, stacked_k = stack_items(items)
                with span("serve.dispatch", batch=bid, lanes=n_lanes,
                          source=source) as sp:
                    before = self._exec_cache_size()
                    with warnings.catch_warnings():
                        # CPU backends can't alias every donated buffer —
                        # harmless
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not usable")
                        out = self._exec(stacked_p, stacked_s, stacked_k)
                    sp["compiled"] = bool(
                        before >= 0 and self._exec_cache_size() > before)
            except Exception as e:  # noqa: BLE001 — handed to result()
                # a compile or dispatch error belongs to these requests:
                # whoever flushed (a submit, the timer thread), each
                # request's result() raises it
                failed_ns = now_ns()
                for rid in rids:
                    req = self._requests[rid]
                    record("serve.queue", req.enqueued_ns, failed_ns,
                           rid=rid, batch=bid, source=source, error=True)
                    req.state, req.error, req.item = "done", e, None
                return
            batch = _Batch(out=out, rids=rids, n_lanes=n_lanes, id=bid)
            self.metrics.record_batch(len(rids), n_lanes)
            for lane, rid in enumerate(rids):
                req = self._requests[rid]
                req.queue_wait_s = record(
                    "serve.queue", req.enqueued_ns, started_ns, rid=rid,
                    batch=bid, source=source)["duration_s"]
                req.state = "running"
                req.batch = batch
                req.lane = lane

    def _exec_cache_size(self) -> int:
        try:
            return self._exec._cache_size()
        except Exception:  # noqa: BLE001 — observability only
            return -1

    # -- poll / result ------------------------------------------------------

    def poll(self, rid: int) -> str:
        """Non-blocking state of a request: queued / running / done.
        Also advances time-based flushes (cooperative scheduling)."""
        with self._lock:
            req = self._req(rid)
        self._pump()
        if req.state == "running":
            value = req.batch.out.value
            if getattr(value, "is_ready", lambda: True)():
                return "done"
        return "done" if req.state == "done" else req.state

    def result(self, rid: int) -> RequestResult:
        """Block until the request's batch completes; per-request outcome."""
        with self._lock:
            req = self._req(rid)
            if req.result is not None:
                return req.result
            if req.state == "queued":
                self._flush_bucket(req.sig)
            if req.error is not None:
                raise req.error
            batch = req.batch
        # block outside the lock: the flusher and other submitters keep
        # running while XLA computes
        with span("serve.block", rid=rid, batch=batch.id):
            jax.block_until_ready(batch.out.value)
        with self._lock:
            if req.result is not None:     # lost a race to another thread
                return req.result
            with span("serve.collect", rid=rid, batch=batch.id):
                req.result = self._collect(req, batch)
            req.state = "done"
            req.batch = None          # release the stacked batch for GC
            req.item = None
            return req.result

    def _collect(self, req: _Request, batch: _Batch) -> RequestResult:
        """One request's lane of a finished batch, checked, and re-solved
        solo if it came back unhealthy (under ``on_failure="fallback"``)."""
        lane = req.lane
        out = jax.tree.map(lambda x: x[lane], batch.out)
        failed = bool(np.asarray(out.status.code) >= STALLED) or not \
            bool(np.all(np.isfinite(np.asarray(out.value))))
        fell_back = False
        if failed and self.config.on_failure == "fallback":
            with span("serve.fallback", rid=req.rid, batch=batch.id):
                out, fell_back = self._fallback(req)
        status_name = (STATUS_NAMES[int(np.asarray(out.status.code))]
                       if out.status is not None else "UNKNOWN")
        latency = self.metrics.record_result(
            req.submitted_at, req.queue_wait_s, failed, fell_back)
        return RequestResult(
            rid=req.rid, value=float(np.asarray(out.value)), output=out,
            status=out.status, status_name=status_name, failed=failed,
            fell_back=fell_back, shape=req.shape,
            padded_shape=req.padded_shape, latency_s=latency)

    def results(self, rids: Sequence[int]) -> List[RequestResult]:
        """Drain a set of requests (flushes any still queued)."""
        self.flush()
        return [self.result(rid) for rid in rids]

    def _fallback(self, req: _Request):
        """Re-solve one failed request solo through the PR-6 ladder. The
        original (unpadded) problem is used — the fallback path owes the
        caller a healthy answer, not a bucket-shaped one."""
        import repro
        try:
            out = repro.solve(req.problem, req.solver, key=req.key,
                              on_failure="fallback")
        except Exception:  # noqa: BLE001 — fallback is best-effort
            return jax.tree.map(lambda x: x[req.lane], req.batch.out), False
        recovered = bool(np.asarray(out.status.code) < STALLED) and bool(
            np.all(np.isfinite(np.asarray(out.value))))
        if not recovered:
            return jax.tree.map(lambda x: x[req.lane], req.batch.out), False
        return out, True

    # -- observability ------------------------------------------------------

    def stats(self) -> dict:
        """One flat dict: request/batch/latency metrics + cache counters."""
        return self.metrics.summary(self.cache.stats())

    def metrics_text(self) -> str:
        """The process-wide metrics registry (including this server's
        ``repro_serve_*`` series) in Prometheus text exposition format —
        the payload ``launch/serve.py --metrics-port`` serves."""
        return registry().prometheus_text()

    def reset_stats(self) -> None:
        """Zero metrics and cache counters, keeping compiled executables
        and cached artifacts warm — the steady-state measurement hook."""
        self.metrics = ServeMetrics()
        self.cache.reset_counters()

    def _req(self, rid: int) -> _Request:
        try:
            return self._requests[rid]
        except KeyError:
            raise KeyError(f"unknown request id {rid}") from None
