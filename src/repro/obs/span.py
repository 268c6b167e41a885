"""Solve-lifecycle spans — lightweight host-side timing scopes.

A span is a named wall-clock interval::

    from repro import obs

    with obs.span("compile"):
        executable = lowered.compile()

Spans nest (the active stack is thread-local, so concurrent server
threads never corrupt each other's nesting) and each completed span is
appended to one process-wide bounded ring, which :func:`spans` snapshots
and :func:`repro.obs.report` aggregates into the per-stage lifecycle
breakdown (select → validate → compile → dispatch → fallback).

The record a span yields is a plain dict — callers may attach attributes
mid-flight (``with span("dispatch") as sp: ...; sp["compiled"] = True``),
which is how ``repro.solve`` marks the dispatches that triggered an XLA
compilation.

With ``REPRO_OBS_XLA=1`` (or ``configure(xla_annotations=True)``) every
span also enters a ``jax.profiler.TraceAnnotation`` of the same name, so
host-side spans land as named regions in XLA profiler traces with zero
changes at the call sites.

Every record carries an ``id`` (process-unique) and the ``parent_id``
of the span open beneath it on the same thread, so one request or batch
can be followed through nested stages. An interval that starts in one
call and ends in another (a request's wait in its bucket) cannot be a
``with`` block; :func:`record` appends it, finished, to the same ring.

One clock: records are stamped in integer nanoseconds (``start_ns`` /
``end_ns``) by :func:`now_ns`, the wall clock that ``jax.profiler``
stamps its host events with (tsl's ``GetCurrentTimeNanos``, i.e.
``CLOCK_REALTIME``). A record therefore lands on the profiler's
timeline once the capture's ``profile_start_time`` is added to the
capture's relative times. ``start_s`` (relative to this module's import)
and ``duration_s`` are derived from the same two readings.

Overhead per span is two clock reads plus one deque append (~1 µs) —
safe on the serving hot path.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional

_TRUTHY = {"1", "true", "yes", "on"}

# bounded: a long-lived server must not grow span history without limit
MAX_SPANS = 65536

now_ns = time.time_ns           # the profiler's host clock (see module doc)
_T0_NS = now_ns()                # zero of the process-relative start_s
_ids = itertools.count(1)        # next() is atomic under the GIL
_lock = threading.Lock()
_records: "deque[dict]" = deque(maxlen=MAX_SPANS)
_tls = threading.local()

# None = resolve from the REPRO_OBS_XLA env var at span entry
_xla_annotations: Optional[bool] = None


def configure(xla_annotations: Optional[bool] = None) -> None:
    """Set the XLA-annotation pass-through (None = defer to env)."""
    global _xla_annotations
    _xla_annotations = xla_annotations


def _use_xla() -> bool:
    if _xla_annotations is not None:
        return _xla_annotations
    return os.environ.get("REPRO_OBS_XLA", "").strip().lower() in _TRUTHY


def _stack() -> List[dict]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _new_record(name: str, parent: Optional[dict], depth: int,
                attrs: dict) -> dict:
    rec: Dict = {
        "name": name,
        "id": next(_ids),
        "parent_id": parent["id"] if parent else None,
        "start_ns": 0,
        "end_ns": 0,
        "start_s": 0.0,
        "duration_s": 0.0,
        "depth": depth,
        "parent": parent["name"] if parent else None,
        "thread": threading.current_thread().name,
    }
    rec.update(attrs)
    return rec


def _stamp(rec: dict, start_ns: int, end_ns: Optional[int] = None) -> None:
    rec["start_ns"] = start_ns
    rec["start_s"] = (start_ns - _T0_NS) * 1e-9
    if end_ns is not None:
        rec["end_ns"] = end_ns
        rec["duration_s"] = (end_ns - start_ns) * 1e-9


@contextmanager
def span(name: str, **attrs) -> Iterator[dict]:
    """Record a named wall-clock span; yields its (mutable) record dict.

    Extra keyword arguments become attributes of the record; more can be
    attached to the yielded dict before the block exits. Records carry
    ``name`` / ``id`` / ``parent_id`` / ``start_ns`` / ``end_ns`` /
    ``start_s`` (process-relative) / ``duration_s`` / ``depth`` /
    ``parent`` (the parent's name) / ``thread``. ``start_ns`` is set
    before the block runs; the end is stamped when it exits.
    """
    stack = _stack()
    rec = _new_record(name, stack[-1] if stack else None, len(stack), attrs)
    stack.append(rec)
    ann = None
    if _use_xla():
        try:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        except Exception:  # noqa: BLE001 — profiling must never break a solve
            ann = None
    # stamped inside the annotation, so the record sits within its event
    _stamp(rec, now_ns())
    try:
        yield rec
    finally:
        _stamp(rec, rec["start_ns"], now_ns())
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:  # noqa: BLE001
                pass
        stack.pop()
        with _lock:
            _records.append(rec)


def record(name: str, start_ns: int, end_ns: int, **attrs) -> dict:
    """Append a finished span for ``[start_ns, end_ns]`` (:func:`now_ns`
    readings) and return its record.

    For an interval that starts and ends in different calls, such as a
    request's wait in its bucket. It has no parent (``parent_id`` None,
    depth 0) and enters no ``TraceAnnotation``: the host is not doing
    that work, and a profiler region would mislabel what it is doing.
    """
    rec = _new_record(name, None, 0, attrs)
    _stamp(rec, start_ns, end_ns)
    with _lock:
        _records.append(rec)
    return rec


def spans() -> List[dict]:
    """Snapshot of completed span records, ordered by start time.

    (Completion order interleaves children before parents; sorting by
    ``start_ns`` restores the lifecycle order a reader expects.)
    """
    with _lock:
        out = [dict(r) for r in _records]
    return sorted(out, key=lambda r: r["start_ns"])


def clear_spans() -> None:
    with _lock:
        _records.clear()


def span_breakdown(records: Optional[List[dict]] = None) -> Dict[str, dict]:
    """Aggregate span durations by name: ``{name: {count, total_s}}``."""
    if records is None:
        records = spans()
    agg: Dict[str, dict] = {}
    for r in records:
        slot = agg.setdefault(r["name"], {"count": 0, "total_s": 0.0})
        slot["count"] += 1
        slot["total_s"] += r["duration_s"]
    return agg
