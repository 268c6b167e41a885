"""From a profiler trace to the device numbers the benchmark reports.

Two steps, kept apart so that the second can be checked on a small
recorded trace without a chip:

1. ``load_xspace`` reads the ``.xplane.pb`` file that ``jax.profiler``
   writes and keeps what the metrics need, in one JSON-ready form:

       {"window": [t0, t1],
        "ops":  [[name, start, duration, meta], ...],   # device 0's ops
        "host": [[name, start, end], ...]}              # host annotations

   in nanoseconds on the trace's common clock. On the TPU an op event is
   named by its whole HLO instruction text; ``name`` keeps the instruction
   name alone (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``),
   which for a Pallas kernel is its function's name
   (``spar_cost_pallas.7``). ``meta`` joins whatever metadata strings the
   event carries (framework op name, source); TPU op events carry none.
2. ``reduce`` turns that into busy time, idle gaps labelled by what the
   host was doing, the ops that took the most time, and the time of the
   ops a name list attributes to one computation.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Sequence

WINDOW = "gwbench.window"          # host annotation around the traced window
DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
META_KEYS = ("hlo_op", "tf_op", "long_name", "source", "source_info",
             "name", "hlo_category")


def load_xspace(path: str, host_prefixes: Sequence[str] = ("serve.",
                                                          WINDOW)) -> dict:
    """The normalized trace of one profiler capture (see module doc)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, host = [], []
    for plane in pd.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if not line.name.startswith(OPS_LINE):
                    continue
                for e in line.events:
                    meta = " ".join(str(v) for k, v in e.stats
                                    if k in META_KEYS)
                    ops.append([short_name(e.name), int(e.start_ns),
                                int(e.duration_ns), meta])
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tuple(host_prefixes)):
                        host.append([e.name, int(e.start_ns), int(e.end_ns)])
    windows = [h for h in host if h[0] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    if not ops:
        raise ValueError(f"no {OPS_LINE!r} events on {DEVICE_PLANE} "
                         f"in {path}")
    w = windows[-1]
    return {"window": [w[1], w[2]], "ops": sorted(ops, key=lambda o: o[1]),
            "host": [h for h in host if h[0] != WINDOW]}


def short_name(name: str) -> str:
    """The HLO instruction name of an op event's text."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def self_times(ops: List[list], lo: int, hi: int) -> Dict[str, int]:
    """Per op name, the time inside [lo, hi] that no op nested in it
    covers: a while loop's event spans its whole body, and its own time
    is only what its body ops leave."""
    out: Dict[str, int] = collections.Counter()
    stack: List[list] = []                  # [name, end, child time]

    def close(entry):
        name, start, end, child = entry
        s, e = _clip(start, end, lo, hi)
        out[name] += max(0, e - s - child)

    for name, start, dur, _ in sorted(ops, key=lambda o: (o[1], -o[2])):
        end = start + dur
        while stack and stack[-1][2] <= start:
            close(stack.pop())
        if stack:
            s, e = _clip(start, end, lo, hi)
            stack[-1][3] += max(0, e - s)
        stack.append([name, start, end, 0])
    while stack:
        close(stack.pop())
    return out


def _clip(start: int, end: int, lo: int, hi: int):
    return max(start, lo), min(end, hi)


def union(intervals: Iterable[tuple], lo: int, hi: int) -> List[tuple]:
    """Merged (start, end) intervals, clipped to [lo, hi]."""
    out: List[list] = []
    for s, e in sorted(intervals):
        s, e = _clip(s, e, lo, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps(busy: List[tuple], lo: int, hi: int) -> List[tuple]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(t: int, host: List[list]) -> str:
    """The innermost host annotation open at time t, or "client" where
    the host was in none of the program's spans."""
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "client"


def matches(op: list, names: Sequence[str]) -> bool:
    return any(n in op[0] or n in op[3] for n in names)


def reduce(trace: dict, attribute: Dict[str, Sequence[str]],
           top: int = 10) -> dict:
    """Busy and idle time of the traced window, its longest idle gaps by
    host label, the device ops with the most self time, and per
    attribution group the device time and event count of the ops whose
    name or metadata holds one of the group's names. Times in seconds."""
    lo, hi = trace["window"]
    ops = trace["ops"]
    busy = union(((o[1], o[1] + o[2]) for o in ops), lo, hi)
    busy_ns = sum(e - s for s, e in busy)
    if ops and not busy_ns:
        raise ValueError("no device op falls in the traced window: the "
                         "device and host clocks do not line up")
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    per_op = collections.Counter(self_times(ops, lo, hi))
    groups = {g: [0, 0] for g in attribute}
    for o in ops:
        s, e = _clip(o[1], o[1] + o[2], lo, hi)
        if e <= s:
            continue
        for g, names in attribute.items():
            if matches(o, names):
                groups[g][0] += e - s
                groups[g][1] += 1
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in per_op.most_common(top)],
        "idle_gaps": [[label_at((s + e) // 2, trace["host"]), (e - s) * 1e-9]
                      for s, e in idle],
        "attributed": {g: {"seconds": t * 1e-9, "events": c}
                       for g, (t, c) in groups.items()},
    }
