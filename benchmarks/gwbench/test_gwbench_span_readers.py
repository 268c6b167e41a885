"""Tests of the per-layer readers that read the program's serve spans
(``serve.queue_ms_per_solve``, ``serve.collect_ms_per_solve`` and their
``.allpairs`` twins) on hand-built span lists: what each sums, the
``serve.fallback`` children the collect reader subtracts, and silence
where the program records no such span."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def _span(name, ms, id_=0, parent_id=None, **attrs):
    return dict(name=name, duration_s=ms * 1e-3, id=id_,
                parent_id=parent_id, **attrs)


_QUEUE_SPANS = [_span("serve.queue", 4.0, 1, rid=0, batch=0),
                _span("serve.queue", 0.5, 2, rid=1, batch=0),
                _span("serve.batch", 9.0, 3, batch=0)]
_COLLECT_SPANS = [_span("serve.block", 50.0, 1, rid=0, batch=0),
                  _span("serve.collect", 30.0, 2, rid=0, batch=0),
                  _span("serve.fallback", 26.0, 3, parent_id=2, rid=0),
                  _span("serve.collect", 6.0, 4, rid=1, batch=0),
                  # a solo solve outside the result path: not subtracted
                  _span("serve.fallback", 100.0, 5, parent_id=None)]


@pytest.mark.parametrize("suffix", ["", ".allpairs"])
@pytest.mark.parametrize("name,spans,solves,want", [
    ("serve.queue_ms_per_solve", _QUEUE_SPANS, 2, 2.25),
    ("serve.queue_ms_per_solve", _COLLECT_SPANS, 2, None),
    ("serve.queue_ms_per_solve", _QUEUE_SPANS, 0, None),
    ("serve.collect_ms_per_solve", _COLLECT_SPANS, 2, 5.0),
    ("serve.collect_ms_per_solve", _QUEUE_SPANS, 2, None),
    ("serve.collect_ms_per_solve", _COLLECT_SPANS, 0, None),
], ids=["queue", "queue_absent", "queue_no_solves", "collect",
        "collect_absent", "collect_no_solves"])
def test_serve_span_readers_on_hand_built_spans(name, spans, solves, want,
                                                 suffix):
    got = harness.load_layer_metric(name + suffix).read(
        SimpleNamespace(spans=spans, solves=solves))
    assert got == (None if want is None else pytest.approx(want))
