"""The ``program_span`` readers on hand-built records of the program's
spans: each divides by the ``session.step`` spans, and reads nothing where
no step was recorded or the program records no spans."""

import sys

import pytest

from bench import program_spans
from bench.metrics import dispatch_host_ms, h2d_host_ms, host_sync_ms


def _span(name, t0_ms, t1_ms, parent="session.step", step=0, **meta):
    from repro.obs import Span

    return Span(name, int(t0_ms * 1e6), int(t1_ms * 1e6), parent, step, meta)


def _snapshot(spans):
    from repro.obs import Snapshot

    return Snapshot(spans, {}, 0)


#: two steps: h2d 2 + 3 ms, triage 0.5 ms, dispatch 1 + 1 ms, one sync of
#: 4 ms inside the second step's exchange
TWO_STEPS = [
    _span("session.step", 0, 10, parent=None, step=0),
    _span("session.h2d", 0, 2),
    _span("session.triage", 2, 2.5),
    _span("session.dispatch", 2.5, 3.5),
    _span("session.step", 20, 30, parent=None, step=1),
    _span("session.h2d", 20, 23, step=1),
    _span("session.dispatch", 23, 24, step=1),
    _span("session.exchange", 24, 29, step=1),
    _span("session.sync", 24, 28, parent="session.exchange", step=1,
          site="AdaptiveFilter.exchange_due"),
]

READERS = [(h2d_host_ms, 2.5), (dispatch_host_ms, 1.25),
           (host_sync_ms, 2.0)]
IDS = [m.__name__.rsplit(".", 1)[1] for m, _ in READERS]


@pytest.mark.parametrize("metric,want", READERS, ids=IDS)
def test_reader_on_a_hand_built_record(metric, want, monkeypatch):
    snap = _snapshot(TWO_STEPS)
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)
    assert metric.read(None) == pytest.approx(want)


@pytest.mark.parametrize("metric", [m for m, _ in READERS], ids=IDS)
def test_reader_reads_nothing_without_steps(metric, monkeypatch):
    empty = _snapshot([])
    monkeypatch.setattr(program_spans, "snapshot", lambda: empty)
    assert metric.read(None) is None


@pytest.mark.parametrize("metric", [m for m, _ in READERS], ids=IDS)
def test_reader_reads_nothing_from_a_program_without_spans(metric,
                                                           monkeypatch):
    import repro

    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    assert program_spans.snapshot() is None
    assert metric.read(None) is None


def test_a_window_without_syncs_reads_zero(monkeypatch):
    snap = _snapshot([s for s in TWO_STEPS if s.name != "session.sync"])
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)
    assert host_sync_ms.read(None) == 0.0
