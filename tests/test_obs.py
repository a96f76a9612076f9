"""repro.obs: the program's spans and counters inside ``FilterSession.step``.

Tracing is on exactly while a profiler session is: off, a span is the
shared no-op; on, the spans are kept in memory and written to the
profiler's host plane, on one clock. Every sanctioned device→host sync the
session can reach opens ``session.sync`` with its site, and the hot-path
lint's allowlist and these spans name the same sites.
"""

import ast
import glob
import inspect
import textwrap

import numpy as np
import pytest

from repro import obs

ROWS = 4096


def _batch(k: int, rows: int = ROWS) -> np.ndarray:
    from repro.data.stream import gen_batch
    return gen_batch(7, k, k * rows, rows)


def _session(**plan):
    from repro.core import (FilterPlan, OrderingConfig, build_session,
                            paper_filters_4)
    rate = plan.pop("calculate_rate", 8 * ROWS)
    plan.setdefault("engine", "jnp")
    return build_session(FilterPlan(
        predicates=paper_filters_4("fig1"),
        ordering=OrderingConfig(collect_rate=64, calculate_rate=rate),
        **plan))


def _steps(session, n: int, state=None):
    """``n`` steps, returning the final state and every StepResult."""
    state = session.init_state() if state is None else state
    results = []
    for k in range(n):
        state, res = session.step(state, _batch(k))
        results.append(res)
    return state, results


class _Traced:
    """A profiler session around a block, the record cleared on entry."""

    def __init__(self, directory):
        self.dir = str(directory)

    def __enter__(self):
        import jax
        obs.clear()
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        self.snap = obs.snapshot()
        return False

    def spans(self, name):
        return [s for s in self.snap.spans if s.name == name]


def _warm(session, n: int = 2):
    """Compile outside the trace: step the shapes the test will use."""
    _steps(session, n)


# ------------------------------------------------------------- off and on
def test_span_is_the_shared_noop_without_a_profiler():
    obs.clear()
    assert obs.span("session.x") is obs.NOOP
    assert obs.sync("FilterSession._sync_rows_into_epoch") is obs.NOOP
    obs.count("session.x")
    session = _session()
    _, results = _steps(session, 3)
    snap = obs.snapshot()
    assert snap.spans == [] and snap.counts == {} and snap.dropped == 0
    # the step's own timer runs with tracing off too
    assert all(r.gate_s is not None and r.gate_s > 0 for r in results)


@pytest.fixture(scope="module")
def stream_trace(tmp_path_factory):
    """A small stream-plan session, three steps under the profiler. The
    collector is paused meanwhile: a pause that lands between a span's
    clock read and its annotation's would part the two durations."""
    import gc

    session = _session()
    _warm(session)
    gc.disable()
    try:
        with _Traced(tmp_path_factory.mktemp("obs-trace")) as t:
            _, results = _steps(session, 3)
    finally:
        gc.enable()
    t.results = results
    return t


def test_stream_step_spans_nest_and_share_the_step_id(stream_trace):
    t = stream_trace
    roots = t.spans("session.step")
    assert len(roots) == 3
    assert len({r.step for r in roots}) == 3
    for root, res in zip(sorted(roots, key=lambda s: s.t0_ns), t.results):
        assert root.parent is None
        # one timer: gate_s is the root span's duration
        assert res.gate_s == (root.t1_ns - root.t0_ns) / 1e9
        kids = [s for s in t.snap.spans if s.step == root.step
                and s is not root]
        assert {k.name for k in kids} == {"session.h2d", "session.dispatch"}
        for k in kids:
            assert k.parent == "session.step"
            assert root.t0_ns <= k.t0_ns <= k.t1_ns <= root.t1_ns
    # a host batch is counted as bytes from the host
    assert t.snap.counts["session.h2d_bytes"] == 3 * 3 * ROWS * 4
    assert not any(s.name.startswith("bench.") for s in t.snap.spans)


def test_trace_file_holds_the_same_spans_on_the_host_plane(stream_trace):
    """The shared-clock proof: the xplane's host plane carries every
    ``session.*`` span the in-memory record has, with the same durations."""
    from jax.profiler import ProfileData

    t = stream_trace
    (path,) = glob.glob(f"{t.dir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    on_plane = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("session."):
                    on_plane.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns))
    names = {s.name for s in t.snap.spans}
    assert set(on_plane) == names
    for name in names:
        mine = sorted(t.spans(name), key=lambda s: s.t0_ns)
        theirs = sorted(on_plane[name])
        assert len(mine) == len(theirs), name
        for s, (_, dur_ns) in zip(mine, theirs):
            got = s.t1_ns - s.t0_ns
            assert abs(got - dur_ns) <= 0.01 * dur_ns + 20_000, (name, got,
                                                                 dur_ns)


def test_deferred_centralized_steady_step_never_syncs(tmp_path):
    """Batches wider than calculate_rate: every step crosses a boundary.
    The host row counter fires each exchange and the device decides it is
    due, so the steady step reads nothing back and still exchanges once
    per step."""
    session = _session(scope="centralized", exchange="deferred",
                       calculate_rate=ROWS // 2)
    state, _ = _steps(session, 2)                # compile outside the trace
    with _Traced(tmp_path) as t:
        _, results = _steps(session, 3, state)
    assert t.spans("session.sync") == []
    assert "session.syncs" not in t.snap.counts
    assert len(t.spans("session.exchange")) == 3
    assert t.snap.counts["session.exchanges"] == 3
    assert [r.metrics_dict()["epoch"] for r in results] == [3, 4, 5]


def test_per_shard_session_never_syncs(tmp_path):
    session = _session(calculate_rate=ROWS // 2)
    _warm(session)
    with _Traced(tmp_path) as t:
        _steps(session, 3)
    assert t.spans("session.sync") == []
    assert t.spans("session.exchange") == []
    assert "session.syncs" not in t.snap.counts


def test_device_resident_batch_counts_no_host_bytes(tmp_path):
    import jax.numpy as jnp

    session = _session(compact=True, capacity=ROWS, skip_tier="zonemap")
    part = jnp.asarray(_batch(0))
    state = session.init_state()
    state, _ = session.step(state, part)
    with _Traced(tmp_path) as t:
        session.step(state, part)
    assert t.snap.counts.get("session.h2d_bytes", 0) == 0
    (root,) = t.spans("session.step")
    kids = {s.name for s in t.snap.spans if s.parent == "session.step"}
    assert kids == {"session.h2d", "session.triage", "session.dispatch"}


def test_bounded_record_counts_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "MAX_SPANS", 2)
    with _Traced(tmp_path) as t:
        for _ in range(5):
            with obs.span("session.x"):
                pass
    assert len(t.snap.spans) == 2 and t.snap.dropped == 3


# ------------------------------------------- the allowlist and the spans
def _retried_step(session):
    """A step retried from its pre-step state (the guarded runtime's
    rollback does this): the session did not issue the state it is handed
    for the retry, so it re-anchors its boundary counter from the device."""
    state = session.init_state()
    for k in range(4):
        nxt, _ = session.step(state, _batch(k))
        session.step(state, _batch(k))
        state = nxt


def _detour_steps(session, detour, n: int = 12, at: int = 6,
                  back: int = 3):
    """(epoch, perm) of each of ``n`` steps the stream keeps, with one
    detour at step ``at``: "retry" steps the pre-step state once more
    first (an attempt whose result was dropped), "rollback" hands back the
    state issued ``back`` steps earlier and replays from there, "restore"
    restores the checkpoint saved there and replays."""
    state = session.init_state()
    states, blobs, kept = [], [], []
    k = 0
    while k < n:
        states[k:] = [state]
        blobs[k:] = [session.save_state(state)]
        if k == at and detour == "retry":
            session.step(state, _batch(k))
        elif k == at and detour is not None:
            k -= back
            del kept[k:]
            state = states[k] if detour == "rollback" \
                else session.restore_state(blobs[k])
            detour = None
        state, res = session.step(state, _batch(k))
        m = res.metrics_dict()
        kept.append((m["epoch"], m["perm"]))
        k += 1
    return kept


@pytest.mark.parametrize("detour", ["retry", "rollback", "restore"])
def test_detoured_stream_keeps_the_epochs(detour):
    """A retried step, a rollback to an older state and a restored
    checkpoint each hand ``step`` a state the host counter does not count
    for; the epochs and orders then match a run without the detour."""
    plan = dict(scope="centralized", exchange="deferred",
                calculate_rate=3 * ROWS + ROWS // 2)
    want = _detour_steps(_session(**plan), None)
    assert want[-1][0] >= 3
    assert _detour_steps(_session(**plan), detour) == want


SYNC_SITES = {
    "AdaptiveFilter.skip_amb_cap": (
        dict(skip_tier="zonemap"), lambda s: _steps(s, 2)),
    "AdaptiveFilter.observe_for_capacity": (
        dict(compact=True, capacity="auto"), lambda s: _steps(s, 2)),
    "FilterSession._observe_skip_arm": (
        dict(skip_tier="auto"), lambda s: _steps(s, 2)),
    "FilterSession._sync_rows_into_epoch": (
        dict(scope="centralized", exchange="deferred",
             calculate_rate=4 * ROWS), _retried_step),
    "FilterSession.validate_state": (
        {}, lambda s: s.validate_state(s.init_state())),
}


@pytest.mark.parametrize("site", sorted(SYNC_SITES))
def test_each_sync_site_opens_a_counted_sync_span(site, tmp_path):
    plan, drive = SYNC_SITES[site]
    session = _session(**dict(plan))
    drive(session)                       # compile outside the trace
    with _Traced(tmp_path) as t:
        drive(session)
    syncs = t.spans("session.sync")
    assert site in {s.meta["site"] for s in syncs}
    assert t.snap.counts["session.syncs"] == len(syncs)


def _sync_functions():
    """Allowlisted functions that hold a sync the hot-path lint detects
    and that the session's host entry points (``step``, and
    ``validate_state``, the guarded runtime's probe) can reach."""
    from pathlib import Path

    from repro.analysis import hotpath_lint as hl
    from repro.core import plan as _plan

    root = Path(_plan.__file__).parent.parent
    fns = []
    for sub in hl.SCAN_DIRS:
        for py in sorted((root / sub).rglob("*.py")):
            rel = py.relative_to(root).as_posix()
            if rel not in hl.EXCLUDES:
                fns.extend(hl._index_functions(py, rel))
    reach = hl._reachable(
        fns, ("FilterSession.step", "FilterSession.validate_state"))
    return {fn.qualname: fn for fn in fns
            if fn.qualname in reach and fn.qualname in hl.ALLOWLIST
            and not hl.ALLOWLIST[fn.qualname].startswith("trace-time")
            and hl._violations_in(fn)}


def _sync_blocks(fn) -> dict:
    """site → line ranges of the ``with obs.sync(site):`` blocks."""
    out: dict = {}
    for node in ast.walk(fn.node):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            call = item.context_expr
            if isinstance(call, ast.Call) and isinstance(
                    call.func, ast.Attribute) and call.func.attr == "sync" \
                    and isinstance(call.args[0], ast.Constant):
                out.setdefault(call.args[0].value, []).append(
                    (node.lineno, node.end_lineno))
    return out


def test_allowlisted_syncs_open_session_sync():
    """Every sanctioned sync the session reaches sits inside
    ``obs.sync(<its qualname>)``, and these are the sites the runtime
    test above drives: the static allowlist and the counter agree."""
    from repro.analysis import hotpath_lint as hl

    fns = _sync_functions()
    assert set(fns) == set(SYNC_SITES)
    for qual, fn in fns.items():
        blocks = _sync_blocks(fn).get(qual)
        assert blocks, f"{qual} opens no obs.sync({qual!r})"
        for line, _, _ in hl._violations_in(fn):
            assert any(a <= line <= b for a, b in blocks), (qual, line)


def test_no_program_span_is_named_like_the_benchmarks():
    """``bench.*`` is the benchmark's prefix (its trace reducer keys on
    it); the program's spans are ``session.*``."""
    from repro.core import adaptive_filter, session

    for mod in (session, adaptive_filter):
        tree = ast.parse(textwrap.dedent(inspect.getsource(mod)))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) \
                    and node.func.attr in ("span", "step_span", "count") \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "obs":
                assert node.args[0].value.startswith("session."), \
                    ast.unparse(node)
