"""Single-pass ingestion: fused in-kernel compaction, O(R) gather parity,
deferred epoch exchange, auto capacity, device tokenize.

Fast cases run on the default 1-device CPU (shard_map live where needed);
the collective-cadence HLO pins fork 4-forced-device subprocesses like
tests/test_sharded_filter.py (slow tier).
"""

import logging
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


# ====================================================== compaction parity
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("capacity", [None, 512, 8])
def test_three_way_compaction_parity(backend, capacity):
    """Fused in-kernel (pallas) / O(R) cumsum (jnp) / legacy argsort /
    host boolean mask all agree — including capacity saturation."""
    import jax.numpy as jnp

    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            OrderingConfig, paper_filters_4)
    from repro.core.filter_exec import compact_fixed, compact_fixed_argsort
    from repro.kernels.filter_chain.ref import compact_fixed_ref
    from repro.data.stream import gen_batch

    rows = 4096
    cap = capacity or rows
    filt = AdaptiveFilter(paper_filters_4("fig1"), AdaptiveFilterConfig(
        backend=backend, compact_output=True, compact_capacity=capacity,
        ordering=OrderingConfig(collect_rate=100, calculate_rate=50_000)))
    state = filt.init_state()
    cols = jnp.asarray(gen_batch(0, 0, 0, rows))

    _, packed, n_kept, mask, metrics = filt._jit_compact(state, cols)
    mask_np = np.asarray(mask)

    ref, n_ref = compact_fixed_ref(cols, mask_np, cap)          # host oracle
    jf, jn = compact_fixed(cols, jnp.asarray(mask_np), cap)     # O(R) cumsum
    af, an = compact_fixed_argsort(cols, jnp.asarray(mask_np), cap)  # legacy

    assert int(n_kept) == n_ref == int(jn) == int(an)
    np.testing.assert_array_equal(np.asarray(packed), ref)
    np.testing.assert_array_equal(np.asarray(jf), ref)
    np.testing.assert_array_equal(np.asarray(af), ref)
    # saturation accounting: dropped = popcount - kept, surfaced in metrics
    assert int(metrics.n_dropped) == int(mask_np.sum()) - int(n_kept)
    if capacity == 8:
        assert int(metrics.n_dropped) > 0


def test_compact_fixed_edge_masks():
    """Cumsum scatter == argsort gather on degenerate masks."""
    import jax.numpy as jnp

    from repro.core.filter_exec import compact_fixed, compact_fixed_argsort

    cols = jnp.asarray(np.arange(3 * 64, dtype=np.float32).reshape(3, 64))
    for mask in (np.zeros(64, bool), np.ones(64, bool),
                 np.arange(64) % 7 == 0):
        for cap in (1, 16, 64, 128):
            a, na = compact_fixed(cols, jnp.asarray(mask), cap, fill=-1.0)
            b, nb = compact_fixed_argsort(cols, jnp.asarray(mask), cap,
                                          fill=-1.0)
            assert int(na) == int(nb)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ================================================== deferred epoch exchange
def _perm_trace(exchange, steps=8):
    import jax
    import jax.numpy as jnp

    from repro.core import (AdaptiveFilterConfig, OrderingConfig,
                            ShardedAdaptiveFilter, paper_filters_4)
    from repro.data.stream import DriftConfig, gen_batch

    mesh = jax.make_mesh((1,), ("data",))
    cfg = AdaptiveFilterConfig(
        scope="centralized", exchange=exchange,
        ordering=OrderingConfig(collect_rate=50, calculate_rate=6000))
    sf = ShardedAdaptiveFilter(paper_filters_4("fig1"), cfg, mesh=mesh)
    st = sf.init_state()
    drift = DriftConfig(kind="regime", period_rows=8192)
    out = []
    for b in range(steps):
        cols = jnp.asarray(gen_batch(0, b, b * 2048, 2048, drift))
        st, _, _ = sf.jit_step(st, cols)
        st = sf.maybe_exchange(st)
        out.append((int(np.asarray(st.epoch)[0]),
                    tuple(np.asarray(st.perm)[0].tolist())))
    return out


def test_deferred_matches_eager_exactly():
    """Sums are associative: deferring the merge to the boundary must adopt
    the IDENTICAL perm at the identical epoch, drift and all."""
    assert _perm_trace("eager") == _perm_trace("deferred")


def test_deferred_async_lags_at_most_one_epoch():
    """deferred-async folds merged stats one boundary late: each epoch's
    perm equals the eager perm of the same or the previous epoch."""
    eager = _perm_trace("eager", steps=10)
    async_ = _perm_trace("deferred-async", steps=10)
    by_epoch = {}
    for ep, perm in eager:
        by_epoch[ep] = perm
    for ep, perm in async_:
        allowed = {by_epoch.get(ep), by_epoch.get(ep - 1)}
        assert perm in allowed, (ep, perm, allowed)
    # and it does converge: same final epoch count
    assert async_[-1][0] == eager[-1][0] > 0


EXCHANGE_MODES = ["deferred", "deferred-async"]


@pytest.mark.parametrize("exchange", EXCHANGE_MODES)
def test_session_exchange_matches_host_path(exchange):
    """The session fires each boundary from its host row counter and the
    device re-checks it; with a rate that is no multiple of the batch
    width (boundaries on some steps, not others) the epochs and adopted
    orders equal the numpy host path's, drift and all."""
    import jax

    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            FilterPlan, OrderingConfig, build_session,
                            paper_filters_4)
    from repro.data.stream import DriftConfig, gen_batch

    rows = 2048
    ordering = OrderingConfig(collect_rate=50, calculate_rate=5000)
    drift = DriftConfig(kind="regime", period_rows=6000)
    batches = [gen_batch(3, b, b * rows, rows, drift) for b in range(14)]
    session = build_session(
        FilterPlan(predicates=paper_filters_4("fig1"), ordering=ordering,
                   scope="centralized", exchange=exchange),
        mesh=jax.make_mesh((1,), ("data",)))
    state = session.init_state()
    got = []
    for batch in batches:
        state, res = session.step(state, batch)
        m = res.metrics_dict()
        got.append((m["epoch"], tuple(m["perm"][0])))
    host = AdaptiveFilter(paper_filters_4("fig1"), AdaptiveFilterConfig(
        ordering=ordering, scope="centralized", exchange=exchange,
        backend="numpy"))
    want = [(m["epoch"], tuple(m["perm"]))
            for _, _, m in host._process_stream_host(batches)]
    assert got == want
    epochs = [e for e, _ in got]
    assert epochs[-1] >= 4
    assert any(a == b for a, b in zip(epochs, epochs[1:]))   # no boundary
    assert len({p for _, p in got}) > 2                      # re-ranked


@pytest.mark.parametrize("exchange", EXCHANGE_MODES)
def test_maybe_exchange_off_boundary_keeps_the_state(exchange):
    """A direct ``maybe_exchange`` on a state that is not due dispatches
    the exchange and gets every value back unchanged — epoch, perm,
    accumulators and the deferred-async stash."""
    import jax
    import jax.numpy as jnp

    from repro.core import (AdaptiveFilterConfig, OrderingConfig,
                            ShardedAdaptiveFilter, paper_filters_4)
    from repro.data.stream import gen_batch

    sf = ShardedAdaptiveFilter(paper_filters_4("fig1"), AdaptiveFilterConfig(
        scope="centralized", exchange=exchange,
        ordering=OrderingConfig(collect_rate=50, calculate_rate=5000)),
        mesh=jax.make_mesh((1,), ("data",)))
    st = sf.init_state()
    for b in range(3):                      # 6144 rows: the first boundary
        st, _, _ = sf.jit_step(st, jnp.asarray(gen_batch(0, b, b * 2048,
                                                         2048)))
    st = sf.maybe_exchange(st)
    assert int(np.asarray(st.epoch)[0]) == 1
    st, _, _ = sf.jit_step(st, jnp.asarray(gen_batch(0, 3, 6144, 2048)))
    assert int(np.asarray(st.rows_into_epoch)[0]) < 5000
    stash = sf._stash
    assert (stash is None) == (exchange == "deferred")
    after = sf.maybe_exchange(st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(after)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if stash is not None:
        assert bool(np.asarray(stash[1])[0])
        for a, b in zip(jax.tree.leaves(stash), jax.tree.leaves(sf._stash)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_exchange_config_validation():
    from repro.core import AdaptiveFilterConfig

    with pytest.raises(ValueError, match="exchange"):
        AdaptiveFilterConfig(exchange="sometimes", scope="centralized")
    with pytest.raises(ValueError, match="CENTRALIZED"):
        AdaptiveFilterConfig(exchange="deferred", scope="per_shard")
    with pytest.raises(ValueError, match="compact_capacity"):
        AdaptiveFilterConfig(compact_output=True, compact_capacity="huge")
    with pytest.raises(ValueError, match="compact_slack"):
        AdaptiveFilterConfig(compact_output=True, compact_capacity="auto",
                             compact_slack=0.5)


@pytest.mark.slow
def test_deferred_per_step_hlo_has_no_collectives():
    """The point of deferral: the per-STEP compiled module is collective-
    free (indistinguishable from PER_SHARD on the wire); the one all-reduce
    lives in the boundary exchange module. Pinned through the shared HLO
    auditor (``repro.analysis.hlo_audit``) so this test and the CI
    ``analysis`` job enforce the identical contract."""
    out = run_py("""
        from repro.analysis import audit_plan, collectives_in, errors
        from repro.core import FilterPlan, OrderingConfig, paper_filters_4

        ordering = OrderingConfig(collect_rate=10, calculate_rate=2000)
        for exchange in ("eager", "deferred", "deferred-async"):
            plan = FilterPlan(predicates=paper_filters_4("fig1"),
                              scope="centralized", shards=4,
                              exchange=exchange, ordering=ordering)
            diags = audit_plan(plan)
            assert not errors(diags), [d.render() for d in diags]
        # and the auditor is not vacuous: an eager CENTRALIZED step audited
        # as if it were deferred must flag the in-step collective
        from repro.analysis import audit_step_text
        from repro.core import build_session
        plan = FilterPlan(predicates=paper_filters_4("fig1"),
                          scope="centralized", shards=4, ordering=ordering)
        session = build_session(plan)
        import jax.numpy as jnp
        from repro.data.stream import gen_batch
        cols = jnp.asarray(gen_batch(0, 0, 0, 4096 * 4))
        txt = session.compiled_step_texts(session.init_state(), cols)["step"]
        assert collectives_in(txt)
        deferred = FilterPlan(predicates=paper_filters_4("fig1"),
                              scope="centralized", shards=4,
                              exchange="deferred", ordering=ordering)
        found = audit_step_text(txt, deferred, num_shards=4)
        assert [d.code for d in found] == ["hlo-step-collective"], found
        print("DEFERRED-HLO-OK")
    """)
    assert "DEFERRED-HLO-OK" in out


@pytest.mark.slow
def test_deferred_converges_across_shards():
    """4 heterogeneous shards: deferred CENTRALIZED adopts the same single
    global perm eager does, with one collective per epoch instead of one
    per step."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (AdaptiveFilterConfig, OrderingConfig,
                                ShardedAdaptiveFilter)
        from repro.core.predicates import OP_GT, Predicate

        preds = [Predicate(f"c{i}", i, OP_GT, 0.5, static_cost=1.0)
                 for i in range(3)]
        R = 4096
        ordering = OrderingConfig(collect_rate=10, calculate_rate=2000)
        cols_np = np.full((3, R * 4), 1.0, np.float32)
        for s in range(4):
            cols_np[s % 3, s * R:(s + 1) * R] = 0.0
        cols = jnp.asarray(cols_np)

        def run(exchange):
            sf = ShardedAdaptiveFilter(preds, AdaptiveFilterConfig(
                scope="centralized", exchange=exchange, ordering=ordering))
            st = sf.init_state()
            for _ in range(3):
                st, mask, met = sf.jit_step(st, cols)
                st = sf.maybe_exchange(st)
            return (np.asarray(st.perm), np.asarray(st.epoch),
                    np.asarray(mask))

        perm_e, ep_e, mask_e = run("eager")
        perm_d, ep_d, mask_d = run("deferred")
        assert (ep_e > 0).all() and (ep_d > 0).all()
        assert len({tuple(p) for p in perm_d}) == 1, perm_d
        assert np.array_equal(perm_e, perm_d), (perm_e, perm_d)
        assert np.array_equal(ep_e, ep_d)
        assert np.array_equal(mask_e, mask_d)
        print("DEFERRED-CONV-OK")
    """)
    assert "DEFERRED-CONV-OK" in out


# ======================================================== capacity auto-tune
def test_auto_capacity_tracks_pass_rate():
    """compact_capacity='auto' re-quantizes to a 128-multiple near
    pass_rate × batch × slack at the first epoch boundary."""
    import jax.numpy as jnp

    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            OrderingConfig, paper_filters_4)
    from repro.data.stream import gen_batch

    rows = 4096
    filt = AdaptiveFilter(paper_filters_4("fig1"), AdaptiveFilterConfig(
        compact_output=True, compact_capacity="auto", compact_slack=1.5,
        ordering=OrderingConfig(collect_rate=20, calculate_rate=8192)))
    assert filt.resolve_capacity(rows) == rows          # lossless cold start
    # auto mode must not let a capacity=None trace pin a stale width —
    # callers have to thread resolve_capacity() per call
    with pytest.raises(ValueError, match="resolve_capacity"):
        filt._step_compact(filt.init_state(),
                           jnp.zeros((4, 256), jnp.float32))

    batches = [np.asarray(gen_batch(0, b, b * rows, rows)) for b in range(6)]
    metrics = [m for _, _, m in filt.process_stream(batches)]
    assert metrics[-1]["epoch"] >= 1
    cap = filt.resolve_capacity(rows)
    assert cap < rows and cap % 128 == 0
    pass_rate = np.mean([m["n_pass"] / rows for m in metrics])
    want = pass_rate * rows * 1.5
    assert abs(cap - want) <= 256 + want * 0.5, (cap, want)
    # tuned capacity never saturated on this stream (slack did its job)
    assert all(m["n_dropped"] == 0 for m in metrics)


def test_overflow_surfaced_and_warned(caplog):
    """Tiny fixed capacity: n_dropped lands in the metrics dict and
    process_stream logs a one-line warning."""
    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            OrderingConfig, paper_filters_4)
    from repro.data.stream import gen_batch

    filt = AdaptiveFilter(paper_filters_4("fig1"), AdaptiveFilterConfig(
        compact_output=True, compact_capacity=8,
        ordering=OrderingConfig(collect_rate=100, calculate_rate=50_000)))
    batch = np.asarray(gen_batch(0, 0, 0, 2048))
    with caplog.at_level(logging.WARNING):
        survivors, mask, m = next(iter(filt.process_stream([batch])))
    assert m["n_dropped"] == int(mask.sum()) - 8 > 0
    assert survivors.shape[1] == 8
    assert any("compaction overflow" in r.message for r in caplog.records)


# ========================================================== device tokenize
def test_device_tokenize_matches_host_pipeline():
    """Pipeline + ShardedPipeline with device_tokenize=True emit LM batches
    bit-identical to the host tokenizer path."""
    import jax

    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            OrderingConfig, ShardedAdaptiveFilter,
                            paper_filters_4)
    from repro.core.plan import TokenizeSpec
    from repro.core.session import FilterSession
    from repro.data.pipeline import Pipeline, make_pipeline
    from repro.data.stream import DriftConfig, LogStream

    ordering = OrderingConfig(collect_rate=100, calculate_rate=100_000)

    def mk_plain(compact, devtok):
        cfg = AdaptiveFilterConfig(ordering=ordering, compact_output=compact)
        stream = LogStream(total_rows=131072, batch_rows=16384)
        return Pipeline(stream, AdaptiveFilter(paper_filters_4("fig1"), cfg),
                        batch_size=4, seq_len=64, vocab_size=1000,
                        device_tokenize=devtok)

    host = [b for _, b in zip(range(3), iter(mk_plain(False, False)))]
    dev = [b for _, b in zip(range(3), iter(mk_plain(True, True)))]
    assert len(host) == len(dev) == 3
    for a, b in zip(host, dev):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["labels"], b["labels"])

    def mk_sharded(devtok):
        cfg = AdaptiveFilterConfig(ordering=ordering, compact_output=True)
        mesh = jax.make_mesh((1,), ("data",))
        filt = ShardedAdaptiveFilter(paper_filters_4("fig1"), cfg, mesh=mesh)
        session = FilterSession.from_filter(
            filt, tokenize=TokenizeSpec(1000, 8) if devtok else None)
        return make_pipeline(
            session, total_rows=131072, batch_rows=16384, batch_size=4,
            seq_len=64, vocab_size=1000, drift=DriftConfig())

    sh = [b for _, b in zip(range(3), iter(mk_sharded(False)))]
    sd = [b for _, b in zip(range(3), iter(mk_sharded(True)))]
    for a, b in zip(sh, sd):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["labels"], b["labels"])


def test_device_tokenize_needs_compact():
    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            paper_filters_4)
    from repro.data.pipeline import Pipeline
    from repro.data.stream import LogStream

    filt = AdaptiveFilter(paper_filters_4("fig1"), AdaptiveFilterConfig())
    with pytest.raises(ValueError, match="device_tokenize"):
        Pipeline(LogStream(total_rows=1024, batch_rows=1024), filt,
                 batch_size=2, seq_len=16, vocab_size=100,
                 device_tokenize=True)
