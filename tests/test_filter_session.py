"""FilterPlan → FilterSession: the one declarative entry point.

Fast tier: plan validation (single-sourced cross-field rules), the
four-way ``session.step`` parity pin (jnp/pallas × sharded/unsharded,
mask and compact paths), the uniform StepResult ABI, shim end-of-life,
versioned checkpoints (v1 blobs, fingerprint guard), and the pure
elastic-reshard math. The multi-device 2↔4-shard elastic restores fork
4-forced-device subprocesses (slow tier, like tests/test_sharded_filter.py).
"""

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


def _ordering(**kw):
    from repro.core import OrderingConfig
    kw.setdefault("collect_rate", 100)
    kw.setdefault("calculate_rate", 5000)
    return OrderingConfig(**kw)


# ================================================================ validation
def test_plan_aggregates_all_violations():
    """A plan with several bad fields reports every one in a single
    ValueError (one round trip), each enumerating its valid choices."""
    from repro.core import FilterPlan, paper_filters_4

    with pytest.raises(ValueError) as ei:
        FilterPlan(predicates=paper_filters_4("fig1"), cost_mode="guess",
                   exchange="sometimes", slack=0.5)
    msg = str(ei.value)
    assert "invalid plan field combinations" in msg
    assert "bad cost_mode" in msg and "'static', 'measured'" in msg
    assert "bad exchange" in msg and "compact_slack" in msg


def test_tokenize_plan_audits_clean():
    """The u32-limb contract (zero f64 ops in step + tokenizer modules, no
    host callbacks, collective-free step) pinned through the shared HLO
    auditor — the same pass the CI ``analysis`` job runs."""
    from repro.analysis import audit_plan
    from repro.core import FilterPlan, TokenizeSpec, paper_filters_4

    plan = FilterPlan(predicates=paper_filters_4("fig1"), compact=True,
                      tokenize=TokenizeSpec(32000), ordering=_ordering())
    assert audit_plan(plan) == []


def test_plan_validates_whole_matrix():
    """FilterPlan is the single source of truth for valid combinations —
    same messages the legacy config surfaces raise (they delegate here)."""
    from repro.core import FilterPlan, TokenizeSpec, paper_filters_4
    preds = paper_filters_4("fig1")

    with pytest.raises(ValueError, match="bad cost_mode"):
        FilterPlan(predicates=preds, cost_mode="guess")
    with pytest.raises(ValueError, match="bad backend"):
        FilterPlan(predicates=preds, engine="cuda9000")
    with pytest.raises(ValueError, match="host"):
        FilterPlan(predicates=preds, cost_mode="measured")
    with pytest.raises(ValueError, match="host engine"):
        FilterPlan(predicates=preds, engine="numpy", cost_mode="measured",
                   shards=2)
    with pytest.raises(ValueError, match="compact_output"):
        FilterPlan(predicates=preds, engine="numpy", cost_mode="measured",
                   compact=True)
    with pytest.raises(ValueError, match="compact_capacity"):
        FilterPlan(predicates=preds, capacity=64)
    with pytest.raises(ValueError, match="compact_capacity"):
        FilterPlan(predicates=preds, compact=True, capacity="huge")
    with pytest.raises(ValueError, match="compact_slack"):
        FilterPlan(predicates=preds, compact=True, capacity="auto",
                   slack=0.2)
    with pytest.raises(ValueError, match="exchange"):
        FilterPlan(predicates=preds, exchange="sometimes",
                   scope="centralized")
    with pytest.raises(ValueError, match="CENTRALIZED"):
        FilterPlan(predicates=preds, exchange="deferred")
    with pytest.raises(ValueError, match="device_tokenize"):
        FilterPlan(predicates=preds, tokenize=TokenizeSpec(1000))
    with pytest.raises(ValueError, match="vocab_size"):
        TokenizeSpec(1 << 25)
    with pytest.raises(ValueError, match="shards"):
        FilterPlan(predicates=preds, shards=0)
    with pytest.raises(ValueError, match="predicate"):
        FilterPlan(predicates=[])


def test_legacy_config_delegates_to_plan_rules():
    """AdaptiveFilterConfig and ShardedAdaptiveFilter funnel through the
    same validate_combo (no drift between the surfaces)."""
    import jax

    from repro.core import AdaptiveFilterConfig, ShardedAdaptiveFilter, \
        paper_filters_4

    with pytest.raises(ValueError, match="CENTRALIZED"):
        AdaptiveFilterConfig(exchange="deferred", scope="per_shard")
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="host engine"):
        ShardedAdaptiveFilter(
            paper_filters_4("fig1"),
            AdaptiveFilterConfig(backend="numpy", cost_mode="measured"),
            mesh=mesh)


def test_fingerprint_covers_semantics_not_execution():
    from repro.core import FilterPlan, paper_filters_4, paper_filters_cnf
    preds = paper_filters_4("fig1")
    base = FilterPlan(predicates=preds, ordering=_ordering())
    # execution details don't change identity (elastic/engine-portable)
    same = FilterPlan(predicates=preds, ordering=_ordering(),
                      engine="pallas", compact=True, capacity=128)
    assert base.fingerprint() == same.fingerprint()
    # semantic fields do
    other_chain = FilterPlan(predicates=paper_filters_cnf("fig1"),
                             ordering=_ordering())
    other_rate = FilterPlan(predicates=preds,
                            ordering=_ordering(calculate_rate=999))
    assert base.fingerprint() != other_chain.fingerprint()
    assert base.fingerprint() != other_rate.fingerprint()


# ============================================================ four-way parity
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("sharded", [False, True])
def test_session_step_matches_legacy(backend, sharded):
    """Acceptance pin: session.step is bit-identical to the legacy
    step/step_compact surfaces on both traceable engines, sharded (live
    1-device shard_map) and unsharded, mask and compact paths."""
    import jax
    import jax.numpy as jnp

    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            FilterPlan, FilterSession, ShardedAdaptiveFilter,
                            build_session, paper_filters_4)
    from repro.data.stream import gen_batch

    preds = paper_filters_4("fig1")
    ordering = _ordering(calculate_rate=3000)
    rows = 2048

    def legacy_pair(compact):
        cfg = AdaptiveFilterConfig(ordering=ordering, backend=backend,
                                   compact_output=compact)
        if sharded:
            mesh = jax.make_mesh((1,), ("data",))
            return ShardedAdaptiveFilter(preds, cfg, mesh=mesh)
        return AdaptiveFilter(preds, cfg)

    def session_for(compact):
        if sharded:
            return FilterSession.from_filter(legacy_pair(compact))
        return build_session(FilterPlan(
            predicates=preds, engine=backend, ordering=ordering,
            compact=compact))

    for compact in (False, True):
        legacy = legacy_pair(compact)
        sess = session_for(compact)
        lstate, sstate = legacy.init_state(), sess.init_state()
        for b in range(3):
            cols = jnp.asarray(gen_batch(0, b, b * rows, rows))
            if compact:
                lstate, lpacked, lkept, lmask, lmet = \
                    legacy._jit_compact(lstate, cols)
            else:
                lstate, lmask, lmet = legacy.jit_step(lstate, cols)
            sstate, res = sess.step(sstate, cols)
            np.testing.assert_array_equal(np.asarray(lmask), res.mask_np)
            np.testing.assert_array_equal(np.asarray(lmet.perm),
                                          np.asarray(res.metrics.perm))
            if compact:
                np.testing.assert_array_equal(np.asarray(lpacked),
                                              np.asarray(res.packed))
                np.testing.assert_array_equal(np.asarray(lkept),
                                              np.asarray(res.n_kept))
        for l, s in zip(jax.tree.leaves(lstate), jax.tree.leaves(sstate)):
            np.testing.assert_array_equal(np.asarray(l), np.asarray(s))


# =============================================================== StepResult
def test_step_result_uniform_abi():
    """One ABI across mask / compact / tokenize modes: n_pass, survivors,
    metrics_dict always answer; tokens only on tokenize plans."""
    from repro.core import FilterPlan, TokenizeSpec, build_session, \
        paper_filters_4
    from repro.data import tokenizer
    from repro.data.stream import gen_batch

    preds = paper_filters_4("fig1")
    cols = gen_batch(0, 0, 0, 2048)

    plain = build_session(FilterPlan(predicates=preds, ordering=_ordering()))
    st, res = plain.step(plain.init_state(), cols)
    want_rows = cols[:, res.mask_np]
    np.testing.assert_array_equal(res.survivors(cols), want_rows)
    assert res.packed is None and res.tokens is None
    assert res.n_pass == int(res.mask_np.sum())
    with pytest.raises(ValueError, match="columns"):
        res.survivors()
    with pytest.raises(ValueError, match="tokenize"):
        res.host_tokens()
    d = res.metrics_dict()
    assert set(d) >= {"work_units", "n_pass", "perm", "epoch", "n_dropped"}

    comp = build_session(FilterPlan(predicates=preds, ordering=_ordering(),
                                    compact=True))
    st, cres = comp.step(comp.init_state(), cols)
    np.testing.assert_array_equal(cres.survivors(), want_rows)

    tok = build_session(FilterPlan(predicates=preds, ordering=_ordering(),
                                   compact=True,
                                   tokenize=TokenizeSpec(1000, 4)))
    st, tres = tok.step(tok.init_state(), cols)
    want_toks = tokenizer.rows_to_tokens(want_rows, 1000, 4)
    np.testing.assert_array_equal(tres.host_tokens(), want_toks)
    # the packed buffer still answers on tokenize plans (rows stay packed)
    np.testing.assert_array_equal(tres.survivors(), want_rows)


def test_step_result_reports_dropped():
    from repro.core import FilterPlan, build_session, paper_filters_4
    from repro.data.stream import gen_batch

    sess = build_session(FilterPlan(predicates=paper_filters_4("fig1"),
                                    ordering=_ordering(), compact=True,
                                    capacity=8))
    _, res = sess.step(sess.init_state(), gen_batch(0, 0, 0, 2048))
    popcount = int(res.mask_np.sum())
    assert res.n_pass == 8
    assert res.n_dropped == popcount - 8 > 0
    assert res.metrics_dict()["n_dropped"] == popcount - 8


# ============================================================== deprecation
def test_shims_are_gone():
    """Deprecation end-of-life: the warn-once shims removed at their EOL
    must STAY removed (no resurrection in a refactor)."""
    from repro.core import AdaptiveFilter, ShardedAdaptiveFilter
    from repro.data import pipeline as pipeline_lib

    assert not hasattr(AdaptiveFilter, "step_compact")
    assert not hasattr(AdaptiveFilter, "jit_step_compact")
    assert not hasattr(ShardedAdaptiveFilter, "jit_step_compact")
    assert not hasattr(pipeline_lib, "make_sharded_pipeline")


def test_internal_callers_are_shim_free():
    """Acceptance grep: no internal caller (launch/, benchmarks/,
    examples/, data/) invokes the removed step_compact /
    jit_step_compact surfaces — everything routes through build_session."""
    root = os.path.join(os.path.dirname(__file__), "..")
    offenders = []
    for sub in ("src/repro/launch", "src/repro/data", "benchmarks",
                "examples"):
        for dirpath, _, files in os.walk(os.path.join(root, sub)):
            for f in files:
                if not f.endswith(".py"):
                    continue
                path = os.path.join(dirpath, f)
                text = open(path, encoding="utf-8").read()
                for needle in (".step_compact(", ".jit_step_compact("):
                    if needle in text:
                        offenders.append((path, needle))
    assert not offenders, offenders


# ============================================================== checkpoints
def _run_session(sess, n=4, rows=2048):
    from repro.data.stream import gen_batch
    st = sess.init_state()
    for b in range(n):
        st, _ = sess.step(st, gen_batch(0, b, b * rows, rows))
    return st


def test_v1_blob_loads_into_v2_session():
    """The raw ``fstate_to_arrays`` dicts every pre-session checkpoint
    holds restore verbatim (bit-identical)."""
    import jax

    from repro.core import FilterPlan, build_session, paper_filters_4
    from repro.data.pipeline import fstate_to_arrays

    sess = build_session(FilterPlan(predicates=paper_filters_4("fig1"),
                                    ordering=_ordering(calculate_rate=4000)))
    st = _run_session(sess)
    v1 = fstate_to_arrays(st)                     # unversioned legacy blob
    got = sess.restore_state(v1)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_v2_roundtrip_and_fingerprint_guard():
    import jax

    from repro.core import FilterPlan, build_session, paper_filters_4

    plan = FilterPlan(predicates=paper_filters_4("fig1"),
                      ordering=_ordering(calculate_rate=4000))
    sess = build_session(plan)
    st = _run_session(sess)
    blob = sess.save_state(st)
    assert blob["version"] == 2 and blob["fingerprint"] == plan.fingerprint()
    got = sess.restore_state(blob)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    other = build_session(FilterPlan(
        predicates=paper_filters_4("fig1"),
        ordering=_ordering(calculate_rate=999)))
    with pytest.raises(ValueError, match="fingerprint"):
        other.restore_state(blob)
    with pytest.raises(ValueError, match="version"):
        sess.restore_state({"arrays": blob["arrays"], "version": 99})


def _stacked_arrays(n_shards, seed=0):
    """Synthetic stacked [S, ...] state arrays with per-shard stats."""
    rng = np.random.default_rng(seed)
    P = G = 4
    return {
        "perm": np.tile(np.arange(P, dtype=np.int32), (n_shards, 1)),
        "group_perm": np.tile(np.arange(G, dtype=np.int32), (n_shards, 1)),
        "adj_rank": np.tile(rng.random(G, np.float32) * 3, (n_shards, 1)),
        "rows_into_epoch": np.full((n_shards,), 1536, np.int32),
        "sample_phase": np.full((n_shards,), 36, np.int32),
        "epoch": np.full((n_shards,), 2, np.int32),
        "stats.num_cut": rng.random((n_shards, P), np.float32) * 100,
        "stats.cost_acc": rng.random((n_shards, P), np.float32) * 50,
        "stats.n_monitored": (rng.random(n_shards, np.float32) * 40 + 1),
        "stats.group_cut": rng.random((n_shards, G), np.float32) * 100,
    }


@pytest.mark.parametrize("s_old,s_new", [(2, 4), (4, 2), (2, 1), (1, 4)])
def test_reshard_sums_exact_power_of_two(s_old, s_new):
    """Partitioned (locally-accumulated) epoch stats are sums: the S→S′
    split/merge preserves the global totals EXACTLY for power-of-two
    rescales; the per-shard epoch PHASE (rows_into_epoch) and consensus
    perm/ranks survive verbatim when the source shards agree."""
    from repro.core.session import reshard_state_arrays

    arrays = _stacked_arrays(s_old)
    groups = (0, 1, 2, 3)
    out = reshard_state_arrays(arrays, s_new, groups=groups)
    for k in ("stats.num_cut", "stats.cost_acc", "stats.n_monitored",
              "stats.group_cut"):
        np.testing.assert_array_equal(
            out[k].sum(axis=0, dtype=np.float64)
            if out[k].ndim > arrays[k].ndim - 1 else out[k],
            arrays[k].sum(axis=0, dtype=np.float64).astype(np.float32),
            err_msg=k)
        assert out[k].dtype == arrays[k].dtype
    # non-sum leaves: broadcast consensus (shards agreed → verbatim)
    np.testing.assert_array_equal(np.atleast_2d(out["perm"])[0],
                                  arrays["perm"][0])
    np.testing.assert_array_equal(np.atleast_2d(out["adj_rank"])[0],
                                  arrays["adj_rank"][0])
    # boundary cadence: every new shard adopts the max source phase
    rows = np.atleast_1d(out["rows_into_epoch"])
    assert np.all(rows == arrays["rows_into_epoch"].max())
    if s_new:
        assert rows.shape[0] == s_new
    else:
        assert out["rows_into_epoch"].ndim == 0


def test_reshard_layouts_replicated_vs_partitioned():
    """Eager CENTRALIZED shards hold psum-merged GLOBAL accumulators
    (replicated): merging must take ONE copy, not the S× sum, and a
    replicated target must receive the whole value, not a split."""
    from repro.core.session import reshard_state_arrays

    groups = (0, 1, 2, 3)
    arrays = _stacked_arrays(2)
    for k in ("stats.num_cut", "stats.cost_acc", "stats.n_monitored",
              "stats.group_cut"):
        arrays[k] = np.broadcast_to(arrays[k][:1],
                                    arrays[k].shape).copy()  # replicated G

    # replicated 2-shard → replicated 4-shard: every shard keeps G
    out = reshard_state_arrays(arrays, 4, groups=groups,
                               src_replicated=True, tgt_replicated=True)
    for s in range(4):
        np.testing.assert_array_equal(out["stats.num_cut"][s],
                                      arrays["stats.num_cut"][0])

    # replicated (eager blob) → partitioned (deferred session), same S:
    # each shard gets G/S so the boundary psum recovers exactly G
    out = reshard_state_arrays(arrays, 2, groups=groups,
                               src_replicated=True, tgt_replicated=False)
    np.testing.assert_array_equal(
        out["stats.num_cut"].sum(axis=0), arrays["stats.num_cut"][0])

    # partitioned (deferred blob) → replicated (eager session): every
    # shard adopts the full merged total
    part = _stacked_arrays(2, seed=3)
    out = reshard_state_arrays(part, 2, groups=groups,
                               src_replicated=False, tgt_replicated=True)
    want = part["stats.num_cut"].astype(np.float64).sum(0).astype(np.float32)
    for s in range(2):
        np.testing.assert_array_equal(out["stats.num_cut"][s], want)


def test_v2_blob_records_stats_layout():
    from repro.core import FilterPlan, build_session, paper_filters_4

    sess = build_session(FilterPlan(predicates=paper_filters_4("fig1"),
                                    ordering=_ordering()))
    blob = sess.save_state(sess.init_state())
    assert blob["stats_layout"] == "partitioned"   # unsharded


def test_reshard_rederives_perm_when_shards_disagree():
    """PER_SHARD sources diverge; the reshard re-derives one consensus
    order from the merged stats with the same cnf_order math the epoch
    boundary uses."""
    from repro.core import stats as stats_lib
    from repro.core.session import reshard_state_arrays

    arrays = _stacked_arrays(2)
    arrays["perm"] = np.asarray([[0, 1, 2, 3], [3, 2, 1, 0]], np.int32)
    groups = (0, 1, 2, 3)
    out = reshard_state_arrays(arrays, 4, groups=groups)
    merged = stats_lib.FilterStats(
        num_cut=arrays["stats.num_cut"].astype(np.float64).sum(0)
        .astype(np.float32),
        cost_acc=arrays["stats.cost_acc"].astype(np.float64).sum(0)
        .astype(np.float32),
        n_monitored=arrays["stats.n_monitored"].astype(np.float64).sum()
        .astype(np.float32),
        group_cut=arrays["stats.group_cut"].astype(np.float64).sum(0)
        .astype(np.float32))
    want, _ = stats_lib.cnf_order(
        stats_lib.group_ranks(merged, groups, xp=np),
        stats_lib.member_ranks(merged, xp=np), groups, xp=np)
    assert len({tuple(p) for p in out["perm"]}) == 1
    np.testing.assert_array_equal(out["perm"][0], want)


def test_pipeline_checkpoint_carries_fingerprint():
    """The production pipeline/TrainDriver checkpoint path writes the
    versioned blob, so restoring into a semantically different plan is
    refused instead of silently loading stale adaptive state."""
    from repro.core import FilterPlan, build_session, paper_filters_4
    from repro.data.pipeline import Pipeline
    from repro.data.stream import LogStream

    def mk(calculate_rate):
        sess = build_session(FilterPlan(
            predicates=paper_filters_4("fig1"),
            ordering=_ordering(calculate_rate=calculate_rate)))
        return Pipeline(LogStream(total_rows=131072, batch_rows=16384),
                        sess, batch_size=2, seq_len=32, vocab_size=500)

    p1 = mk(100_000)
    next(iter(p1))
    st = p1.state()
    assert st.filter_state["fingerprint"]
    p_same = mk(100_000)
    p_same.restore(st)                       # same plan → loads
    with pytest.raises(ValueError, match="fingerprint"):
        mk(999).restore(st)                  # different ordering → refused


def test_unsharded_session_loads_sharded_blob():
    """A stacked checkpoint merges down to one executor (S→1 of the
    elastic path, no mesh needed)."""
    from repro.core import FilterPlan, build_session, paper_filters_4

    sess = build_session(FilterPlan(predicates=paper_filters_4("fig1"),
                                    ordering=_ordering()))
    arrays = _stacked_arrays(2)
    st = sess.restore_state(arrays)
    assert np.asarray(st.rows_into_epoch).ndim == 0
    np.testing.assert_array_equal(
        np.asarray(st.stats.num_cut),
        arrays["stats.num_cut"].astype(np.float64).sum(0).astype(np.float32))


# ===================================================== slow: live 2↔4 shards
_ELASTIC_PRELUDE = textwrap.dedent("""
    import jax, numpy as np
    from repro.core import FilterPlan, OrderingConfig, build_session, \\
        paper_filters_4
    from repro.data.stream import gen_batch

    ordering = OrderingConfig(collect_rate=10, calculate_rate=4000)
    preds = paper_filters_4("fig1")
    R = 1024

    def sess(shards):
        return build_session(FilterPlan(
            predicates=preds, ordering=ordering, scope="centralized",
            exchange="deferred", shards=shards))

    def feed(s, st, steps, rows_total):
        for b in range(steps):
            st, _ = s.step(st, gen_batch(0, b, b * rows_total, rows_total))
        return st
""")


@pytest.mark.slow
@pytest.mark.parametrize("s_old,s_new", [(2, 4), (4, 2)])
def test_elastic_restore_rederives_same_perm(s_old, s_new):
    """Acceptance pin: a 2-shard checkpoint restores onto a 4-shard mesh
    (and back); the global stat sums are preserved exactly, and firing the
    boundary exchange on the restored state adopts the IDENTICAL
    permutation the unresharded run adopts (sums are associative)."""
    out = run_py(_ELASTIC_PRELUDE + textwrap.dedent(f"""
        s_old, s_new = {s_old}, {s_new}
        a = sess(s_old)
        # cross one epoch boundary (nontrivial perm), then accumulate a
        # partial epoch of per-shard-divergent deferred evidence
        st = feed(a, a.init_state(), 6, R * s_old)
        assert int(np.asarray(st.epoch).max()) >= 1
        assert float(np.asarray(st.stats.n_monitored).sum()) > 0
        blob = a.save_state(st)

        b = sess(s_new)
        st2 = b.restore_state(blob)
        # perm carried over verbatim (centralized shards agree)
        assert np.asarray(st2.perm).shape[0] == s_new
        for row in np.asarray(st2.perm):
            assert np.array_equal(row, np.asarray(st.perm)[0]), (row,)
        # merged accumulators exactly preserved
        for k in ("num_cut", "cost_acc", "n_monitored", "group_cut"):
            got = np.asarray(getattr(st2.stats, k)).sum(axis=0)
            want = np.asarray(getattr(st.stats, k)).sum(axis=0)
            assert np.array_equal(got, want), (k, got, want)
        # the boundary exchange re-derives the SAME permutation on both
        # meshes — the machine-checkable "sums are associative" claim. The
        # exchange fires only when due: a full epoch more of rows makes it
        # due and leaves the overshoot it keeps as it was
        due = lambda s: s._replace(
            rows_into_epoch=s.rows_into_epoch + ordering.calculate_rate)
        na, _ = a.filter.jit_exchange(due(st))
        nb, _ = b.filter.jit_exchange(due(st2))
        assert (np.asarray(na.epoch) == np.asarray(st.epoch) + 1).all()
        assert (np.asarray(nb.epoch) == np.asarray(st.epoch).max() + 1).all()
        pa, pb = np.asarray(na.perm), np.asarray(nb.perm)
        assert len({{tuple(p) for p in pa}} | {{tuple(p) for p in pb}}) == 1, \\
            (pa, pb)
        ra, rb = np.asarray(na.adj_rank), np.asarray(nb.adj_rank)
        assert np.array_equal(ra[0], rb[0]), (ra, rb)
        print("ELASTIC-OK")
    """))
    assert "ELASTIC-OK" in out


@pytest.mark.slow
def test_sharded_device_tokenize_4dev_matches_host():
    """4-shard tokenize plans run the hash+pack PER SHARD under shard_map
    (a global pack over the shard-sharded buffer hangs the SPMD
    partitioner — the pre-session code path was never drivable on a real
    mesh) and the shard-major token stream is bit-identical to the host
    tokenizer."""
    out = run_py("""
        import numpy as np
        from repro.core import FilterPlan, OrderingConfig, TokenizeSpec, \\
            build_session, paper_filters_4
        from repro.data import tokenizer
        from repro.data.stream import gen_batch

        plan = FilterPlan(
            predicates=paper_filters_4("fig1"),
            ordering=OrderingConfig(collect_rate=100, calculate_rate=50_000),
            scope="centralized", shards=4, compact=True,
            tokenize=TokenizeSpec(1000, 4))
        sess = build_session(plan)
        st = sess.init_state()
        R = 8192
        for b in range(2):
            cols = gen_batch(0, b, b * 4 * R, 4 * R)
            st, res = sess.step(st, cols)
            toks = res.host_tokens()
            want = tokenizer.rows_to_tokens(res.survivors(), 1000, 4)
            assert np.array_equal(toks, want), (toks.shape, want.shape)
        print("TOK-4DEV-OK")
    """)
    assert "TOK-4DEV-OK" in out


@pytest.mark.slow
def test_sharded_pipeline_elastic_restore_2_to_4():
    """ROADMAP closure: a 2-shard ShardedPipeline checkpoint restores onto
    a 4-shard pipeline (filter state resharded, streams resumed at the next
    unconsumed global batch) and keeps emitting LM batches."""
    out = run_py("""
        import numpy as np
        from repro.core import FilterPlan, OrderingConfig, build_session, \\
            paper_filters_4
        from repro.data.pipeline import make_pipeline

        ordering = OrderingConfig(collect_rate=100, calculate_rate=50_000)

        def mk(shards):
            session = build_session(FilterPlan(
                predicates=paper_filters_4("fig1"), ordering=ordering,
                scope="centralized", shards=shards, compact=True))
            return make_pipeline(session, total_rows=2_097_152,
                                 batch_rows=65536, batch_size=4, seq_len=64,
                                 vocab_size=1000)

        p2 = mk(2)
        it = iter(p2)
        head = [next(it) for _ in range(3)]
        ckpt = p2.state()

        p4 = mk(4)
        p4.restore(ckpt)
        assert np.asarray(p4._fstate.perm).shape[0] == 4
        # stream cursors: every new partition resumes at the next
        # unconsumed global batch index
        assert all(s.cursor == max(ckpt.stream_cursors)
                   for s in p4.streams)
        assert p4.rows_in == p2.rows_in and p4.rows_pass == p2.rows_pass
        got = [b for _, b in zip(range(3), iter(p4))]
        assert len(got) == 3
        for b in got:
            assert b["tokens"].shape == (4, 64)
        print("PIPE-ELASTIC-OK")
    """)
    assert "PIPE-ELASTIC-OK" in out
