"""ShardedAdaptiveFilter: scope semantics under real shard_map + device-side
compaction.

Fast cases run in-process on an explicit 1-device mesh (shard_map is live,
just unreplicated). The 4-device cases fork a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count so the main pytest process
keeps seeing exactly 1 device (contract §MULTI-POD 0); they are ``slow``
tier and also run in CI's dedicated sharded job.
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


# ================================================================= fast tier
def _one_device_filter(cfg):
    import jax

    from repro.core import ShardedAdaptiveFilter, paper_filters_4
    mesh = jax.make_mesh((1,), ("data",))
    return ShardedAdaptiveFilter(paper_filters_4("fig1"), cfg, mesh=mesh)


def test_sharded_one_device_matches_unsharded():
    """A 1-shard mesh is the degenerate case: identical mask, perm, state."""
    import jax.numpy as jnp

    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            OrderingConfig, paper_filters_4, shard_slice)
    from repro.data.stream import gen_batch

    cfg = AdaptiveFilterConfig(ordering=OrderingConfig(collect_rate=100,
                                                       calculate_rate=4000))
    sharded = _one_device_filter(cfg)
    ref = AdaptiveFilter(paper_filters_4("fig1"), cfg)
    sstate, rstate = sharded.init_state(), ref.init_state()
    for b in range(3):
        cols = jnp.asarray(gen_batch(0, b, b * 8192, 8192))
        sstate, smask, smet = sharded.jit_step(sstate, cols)
        rstate, rmask, rmet = ref.jit_step(rstate, cols)
        assert np.array_equal(np.asarray(smask), np.asarray(rmask))
        assert np.array_equal(np.asarray(smet.perm)[0], np.asarray(rmet.perm))
    final = shard_slice(sstate, 0)
    assert np.array_equal(np.asarray(final.perm), np.asarray(rstate.perm))
    np.testing.assert_allclose(np.asarray(final.adj_rank),
                               np.asarray(rstate.adj_rank), rtol=1e-6)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_compact_output_matches_boolean_mask(backend):
    """compact_output=True: padded on-device survivors are bit-identical
    (up to padding) to the host boolean-mask path — for BOTH traceable
    engines, which share the same compaction gather."""
    import jax.numpy as jnp

    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            OrderingConfig, paper_filters_4)
    from repro.data.stream import gen_batch

    ordering = OrderingConfig(collect_rate=100, calculate_rate=5000)
    filt = AdaptiveFilter(paper_filters_4("fig1"),
                          AdaptiveFilterConfig(backend=backend,
                                               compact_output=True,
                                               ordering=ordering))
    state = filt.init_state()
    cols = jnp.asarray(gen_batch(0, 0, 0, 4096))
    _, packed, n_kept, mask, _ = filt._jit_compact(state, cols)
    _, mask_ref, _ = filt.jit_step(state, cols)

    assert np.array_equal(np.asarray(mask), np.asarray(mask_ref))
    n = int(n_kept)
    host_path = np.asarray(cols)[:, np.asarray(mask_ref)]
    assert np.array_equal(np.asarray(packed)[:, :n], host_path)
    assert np.all(np.asarray(packed)[:, n:] == 0.0)     # padding is fill


def test_compact_capacity_saturates():
    from repro.core import AdaptiveFilter, AdaptiveFilterConfig, \
        paper_filters_4
    from repro.data.stream import gen_batch
    import jax.numpy as jnp

    filt = AdaptiveFilter(paper_filters_4("fig1"),
                          AdaptiveFilterConfig(compact_output=True,
                                               compact_capacity=8))
    _, packed, n_kept, mask, _ = filt._jit_compact(
        filt.init_state(), jnp.asarray(gen_batch(0, 0, 0, 4096)))
    assert packed.shape[1] == 8
    assert int(n_kept) == 8                     # > 8 survivors → saturates
    first8 = np.asarray(gen_batch(0, 0, 0, 4096))[:, np.asarray(mask)][:, :8]
    assert np.array_equal(np.asarray(packed), first8)


def test_compact_output_flag_validation():
    """The flag is wired: host engines reject it, capacity needs the flag."""
    from repro.core import AdaptiveFilterConfig

    with pytest.raises(ValueError, match="compact_output"):
        AdaptiveFilterConfig(backend="numpy", compact_output=True,
                             cost_mode="measured")
    with pytest.raises(ValueError, match="compact_capacity"):
        AdaptiveFilterConfig(compact_capacity=16)
    with pytest.raises(ValueError, match="compact_capacity"):
        AdaptiveFilterConfig(compact_output=True, compact_capacity=0)


def test_per_batch_scope_preserves_sample_phase_and_epoch():
    """PER_BATCH resets *evidence* per batch, not the monitor stride or the
    re-rank counter: sample_phase must walk through the stream (same offsets
    as any other scope) and epoch must accumulate across batches."""
    import jax.numpy as jnp

    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            OrderingConfig, paper_filters_4)
    from repro.data.stream import gen_batch

    n_rows, collect = 256, 100
    mk = lambda scope: AdaptiveFilter(paper_filters_4("fig1"),
                                      AdaptiveFilterConfig(
        scope=scope,
        ordering=OrderingConfig(collect_rate=collect, calculate_rate=200)))
    pb, ps = mk("per_batch"), mk("per_shard")
    pb_state, ps_state = pb.init_state(), ps.init_state()
    for b in range(4):
        cols = jnp.asarray(gen_batch(0, b, b * n_rows, n_rows))
        pb_state, _, pb_met = pb.jit_step(pb_state, cols)
        ps_state, _, _ = ps.jit_step(ps_state, cols)
        # stride position identical across scopes — the global row offset
        assert int(pb_state.sample_phase) == int(ps_state.sample_phase) \
            == ((b + 1) * n_rows) % collect
    assert int(pb_met.epoch) == 4               # one re-rank per 256-row batch


def test_sharded_rejects_host_backend():
    from repro.core import AdaptiveFilterConfig

    with pytest.raises(ValueError, match="host engine"):
        _one_device_filter(AdaptiveFilterConfig(backend="numpy",
                                                cost_mode="measured"))


# ============================================================ slow, 4 devices
_HETERO_PRELUDE = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (AdaptiveFilterConfig, OrderingConfig,
                            ShardedAdaptiveFilter)
    from repro.core.predicates import OP_GT, Predicate

    preds = [Predicate(f"c{i}", i, OP_GT, 0.5, static_cost=1.0)
             for i in range(3)]
    R = 4096
    ordering = OrderingConfig(collect_rate=10, calculate_rate=2000)

    def shard_cols(shard):
        # heterogeneous per-shard drift: shard i's column (i % 3) cuts
        # everything, the others pass everything — each shard has a
        # different optimal front-runner
        cols = np.full((3, R), 1.0, np.float32)
        cols[shard % 3] = 0.0
        return cols

    cols = jnp.asarray(np.concatenate([shard_cols(s) for s in range(4)],
                                      axis=1))

    def run(scope, steps=3):
        sf = ShardedAdaptiveFilter(preds, AdaptiveFilterConfig(
            scope=scope, ordering=ordering))
        st = sf.init_state()
        for _ in range(steps):
            st, mask, met = sf.jit_step(st, cols)
        return sf, st, np.asarray(met.perm), np.asarray(met.epoch)
""")


@pytest.mark.slow
def test_per_shard_diverges_centralized_converges():
    """Paper §2.2 executed: under heterogeneous per-shard drift the
    PER_SHARD states adapt to their own slice (divergent perms, each led by
    its shard's best cutter) while CENTRALIZED psum-merges the epoch stats
    so every shard adopts one identical global order."""
    out = run_py(_HETERO_PRELUDE + textwrap.dedent("""
        sf, st, perms, epochs = run("per_shard")
        assert (epochs > 0).all(), epochs
        # every shard leads with its own cutter...
        for s in range(4):
            assert perms[s][0] == s % 3, (s, perms[s])
        # ...and shards with different cutters genuinely diverge
        assert len({tuple(p) for p in perms}) == 3, perms

        sf, st, perms, epochs = run("centralized")
        assert (epochs > 0).all(), epochs
        assert len({tuple(p) for p in perms}) == 1, perms
        print("SCOPES-OK")
    """))
    assert "SCOPES-OK" in out


@pytest.mark.slow
def test_per_shard_hlo_has_no_collectives():
    """PER_SHARD ⇒ zero network traffic, machine-checked on the compiled
    HLO; CENTRALIZED must show the stat all-reduce. Pinned through the
    shared auditor (``repro.analysis.hlo_audit``): the plan's scope tells
    the auditor whether collectives must be absent or present, so this
    test and the CI ``analysis`` job enforce the identical contract."""
    out = run_py(textwrap.dedent("""
        from repro.analysis import audit_plan, audit_step_text, errors
        from repro.core import (FilterPlan, OrderingConfig, build_session,
                                paper_filters_4)

        ordering = OrderingConfig(collect_rate=10, calculate_rate=2000)
        for scope in ("per_shard", "per_batch", "centralized"):
            plan = FilterPlan(predicates=paper_filters_4("fig1"),
                              scope=scope, shards=4, ordering=ordering)
            diags = audit_plan(plan)
            assert not errors(diags), (scope, [d.render() for d in diags])
        # cross-audit proves the checks bite: the eager CENTRALIZED step
        # (which legitimately carries the all-reduce) must FAIL the
        # PER_SHARD collective-free contract
        import jax.numpy as jnp
        import numpy as np
        cent = FilterPlan(predicates=paper_filters_4("fig1"),
                          scope="centralized", shards=4, ordering=ordering)
        session = build_session(cent)
        cols = jnp.asarray(np.random.default_rng(0).uniform(
            -64, 64, (4, 4096 * 4)).astype(np.float32))
        txt = session.compiled_step_texts(session.init_state(), cols)["step"]
        per_shard = FilterPlan(predicates=paper_filters_4("fig1"),
                               scope="per_shard", shards=4,
                               ordering=ordering)
        found = audit_step_text(txt, per_shard, num_shards=4)
        assert [d.code for d in found] == ["hlo-step-collective"], found
        print("HLO-OK")
    """))
    assert "HLO-OK" in out


@pytest.mark.slow
def test_sharded_compaction_and_pipeline_roundtrip_4dev():
    """4-shard ingestion: compacted survivors == mask-path survivors, and
    the sharded checkpoint restores to a bit-identical batch stream."""
    out = run_py("""
        import jax, numpy as np
        from repro.core import (AdaptiveFilterConfig, OrderingConfig,
                                ShardedAdaptiveFilter, paper_filters_4)
        from repro.core.session import FilterSession
        from repro.data.pipeline import make_pipeline
        from repro.data.stream import DriftConfig

        ordering = OrderingConfig(collect_rate=100, calculate_rate=50_000)
        drift = DriftConfig(kind="regime", period_rows=300_000)
        mesh = jax.make_mesh((4,), ("data",))

        def mk(compact):
            cfg = AdaptiveFilterConfig(scope="centralized", ordering=ordering,
                                       compact_output=compact)
            filt = ShardedAdaptiveFilter(paper_filters_4("fig1"), cfg,
                                         mesh=mesh)
            return make_pipeline(
                FilterSession.from_filter(filt), total_rows=1_048_576,
                batch_rows=65536, batch_size=4, seq_len=64, vocab_size=1000,
                drift=drift)

        pipe = mk(compact=True)
        it = iter(pipe)
        head = [next(it) for _ in range(3)]
        ckpt = pipe.state()
        tail = [next(it) for _ in range(3)]

        # compacted path == boolean-mask path, bit-identical LM batches
        plain = [b for _, b in zip(range(3), iter(mk(compact=False)))]
        for a, b in zip(head, plain):
            assert np.array_equal(a["tokens"], b["tokens"])

        # checkpoint round-trip: fresh pipeline resumes bit-identically
        pipe2 = mk(compact=True)
        pipe2.restore(ckpt)
        got = [b for _, b in zip(range(3), iter(pipe2))]
        for a, b in zip(tail, got):
            assert np.array_equal(a["tokens"], b["tokens"])
            assert np.array_equal(a["labels"], b["labels"])
        print("PIPE-OK")
    """)
    assert "PIPE-OK" in out
