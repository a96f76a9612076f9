"""Multi-device behaviour, each case in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count so the main pytest process
keeps seeing exactly 1 device (contract §MULTI-POD 0)."""

import os
import subprocess
import sys
import textwrap

import pytest

# each case forks a fresh interpreter (jax re-import + multi-device init):
# minutes, not seconds — excluded from the fast tier via -m "not slow"
pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


def test_per_shard_scope_has_no_collectives():
    """Paper §2.2: per-executor scope ⇒ no network traffic. The lowered HLO
    of the sharded filter step must contain NO collective ops; the
    centralized scope must contain an all-reduce."""
    out = run_py("""
        import jax, jax.numpy as jnp
        import numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import paper_filters_4, pack
        from repro.core.filter_exec import run_chain
        from repro.core.scope import Scope, reduce_stats
        from repro.core.stats import FilterStats

        mesh = jax.make_mesh((4,), ("data",))
        specs = pack(paper_filters_4("fig1"))

        def step(cols, scope):
            res = run_chain(cols, specs, jnp.arange(4, dtype=jnp.int32),
                            collect_rate=100, sample_phase=0)
            st = FilterStats(res.cut_counts, res.monitor_cost,
                             res.n_monitored)
            st = reduce_stats(st, scope, ("data",))
            if scope is Scope.CENTRALIZED:     # identical on every shard
                return st.num_cut, st.cost_acc, st.n_monitored
            # per-shard: stack local stats on a leading device axis
            return st.num_cut[None], st.cost_acc[None], st.n_monitored[None]

        cols = jnp.zeros((3, 4096), jnp.float32)
        for scope, want_collective in ((Scope.PER_SHARD, False),
                                       (Scope.CENTRALIZED, True)):
            outs = (P(), P(), P()) if scope is Scope.CENTRALIZED \\
                else (P("data"), P("data"), P("data"))
            f = jax.jit(shard_map(partial(step, scope=scope), mesh=mesh,
                        in_specs=P(None, "data"), out_specs=outs))
            txt = f.lower(cols).compile().as_text()
            has = any(k in txt for k in
                      ("all-reduce", "all-gather", "reduce-scatter"))
            assert has == want_collective, (scope, has)
        print("SCOPE-OK")
    """)
    assert "SCOPE-OK" in out


def test_sharded_filter_matches_single_device():
    """Filter outcome and monitor stats are identical whether the batch is
    processed on 1 device or sharded 4 ways (per-shard states merged)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import paper_filters_4, pack
        from repro.core.filter_exec import run_chain
        from repro.data.stream import gen_batch

        specs = pack(paper_filters_4("fig1"))
        cols = jnp.asarray(gen_batch(0, 0, 0, 64_000))
        perm = jnp.asarray([2, 0, 3, 1], jnp.int32)

        res1 = run_chain(cols, specs, perm, collect_rate=1000, sample_phase=0)

        mesh = jax.make_mesh((4,), ("data",))
        def shard_step(c):
            # per-shard phase: shard i starts at row i*16000
            phase = (jax.lax.axis_index("data") * 16000) % 1000
            r = run_chain(c, specs, perm, collect_rate=1000,
                          sample_phase=phase)
            return r.mask, r.cut_counts[None], r.n_monitored[None]
        f = jax.jit(shard_map(shard_step, mesh=mesh,
                    in_specs=P(None, "data"),
                    out_specs=(P("data"), P("data"), P("data"))))
        mask4, cut4, nmon4 = f(cols)
        # psum-free: per-shard partials concatenate; host merges stats
        assert np.array_equal(np.asarray(mask4), np.asarray(res1.mask))
        np.testing.assert_allclose(np.asarray(cut4).sum(0),
                                   np.asarray(res1.cut_counts))
        assert float(np.asarray(nmon4).sum()) == float(res1.n_monitored)
        print("SHARD-OK")
    """)
    assert "SHARD-OK" in out


def test_pipeline_parallel_matches_reference():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.parallel.pipeline import pipeline_apply

        mesh = make_test_mesh((4,), ("stage",))
        n_stages, m, mb, d = 4, 8, 4, 16
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (n_stages, d, d), jnp.float32) * 0.3
        xs = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d), jnp.float32)

        def block(wi, x):
            return jnp.tanh(x @ wi["w"])

        got = pipeline_apply(block, {"w": w}, xs, mesh=mesh)
        ref = xs
        for s in range(n_stages):
            ref = jnp.tanh(ref @ w[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("PP-OK")
    """)
    assert "PP-OK" in out


def test_compressed_psum_grad_allreduce():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.parallel.compression import (compressed_psum,
            init_error_feedback, int8_decompress)

        mesh = jax.make_mesh((4,), ("data",))
        g = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 64.0}

        def red(gi, scheme):
            out, _ = compressed_psum(gi, "data", scheme=scheme,
                                     residual=jax.tree.map(jnp.zeros_like, gi))
            return out
        for scheme, tol in (("none", 1e-6), ("int8", 0.05), ("topk", None)):
            f = jax.jit(shard_map(partial(red, scheme=scheme), mesh=mesh,
                        in_specs=P(), out_specs=P()))
            got = f(g)["w"]
            want = g["w"] * 4
            if scheme == "topk":
                # top-1% kept: reduced result must be a masked subset
                nz = np.asarray(got != 0)
                assert nz.sum() >= 1 and nz.sum() <= 8
                np.testing.assert_allclose(np.asarray(got)[nz],
                                           np.asarray(want)[nz], rtol=1e-5)
            else:
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           rtol=tol, atol=tol)
        print("COMP-OK")
    """)
    assert "COMP-OK" in out


def test_mini_dryrun_train_and_decode():
    """A scaled-down dry-run: reduced config, 2x2 mesh, lower+compile train
    AND decode with the production sharding rules — the same code path
    launch/dryrun.py uses for the 16x16 and 2x16x16 meshes."""
    out = run_py("""
        import jax
        from repro.configs import get_smoke_config, SHAPES
        from repro.configs.base import ShapeCell
        from repro.launch.mesh import make_test_mesh
        from repro.launch.specs import make_cell
        from repro.launch.steps import (make_decode_step, make_train_step)

        mesh = make_test_mesh((2, 2), ("data", "model"))
        cfg = get_smoke_config("dbrx-132b")
        cell_train = ShapeCell("t", 64, 4, "train")
        cell_dec = ShapeCell("d", 64, 4, "decode")
        import repro.launch.specs as specs_mod
        specs_mod.SHAPES = dict(SHAPES, t=cell_train, d=cell_dec)

        kind, args, model, cfg2, opt_cfg = specs_mod.make_cell(
            "dbrx-132b", "t", mesh, cfg=cfg)
        with mesh:
            c = jax.jit(make_train_step(model, opt_cfg),
                        donate_argnums=(0, 1)).lower(*args).compile()
            assert c.memory_analysis() is not None

        kind, args, model, cfg2, opt_cfg = specs_mod.make_cell(
            "dbrx-132b", "d", mesh, cfg=cfg)
        with mesh:
            c = jax.jit(make_decode_step(model),
                        donate_argnums=(2,)).lower(*args).compile()
            assert c.cost_analysis() is not None
        print("DRYRUN-OK")
    """)
    assert "DRYRUN-OK" in out


def test_elastic_reshard_2_to_4_devices():
    """Checkpoint written under a 2-device mesh restores onto a 4-device
    mesh (elastic rescale)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import save_checkpoint, load_checkpoint
        from repro.launch.mesh import make_test_mesh

        tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
        m2 = make_test_mesh((2,), ("data",))
        sh2 = {"w": NamedSharding(m2, P("data", None))}
        t2 = jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sh2)
        d = tempfile.mkdtemp()
        save_checkpoint(d, 3, t2)

        m4 = make_test_mesh((4,), ("data",))
        sh4 = {"w": NamedSharding(m4, P("data", None))}
        got, _, step = load_checkpoint(d, tree, shardings=sh4)
        assert step == 3
        assert len(got["w"].sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(tree["w"]))
        print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in out
