"""Additional coverage: chunked CE oracle, serve driver, dry-run artifact
schema, compression math, snap-on-flip behavior."""

import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_cross_entropy_chunked_matches_plain():
    from repro.models.common import (cross_entropy, cross_entropy_chunked,
                                     unembed)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 64, 16), jnp.float32)
    table = jax.random.normal(jax.random.PRNGKey(1), (40, 16), jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 40)
    plain = cross_entropy(unembed(x, table, True), labels, final_cap=30.0)
    chunked = cross_entropy_chunked(x, table, True, labels, final_cap=30.0,
                                    chunk=16)
    np.testing.assert_allclose(float(plain), float(chunked), rtol=1e-5)
    # grads agree too (the checkpointed path rematerializes logits)
    g1 = jax.grad(lambda h: cross_entropy(
        unembed(h, table, True), labels))(x)
    g2 = jax.grad(lambda h: cross_entropy_chunked(
        h, table, True, labels, chunk=16))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=2e-4,
                               atol=1e-6)


def test_snap_on_flip_reorders_faster_than_momentum():
    """With heavy momentum and an adversarial flip, snap must adopt the
    fresh order in ONE epoch while the paper controller lags."""
    from repro.core import (AdaptiveFilter, AdaptiveFilterConfig,
                            OrderingConfig)
    from repro.core.predicates import OP_GT, Predicate

    preds = [Predicate("a", 0, OP_GT, 0.5, static_cost=1.0),
             Predicate("b", 1, OP_GT, 0.5, static_cost=1.0)]

    def run(snap):
        cfg = AdaptiveFilterConfig(ordering=OrderingConfig(
            collect_rate=10, calculate_rate=4000, momentum=0.9,
            snap_threshold=snap))
        filt = AdaptiveFilter(preds, cfg)
        state = filt.init_state()
        step = jax.jit(filt.step)
        r = np.random.default_rng(0)
        # phase 1: predicate 1 cuts everything → order (1, 0)
        for _ in range(3):
            cols = np.stack([r.uniform(0.4, 1.0, 4096),
                             r.uniform(0.0, 0.45, 4096)]).astype(np.float32)
            state, _, _ = step(state, jnp.asarray(cols))
        assert np.asarray(state.perm).tolist() == [1, 0]
        # phase 2 (flip): predicate 0 cuts everything — ONE epoch of data
        for _ in range(1):
            cols = np.stack([r.uniform(0.0, 0.45, 4096),
                             r.uniform(0.4, 1.0, 4096)]).astype(np.float32)
            state, _, _ = step(state, jnp.asarray(cols))
        return np.asarray(state.perm).tolist()

    assert run(snap=0.0) == [1, 0], "momentum 0.9 should still lag"
    assert run(snap=1.3) == [0, 1], "snap should adopt the fresh order"


def test_dryrun_artifacts_schema():
    art = pathlib.Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"
    if not art.exists():
        pytest.skip("no dry-run artifacts in this checkout")
    files = list(art.glob("*.json"))
    assert len(files) >= 80, "expected both baseline and opt passes"
    for p in files:
        r = json.loads(p.read_text())
        assert r["status"] in ("ok", "skip", "error")
        assert r["status"] != "error", f"{p.name}: {r.get('error')}"
        if r["status"] == "ok":
            ro = r["roofline"]
            for k in ("t_compute_s", "t_memory_s", "t_collective_s"):
                assert ro[k] >= 0
            assert ro["dominant"] in ("compute", "memory", "collective")
            assert r["memory"]["total_bytes"] > 0
            assert r["loop_aware"]["unknown_trip_loops"] == 0


def test_serve_driver_end_to_end(tmp_path):
    env = {"PYTHONPATH": str(pathlib.Path(__file__).resolve().parents[1] / "src")}
    import os
    env.update({k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen2.5-14b",
         "--smoke", "--requests", "8", "--batch", "4", "--prompt-len", "16",
         "--new-tokens", "2", "--bench-out", str(tmp_path / "serve.json")],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "admitted=" in out.stdout


def test_int8_compression_roundtrip_error_bounded():
    from repro.parallel.compression import int8_compress, int8_decompress
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 0.1, (64, 64)),
                          jnp.float32)}
    q, s = int8_compress(g)
    back = int8_decompress(q, s)
    err = float(jnp.max(jnp.abs(back["w"] - g["w"])))
    assert err <= float(s["w"]) * 0.51 + 1e-9   # half-ULP of the int8 grid


def test_topk_error_feedback_conserves_mass():
    from repro.parallel.compression import init_error_feedback, topk_compress
    g = {"w": jnp.arange(100, dtype=jnp.float32).reshape(10, 10)}
    res = init_error_feedback(g)
    sent, res = topk_compress(g, res, fraction=0.05)
    total = sent["w"] + res["w"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(g["w"]))
    assert int(jnp.sum(sent["w"] != 0)) == 5


@pytest.mark.parametrize("arch", ["gemma2-9b", "deepseek-v3-671b",
                                  "rwkv6-3b", "zamba2-2.7b", "qwen2.5-14b",
                                  "chatglm3-6b", "dbrx-132b"])
def test_prefill_decode_matches_full_forward(arch):
    """The strongest serving-correctness check: prefill T-1 tokens, decode
    token T-1 against the cache, and compare the next-token logits with the
    full-sequence forward pass. Validates the absorbed-MLA decode math,
    sliding-window decode masks, GQA cache updates, and SSM/hybrid state
    handoff numerically (bf16 path, tolerance from summation-order only)."""
    from repro.configs import get_smoke_config
    from repro.launch.serve import _grow_cache
    from repro.models import transformer as tfm
    from repro.models.registry import batch_for, build_model

    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    t = 24
    batch = batch_for(cfg, 2, t, kind="prefill")
    batch.pop("labels", None)
    toks = batch["tokens"]

    x, _, _ = tfm.forward(params, cfg, batch, mode="train", remat=False)
    logits_full = np.asarray(
        tfm.logits_from_hidden(params, cfg, x[:, -1]).astype(jnp.float32))
    from repro.models.common import softcap
    logits_full = np.asarray(softcap(jnp.asarray(logits_full),
                                     cfg.final_softcap))

    pf = {k: (v[:, :t - 1] if k == "tokens" else
              (v[..., :t - 1] if k == "positions" else v))
          for k, v in batch.items()}
    _, cache = model.prefill(params, pf)
    cache = _grow_cache(model, cache, 2, t)
    logits_dec, _ = model.decode_step(params, toks[:, t - 1:t], cache,
                                      jnp.asarray(t - 1))
    logits_dec = np.asarray(logits_dec.astype(jnp.float32))

    # The dense MoE dispatch is dropless, so each token's MoE output is a
    # pure function of the token — MoE archs match at the same bf16
    # summation-order noise level as the dense/SSM paths.
    tol = 0.15
    assert np.max(np.abs(logits_full - logits_dec)) < tol, arch
    np.testing.assert_array_equal(np.argmax(logits_full, -1),
                                  np.argmax(logits_dec, -1))
