"""End-to-end training driver (deliverable b): adaptive-filter data pipeline
→ LM train loop with checkpoint/restart.

CPU-scale example (the ~100M-class config):
  PYTHONPATH=src python -m repro.launch.train \
      --arch qwen2.5-14b --smoke --steps 200 --batch 8 --seq 256

``--smoke`` swaps in the reduced same-family config so the run fits a
laptop; on real hardware drop it and point --ckpt-dir at durable storage.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.configs.paper_filters import DEFAULT as PAPER
from repro.core import (FilterPlan, OrderingConfig, TokenizeSpec,
                        build_session, paper_filters_4, paper_filters_cnf)
from repro.data.pipeline import Pipeline
from repro.data.stream import DriftConfig, LogStream
from repro.launch.steps import make_train_step
from repro.models.registry import build_model
from repro.optim import AdamWConfig, init_opt_state
from repro.runtime import (FailureInjector, GracefulShutdown, GuardedSession,
                           TrainDriver)


def parse_capacity(text: str | None) -> int | str | None:
    """``--compact-capacity`` value: int, "auto", or None (batch width)."""
    if text is None:
        return None
    if text == "auto":
        return "auto"
    return int(text)


def build_pipeline(cfg, *, batch: int, seq: int, total_rows: int,
                   ordering: OrderingConfig, drift: DriftConfig,
                   shard_id: int = 0, num_shards: int = 1,
                   chain: str = "flat", filter_shards: int = 1,
                   filter_scope: str = "per_shard",
                   compact_output: bool = False,
                   compact_capacity: int | str | None = None,
                   exchange: str = "eager",
                   device_tokenize: bool = False,
                   guarded: bool = False):
    """One ingestion pipeline, declared as ONE ``FilterPlan``.

    Every CLI knob maps to a plan field (engine × scope × shards ×
    compaction × exchange × tokenize — the whole matrix is validated once,
    in the plan); ``build_session`` compiles it and the pipeline drives
    ``session.step``. ``filter_shards > 1`` needs that many visible devices
    — on a CPU host force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    if filter_shards > 1 and filter_shards > jax.device_count():
        raise SystemExit(
            f"--filter-shards {filter_shards} > visible devices "
            f"({jax.device_count()}); set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={filter_shards} "
            "or run on a bigger mesh")
    preds = (paper_filters_cnf if chain == "cnf" else paper_filters_4)("fig1")
    plan = FilterPlan(
        predicates=preds, ordering=ordering, scope=filter_scope,
        shards=filter_shards, compact=compact_output,
        capacity=compact_capacity, exchange=exchange,
        tokenize=TokenizeSpec(cfg.vocab) if device_tokenize else None)
    session = build_session(plan)
    if guarded:
        # the self-healing wrapper: quarantine poisoned batches, validate
        # state at boundaries, retry/degrade/roll back on failures — the
        # pipeline drives it through the identical step API
        session = GuardedSession(session)
    if filter_shards > 1:
        from repro.data.pipeline import make_pipeline
        return make_pipeline(session, total_rows=total_rows,
                             batch_rows=65536, drift=drift, batch_size=batch,
                             seq_len=seq, vocab_size=cfg.vocab)
    stream = LogStream(total_rows=total_rows, batch_rows=65536,
                       drift=drift, shard_id=shard_id, num_shards=num_shards)
    return Pipeline(stream, session, batch_size=batch, seq_len=seq,
                    vocab_size=cfg.vocab)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="qwen2.5-14b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU scale)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--rows", type=int, default=20_000_000)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--chain", choices=["flat", "cnf"], default="flat",
                    help="filter shape: the paper's conjunction or its "
                         "CNF (AND-of-OR) variant")
    ap.add_argument("--filter-shards", type=int, default=1,
                    help="run the adaptive filter data-parallel over this "
                         "many mesh shards (shard_map; needs that many "
                         "visible devices)")
    ap.add_argument("--filter-scope",
                    choices=["per_batch", "per_shard", "centralized"],
                    default="per_shard",
                    help="lifetime/locality of the adaptive metadata "
                         "(paper §2.2)")
    ap.add_argument("--compact-output", action="store_true",
                    help="device-side survivor compaction (padded gather + "
                         "count instead of a host boolean index)")
    ap.add_argument("--compact-capacity", default=None,
                    help="compaction width: an int, or 'auto' to track the "
                         "monitor lane's pass-rate (slack-padded, "
                         "re-quantized to 128s at epoch boundaries); "
                         "default = batch width (lossless)")
    ap.add_argument("--exchange",
                    choices=["eager", "deferred", "deferred-async"],
                    default="eager",
                    help="CENTRALIZED stat exchange cadence: per-step psum "
                         "(eager), one collective per epoch (deferred), or "
                         "epoch-late folding (deferred-async)")
    ap.add_argument("--device-tokenize", action="store_true",
                    help="tokenize/pack the padded compacted buffers on "
                         "device (needs --compact-output); the host only "
                         "ever sees the dense token stream")
    ap.add_argument("--guarded", action="store_true",
                    help="wrap the filter session in the self-healing "
                         "GuardedSession (quarantine poisoned batches, "
                         "state validation, retry/degrade/rollback)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    print(f"[train] arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M")

    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig()
    opt_state = init_opt_state(params, opt_cfg)
    step_fn = jax.jit(make_train_step(model, opt_cfg, peak_lr=args.lr,
                                      warmup=20, total=args.steps),
                      donate_argnums=(0, 1))

    ordering = OrderingConfig(collect_rate=PAPER.ordering.collect_rate,
                              calculate_rate=500_000,
                              momentum=PAPER.ordering.momentum)
    pipeline = build_pipeline(cfg, batch=args.batch, seq=args.seq,
                              total_rows=args.rows, ordering=ordering,
                              drift=PAPER.drift, chain=args.chain,
                              filter_shards=args.filter_shards,
                              filter_scope=args.filter_scope,
                              compact_output=args.compact_output,
                              compact_capacity=parse_capacity(
                                  args.compact_capacity),
                              exchange=args.exchange,
                              device_tokenize=args.device_tokenize,
                              guarded=args.guarded)

    driver = TrainDriver(step_fn=step_fn, pipeline=pipeline, params=params,
                         opt_state=opt_state, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         injector=FailureInjector())
    if args.resume and driver.try_restore():
        print(f"[train] resumed from step {driver.step}")

    t0 = time.time()
    with GracefulShutdown() as stop:
        done = driver.run(args.steps, stop=stop)
    dt = time.time() - t0
    if stop.requested:
        # the driver already flushed a final checkpoint before returning
        print(f"[train] shutdown requested at step {driver.step}: "
              f"checkpoint flushed to {args.ckpt_dir}")
        print(f"[train] resume: python -m repro.launch.train --resume "
              f"--ckpt-dir {args.ckpt_dir} --arch {args.arch} "
              f"--steps {args.steps}"
              + (" --smoke" if args.smoke else "")
              + (" --guarded" if args.guarded else ""))
    losses = driver.history
    print(f"[train] done={done} steps={driver.step} "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({dt:.1f}s, {driver.step / max(dt, 1e-9):.2f} steps/s)"
          + (f" guard[{pipeline._session.health.summary()}]"
             if args.guarded else ""))
    print(f"[train] pipeline: rows_in={pipeline.rows_in} "
          f"rows_pass={pipeline.rows_pass} "
          f"filter perm={pipeline.last_metrics.get('perm')} "
          f"epochs={pipeline.last_metrics.get('epoch')} "
          f"n_dropped={pipeline.last_metrics.get('n_dropped', 0)}"
          + (f" per_shard={pipeline.last_metrics['n_dropped_per_shard']}"
             if "n_dropped_per_shard" in pipeline.last_metrics else ""))


if __name__ == "__main__":
    main()
