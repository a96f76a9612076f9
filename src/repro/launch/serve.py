"""Serving CLI: the continuous-batching admission server, thin.

All mechanism lives in ``repro.serving`` — this launcher only builds the
pieces (drifting-mix traffic → ``RequestStream``, guardrail plan →
session, slot executor) and wires them into ``AdmissionServer``, then
writes ``BENCH_serve.json`` and applies the CI smoke gates:

  * PARITY (correctness, hard): the queued server's admit/reject
    sequence and final ``OrderState`` must be bit-identical to a
    synchronous reference run over the same seeded traffic — queuing
    changes latency, never admission decisions.
  * requests/sec and p99 admission latency (perf, sim executor only):
    conservative absolute floors that catch gross stalls (per-batch
    recompiles, a blocked queue) without flaking on slow CI runners.

  PYTHONPATH=src python -m repro.launch.serve --smoke
  PYTHONPATH=src python -m repro.launch.serve --executor model \
      --arch gemma2-9b --smoke --requests 64 --new-tokens 16
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import FilterPlan, OrderingConfig, build_session
from repro.data.stream import RequestStream
from repro.runtime import GracefulShutdown, GuardedSession, GuardPolicy
from repro.serving import (AdmissionServer, ServerConfig, SimExecutor,
                           TrafficConfig, TrafficGenerator, guardrail_chain,
                           phase_of, synchronous_reference)

__all__ = ["guardrail_chain", "ModelSlotExecutor", "main"]


class ModelSlotExecutor:
    """Real prefill/decode in the slots: each admitted request prefills
    a batch-1 prompt into its freed slot and decodes one token per
    server tick until ``new_tokens`` are out — continuous batching at
    slot granularity (per-slot caches stay independent; packing the
    per-tick decodes into one batched call is the ROADMAP follow-up)."""

    def __init__(self, arch: str, smoke: bool, prompt_len: int,
                 new_tokens: int):
        import jax

        from repro.configs import get_config, get_smoke_config
        from repro.models.registry import batch_for, build_model

        self.cfg = get_smoke_config(arch) if smoke else get_config(arch)
        self.model = build_model(self.cfg)
        self.params = self.model.init(jax.random.PRNGKey(0))
        self._jit_prefill = jax.jit(self.model.prefill)
        self._jit_decode = jax.jit(self.model.decode_step, donate_argnums=(2,))
        self._batch_for = batch_for
        self.prompt_len = prompt_len
        self.new_tokens = new_tokens

    def prefill(self, ticket):
        import jax.numpy as jnp

        batch = self._batch_for(self.cfg, 1, self.prompt_len, kind="prefill")
        batch.pop("labels", None)
        logits, cache = self._jit_prefill(self.params, batch)
        cache = _grow_cache(self.model, cache, 1,
                            self.prompt_len + self.new_tokens)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        return {"cache": cache, "tok": tok, "t": 0}

    def advance(self, ctx):
        import jax.numpy as jnp

        if self.cfg.embeds_input:
            step_in = jnp.zeros((1, 1, self.cfg.d_model), jnp.bfloat16)
        else:
            step_in = ctx["tok"]
        logits, cache = self._jit_decode(self.params, step_in, ctx["cache"],
                                         jnp.asarray(self.prompt_len
                                                     + ctx["t"]))
        ctx = {"cache": cache,
               "tok": jnp.argmax(logits, -1)[:, None].astype(jnp.int32),
               "t": ctx["t"] + 1}
        return ctx, ctx["t"] >= self.new_tokens


def _grow_cache(model, cache, batch, capacity):
    """Pad prefill-sized cache buffers out to decode capacity."""
    import jax
    import jax.numpy as jnp

    fresh = model.init_cache(batch, capacity)

    def fit(old, new):
        if old.shape == new.shape:
            return old
        pads = [(0, n - o) for o, n in zip(old.shape, new.shape)]
        return jnp.pad(old, pads)

    return jax.tree.map(fit, cache, fresh)


def _parity(report, ref_masks, ref_blob) -> dict:
    """Bit-compare the server run against the synchronous oracle."""
    masks_equal = set(report.masks) == set(ref_masks) and all(
        np.array_equal(report.masks[b], ref_masks[b]) for b in ref_masks)
    a, b = report.state_blob["arrays"], ref_blob["arrays"]
    state_equal = set(a) == set(b) and all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    return {"checked": True, "masks_equal": bool(masks_equal),
            "state_equal": bool(state_equal),
            "ok": bool(masks_equal and state_equal)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small 3-phase run + parity/perf gates + "
                         "BENCH_serve.json (the CI bench-serve job)")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests (default 4096; 1536 under --smoke)")
    ap.add_argument("--batch", type=int, default=64,
                    help="admission micro-batch rows")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--queue-depth", type=int, default=8)
    ap.add_argument("--max-backlog", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--users", type=int, default=1 << 20,
                    help="persistent synthetic user identities")
    ap.add_argument("--phase-requests", type=int, default=None,
                    help="rows per traffic phase (default requests//3: the "
                         "run sweeps organic → abuse storm → enterprise)")
    ap.add_argument("--guarded", action="store_true",
                    help="wrap the gate in the self-healing GuardedSession "
                         "(always on under --smoke so BENCH_serve.json "
                         "carries real GuardHealth counters)")
    ap.add_argument("--promote-after", type=int, default=4,
                    help="clean validated boundaries before a degraded "
                         "rung re-promotes (guarded runs)")
    ap.add_argument("--executor", choices=("sim", "model"), default="sim")
    ap.add_argument("--arch", default="gemma2-9b",
                    help="model arch for --executor model")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--bench-out", default="BENCH_serve.json")
    ap.add_argument("--state-out", default="/tmp/repro_serve_state.json",
                    help="where a graceful SIGINT/SIGTERM flushes the "
                         "guardrail OrderState (versioned session blob)")
    ap.add_argument("--no-reference", action="store_true",
                    help="skip the synchronous parity reference run")
    ap.add_argument("--gate-rps", type=float, default=100.0,
                    help="smoke gate: minimum sustained requests/sec")
    ap.add_argument("--gate-p99-ms", type=float, default=2500.0,
                    help="smoke gate: maximum p99 admission latency")
    args = ap.parse_args(argv)
    enable_compile_cache()

    requests = args.requests if args.requests is not None \
        else (1536 if args.smoke else 4096)
    requests = (requests // args.batch) * args.batch or args.batch
    phase_requests = args.phase_requests or max(requests // 3, args.batch)
    guarded = args.guarded or args.smoke

    tcfg = TrafficConfig(seed=args.seed, n_users=args.users,
                         phase_requests=phase_requests)
    traffic = TrafficGenerator(tcfg)
    n_batches = requests // args.batch
    phases_seen = sorted({phase_of(tcfg, b * args.batch + args.batch / 2)
                          for b in range(n_batches)})

    # the guardrail stage is ONE declarative plan: compile it to a session
    # and drive the single step entry point (same API the data pipelines
    # use, so serve/train metrics agree field-for-field)
    plan = FilterPlan(
        predicates=guardrail_chain(),
        ordering=OrderingConfig(collect_rate=4, calculate_rate=64,
                                momentum=0.3))
    session = build_session(plan)
    if guarded:
        session = GuardedSession(
            session, GuardPolicy(promote_after=args.promote_after))

    if args.executor == "model":
        executor = ModelSlotExecutor(args.arch, args.smoke, args.prompt_len,
                                     args.new_tokens)
    else:
        executor = SimExecutor(max_decode_steps=args.new_tokens)

    server = AdmissionServer(
        session,
        RequestStream(traffic.gen, total_rows=requests,
                      batch_rows=args.batch),
        ServerConfig(num_slots=args.slots, queue_depth=args.queue_depth,
                     max_backlog=args.max_backlog),
        executor=executor,
        warmup_batch=traffic.gen(0, 0, args.batch))

    stop = GracefulShutdown()
    t0 = time.time()
    with stop:
        report = server.run(stop=stop)
    dt = time.time() - t0

    if stop.requested:
        # graceful shutdown: the server drained in-flight slots and
        # flushed the final checkpoint into the report — persist it
        blob = report.state_blob
        payload = {k: (v.tolist() if isinstance(v, np.ndarray) else v)
                   for k, v in blob.items() if k != "arrays"}
        payload["arrays"] = {k: np.asarray(v).tolist()
                             for k, v in blob["arrays"].items()}
        payload["dtypes"] = {k: str(np.asarray(v).dtype)
                             for k, v in blob["arrays"].items()}
        with open(args.state_out, "w") as f:
            json.dump(payload, f)
        print(f"[serve] shutdown requested: drained {len(report.results)} "
              f"results; guardrail state flushed to {args.state_out}")
        print("[serve] resume: restores via FilterSession.restore_state")

    parity = {"checked": False, "ok": None}
    if not args.no_reference and not stop.requested:
        ref_session = build_session(plan)
        ref_state, ref_masks = synchronous_reference(
            ref_session,
            RequestStream(traffic.gen, total_rows=requests,
                          batch_rows=args.batch))
        parity = _parity(report, ref_masks, ref_session.save_state(ref_state))

    m = report.metrics
    payload = {
        **m,
        "parity": parity,
        "config": {
            "requests": requests, "batch": args.batch, "slots": args.slots,
            "queue_depth": args.queue_depth, "seed": args.seed,
            "n_users": args.users, "phase_requests": phase_requests,
            "phases_seen": phases_seen, "guarded": guarded,
            "executor": args.executor, "smoke": args.smoke,
        },
    }
    with open(args.bench_out, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)

    lat = m["admission_latency_ms"]
    health = f" guard[{report.health_line}]" if report.health_line else ""
    print(f"[serve] {m['decided']}/{m['requests']} decided "
          f"(admitted={m['admit_rate']:.2f} rejected={m['reject_rate']:.2f} "
          f"quarantined={m['quarantine_rate']:.2f}) "
          f"{m['requests_per_sec']:.0f} req/s "
          f"p99 admission {lat['p99']:.1f}ms "
          f"occupancy {m['slot_occupancy']:.2f} "
          f"phases {phases_seen} ({dt:.1f}s){health}")
    print(f"[serve] wrote {args.bench_out}")

    failures = []
    if parity["checked"] and not parity["ok"]:
        failures.append(f"PARITY: queued admission diverged from the "
                        f"synchronous reference ({parity})")
    if args.smoke and args.executor == "sim" and not stop.requested:
        if m["requests_per_sec"] < args.gate_rps:
            failures.append(f"requests/sec {m['requests_per_sec']:.0f} "
                            f"< floor {args.gate_rps:.0f}")
        if lat["p99"] > args.gate_p99_ms:
            failures.append(f"p99 admission {lat['p99']:.1f}ms "
                            f"> ceiling {args.gate_p99_ms:.0f}ms")
    for msg in failures:
        print(f"[serve] GATE FAILED: {msg}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
