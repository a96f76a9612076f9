#!/usr/bin/env python3
"""The control: the plain reference put in the program's place, computed
in bfloat16, the precision below the float32 the configurations state.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

Each seed is one whole run of the harness (data, warm-up, window, the
comparison), with ``ControlSession`` standing in for the program's
``FilterSession``: it rounds every batch to bfloat16, evaluates the chain
and the paper's ordering with ``bench/reference.py``, and hands back what
the program's step would. The run prints the numbers compared and their
limits; a sound comparison reads the control as not correct. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference as ref  # noqa: E402


class ControlSession:
    """``init_state``/``step`` with the program's result shapes, computed by
    the reference in bfloat16 on the host."""

    def __init__(self, config: dict):
        self.preds = config["predicates"]
        self.order = ref.Ordering(self.preds, config["ordering"])
        plan = config["plan"]
        self.shards = plan.get("shards", 1)
        self.compact = bool(plan.get("compact"))
        self.capacity = plan.get("capacity")

    def init_state(self):
        return None

    def step(self, state, batch):
        import jax.numpy as jnp

        t0 = time.perf_counter()
        cols = ref.as_bfloat16(np.asarray(batch, np.float32))
        n = len(self.preds)
        perm = np.arange(n) if self.order.adj is None \
            else np.argsort(self.order.adj, kind="stable")
        local = cols.shape[1] // self.shards
        cuts, n_mon = np.zeros(n), 0
        start = self.order.sample_start()
        for s in range(self.shards):
            c, m = ref.cut_counts(cols[:, s * local:(s + 1) * local],
                                  self.preds, start, self.order.collect)
            cuts += c
            n_mon += m
        mask = ref.chain_mask(cols, self.preds)
        self.order.fold(cuts, n_mon, local)
        shape = (self.shards,) if self.shards > 1 else ()
        zero = jnp.zeros(shape, jnp.int32)
        metrics = types.SimpleNamespace(
            perm=jnp.asarray(np.broadcast_to(perm, shape + (n,)), jnp.int32),
            epoch=jnp.full(shape, self.order.epoch, jnp.int32),
            n_pass=jnp.asarray(int(mask.sum()), jnp.int32),
            n_tiles_pass=zero, n_tiles_fail=zero,
            n_tiles_ambiguous=zero)
        packed = n_kept = None
        if self.compact:
            cap = self.capacity or cols.shape[1]
            out = np.zeros((cols.shape[0], cap), np.float32)
            kept = cols[:, mask][:, :cap]
            out[:, :kept.shape[1]] = kept
            packed = jnp.asarray(out)
            n_kept = jnp.asarray(kept.shape[1], jnp.int32)
        res = types.SimpleNamespace(
            mask=jnp.asarray(mask), packed=packed, n_kept=n_kept,
            metrics=metrics, gate_s=time.perf_counter() - t0)
        return state, res


def main(argv: list[str] | None = None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        try:
            out = run.run_cell(one, session_factory=ControlSession)
        except run.Refused as e:
            run.log(f"FAIL: {e}")
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "bfloat16 reference",
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
