#!/usr/bin/env python3
"""The control for tree cells (``scan_tree``): the plain reference put in
the program's place, computed in bfloat16, the precision below the
float32 the configurations state.

  python3 bench/control_tree.py --workload <cell> --seconds <s> --seeds <n> ...

As ``bench/control.py``, each seed is one whole run of the harness with
``ControlTreeSession`` standing in for the program's ``FilterSession``: it
rounds every partition to bfloat16, evaluates the tree and its
three-level ordering with ``bench/reference_tree.py``, and hands back what
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

from bench import reference_tree as ref  # noqa: E402


class ControlTreeSession:
    """``init_state``/``step`` with the program's result shapes, computed by
    the tree reference in bfloat16 on the host (one executor)."""

    def __init__(self, config: dict):
        self.preds = config["predicates"]
        self.order = ref.Ordering(self.preds, config["ordering"])
        self.capacity = config["plan"].get("capacity")

    def init_state(self):
        return None

    def step(self, state, batch):
        import jax.numpy as jnp

        t0 = time.perf_counter()
        cols = ref.as_bfloat16(np.asarray(batch, np.float32))
        perm = self.order.order()
        *cuts, n_mon = ref.cut_counts(cols, self.preds,
                                      self.order.sample_start(),
                                      self.order.collect)
        mask = ref.tree_mask(cols, self.preds)
        self.order.fold(cuts, n_mon, cols.shape[1])
        zero = jnp.zeros((), jnp.int32)
        metrics = types.SimpleNamespace(
            perm=jnp.asarray(perm, jnp.int32),
            epoch=jnp.asarray(self.order.epoch, jnp.int32),
            n_pass=jnp.asarray(int(mask.sum()), jnp.int32),
            n_tiles_pass=zero, n_tiles_fail=zero, n_tiles_ambiguous=zero,
            n_atom_tiles=zero)
        cap = self.capacity or cols.shape[1]
        out = np.zeros((cols.shape[0], cap), np.float32)
        kept = cols[:, mask][:, :cap]
        out[:, :kept.shape[1]] = kept
        res = types.SimpleNamespace(
            mask=jnp.asarray(mask), packed=jnp.asarray(out),
            n_kept=jnp.asarray(kept.shape[1], jnp.int32), metrics=metrics,
            gate_s=time.perf_counter() - t0)
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
            out = run.run_cell(one, session_factory=ControlTreeSession)
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
