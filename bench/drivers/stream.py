"""A record stream fed from the host through ``FilterSession.step``.

Closed loop: a batch is handed to ``session.step`` as soon as fewer than
``traffic["in_flight"]`` batches are waiting for their masks, so the
executor double-buffers with ``in_flight = 2``. A batch is done when its
mask is on the host. The host ring of distinct batches (made in set-up
from the seed) is replayed in order; the session's ordering state runs on
across replays.
"""

from __future__ import annotations

import collections
import json
from time import perf_counter as now

import numpy as np

from bench import program
from bench import reference as ref
from bench.drivers.common import (Reservoir, Window, annotate, data_module,
                                  ordering_checks)

#: masks kept, drawn from the seed, for the comparison after the window
SAMPLED_MASKS = 16


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 session_factory=program.session):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.shards = config["plan"].get("shards", 1)
        self.local_rows = config["batch_rows"]
        self.ring = data_module(config["data"]).host_ring(seed, config,
                                                          traffic)
        self.rows_per_step = int(self.ring[0].shape[1])
        self.session = session_factory(config)
        self.state = self.session.init_state()
        self.in_flight = int(traffic["in_flight"])
        self.records: list[dict] = []        # every step, device values
        self._host: list[dict] = []
        self.masks = Reservoir(SAMPLED_MASKS, seed)

    def describe(self) -> str:
        return (f"host ring: {len(self.ring)} steps of f32"
                f"{list(self.ring[0].shape)} "
                f"({sum(b.nbytes for b in self.ring)} bytes); traffic "
                f"{json.dumps(self.traffic)}")

    # ------------------------------------------------------------- loop
    def warm(self) -> None:
        """Every shape of the window: one pass over the ring (step, epoch
        boundary, mask readback), through the window's own loop."""
        self._loop(n_steps=max(len(self.ring), 2 * self.in_flight))

    def window(self, seconds: float) -> Window:
        return self._loop(seconds=seconds)

    def _loop(self, seconds: float | None = None,
              n_steps: int | None = None) -> Window:
        win = Window(latency_kind="batch", first_step=len(self.records))
        pending: collections.deque = collections.deque()
        t0 = now()
        end = t0 + seconds if seconds is not None else float("inf")
        timed = seconds is not None

        def complete():
            t_sub, k, mask = pending.popleft()
            with annotate("bench.readback"):
                host = np.asarray(mask)
            t_done = now()
            if timed:
                self.masks.offer((k, host))
                if t_done <= end:
                    win.rows_done += self.rows_per_step
                    win.latencies_s.append(t_done - t_sub)

        while True:
            t = now()
            if t >= end or (n_steps is not None and win.steps >= n_steps):
                break
            k = len(self.records)
            batch = self.ring[k % len(self.ring)]
            with annotate("bench.submit"):
                self.state, res = self.session.step(self.state, batch)
            res.mask.copy_to_host_async()
            m = res.metrics
            self.records.append({"perm": m.perm, "epoch": m.epoch,
                                 "n_pass": m.n_pass, "gate_s": res.gate_s})
            pending.append((t, k, res.mask))
            win.steps += 1
            if len(pending) >= self.in_flight:
                complete()
        win.seconds = (end - t0) if timed else now() - t0
        while pending:
            complete()
        win.attempted = win.steps
        return win

    # ------------------------------------------------------------ check
    def host_records(self) -> list[dict]:
        import jax

        if len(self._host) != len(self.records):
            self._host = jax.device_get(self.records)
        return self._host

    def check(self) -> dict:
        """Every number compared, each exact (limit 0)."""
        recs = self.host_records()
        n_slots = len(self.ring)
        preds = self.config["predicates"]
        need = sorted({k % n_slots for k, _ in self.masks.items}
                      | set(range(min(n_slots, len(recs)))))
        want = {s: ref.chain_mask(self.ring[s], preds) for s in need}
        mask_wrong = sum(int(np.count_nonzero(m != want[k % n_slots]))
                         for k, m in self.masks.items)
        pass_wrong = sum(
            int(np.sum(r["n_pass"])) != int(np.count_nonzero(
                want[k % n_slots]))
            for k, r in enumerate(recs))

        def cuts_of(k, start, stride):
            batch = self.ring[k % n_slots]
            total, n_mon = np.zeros(len(preds)), 0
            for s in range(self.shards):
                part = batch[:, s * self.local_rows:(s + 1) * self.local_rows]
                c, n = ref.cut_counts(part, preds, start, stride)
                total += c
                n_mon += n
            return total, n_mon

        out = {"mask_rows_wrong": mask_wrong,
               "pass_count_wrong": pass_wrong}
        out.update(ordering_checks(self.config, self.local_rows, self.shards,
                                   recs, cuts_of))
        return out

    def layer_counts(self, win: Window) -> dict:
        """Counters of the window's steps for the per-layer readers."""
        recs = self.host_records()
        lo, hi = win.first_step, win.first_step + win.steps
        epochs = [int(np.max(r["epoch"])) for r in recs[max(lo - 1, 0):hi]]
        if lo == 0:
            epochs.insert(0, 0)
        return {"gate_s": [r["gate_s"] for r in recs[lo:hi]],
                "rows": [self.rows_per_step] * win.steps,
                "boundaries": sum(b != a for a, b in zip(epochs, epochs[1:]))}
