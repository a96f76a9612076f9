"""Queries over a table cached on the device, one partition per
``FilterSession.step``.

A query steps every partition of the table in order through the session
(skip tier, chain kernel, fused compaction) and reduces each partition's
packed survivors on the device into the query's answer (Q6's revenue,
sum of l_extendedprice · l_discount over the survivors). The query ends
when its answer is on the host. Queries run back to back, one at a time.
At most ``traffic["in_flight"]`` partitions are dispatched ahead of the
oldest unfinished one, so only that many packed outputs are alive.

Every step's kept count is recorded for the comparison with the
reference; in each query one partition, drawn from the seed, is reduced
by a second program that also folds its packed survivors into a
checksum, so that the answers themselves are compared too, at the cost of
one extra pass over one partition's output per query.
"""

from __future__ import annotations

import collections
import random
from time import perf_counter as now

import numpy as np

from bench import program
from bench import reference as ref
from bench.drivers.common import (Window, annotate, data_module,
                                  ordering_checks)


def _consumer(price_col: int, disc_col: int, checksum: bool):
    """``(revenue, packed, n_kept, counters, perm) -> (revenue, row)``: the
    query's running revenue, and one small int32 row per step for the check
    and the per-layer readers (the checksum is 0 unless ``checksum``)."""
    import jax
    import jax.numpy as jnp

    w_mul = jnp.uint32(0x9E3779B1)
    w_add = jnp.uint32(0x7F4A7C15)

    def consume(revenue, packed, n_kept, counters, perm):
        n_cols, cap = packed.shape
        idx = jnp.arange(cap, dtype=jnp.uint32)
        keep = idx < n_kept.astype(jnp.uint32)
        part = jnp.sum(jnp.where(keep, packed[price_col] * packed[disc_col],
                                 0.0))
        chk = jnp.uint32(0)
        if checksum:
            bits = jax.lax.bitcast_convert_type(packed, jnp.uint32)
            pos = idx[None, :] * jnp.uint32(n_cols) \
                + jnp.arange(n_cols, dtype=jnp.uint32)[:, None]
            w = (pos * w_mul + w_add) | jnp.uint32(1)
            chk = jnp.sum(jnp.where(keep[None, :], bits * w, jnp.uint32(0)),
                          dtype=jnp.uint32)
        row = jnp.concatenate([
            jnp.stack([n_kept.astype(jnp.int32),
                       jax.lax.bitcast_convert_type(chk, jnp.int32),
                       *(jnp.asarray(c, jnp.int32) for c in counters)]),
            perm.astype(jnp.int32)])
        return revenue + part, row

    return jax.jit(consume)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 session_factory=program.session):
        import jax.numpy as jnp

        self.config, self.traffic, self.seed = config, traffic, seed
        self.rows = config["partition_rows"]
        self.table = data_module(config["data"]).device_table(seed, config,
                                                              traffic)
        self.session = session_factory(config)
        self.state = self.session.init_state()
        self.in_flight = int(traffic["in_flight"])
        q = config["answer"]
        self.consume = [_consumer(q["price_column"], q["discount_column"], c)
                        for c in (False, True)]
        self.pick = random.Random(seed)     # the checked partition per query
        self.zero = jnp.zeros((), jnp.float32)
        # (partition, checked, row, gate_s) per step
        self.records: list[tuple] = []
        self._host: list[dict] = []

    def describe(self) -> str:
        n = len(self.table)
        return (f"device table: {n} partitions of f32"
                f"{list(self.table[0].shape)} ({n * self.table[0].nbytes} "
                f"bytes), {self.traffic['order']} order, "
                f"{self.in_flight} partitions in flight")

    # ------------------------------------------------------------- loop
    def warm(self) -> None:
        """One whole query: every partition, the reduction, the readback."""
        self._loop(n_queries=1)

    def window(self, seconds: float) -> Window:
        return self._loop(seconds=seconds)

    def _loop(self, seconds: float | None = None,
              n_queries: int | None = None) -> Window:
        win = Window(latency_kind="query", first_step=len(self.records))
        t0 = now()
        end = t0 + seconds if seconds is not None else float("inf")
        timed = seconds is not None
        queries = 0
        pending: collections.deque = collections.deque()

        def settle():
            chk = pending.popleft()
            chk.block_until_ready()
            if timed and now() <= end:
                win.rows_done += self.rows

        while now() < end and (n_queries is None or queries < n_queries):
            t_start = now()
            win.attempted += 1
            revenue = self.zero
            checked = self.pick.randrange(len(self.table))
            for p, part in enumerate(self.table):
                if now() >= end:
                    break
                with annotate("bench.submit"):
                    self.state, res = self.session.step(self.state, part)
                with annotate("bench.query_reduce"):
                    m = res.metrics
                    revenue, row = self.consume[p == checked](
                        revenue, res.packed, res.n_kept,
                        (m.n_tiles_pass, m.n_tiles_fail,
                         m.n_tiles_ambiguous, m.epoch), m.perm)
                self.records.append((p, p == checked, row, res.gate_s))
                del res
                win.steps += 1
                pending.append(row)
                if len(pending) > self.in_flight:
                    settle()
            else:
                while pending:
                    settle()
                with annotate("bench.readback"):
                    float(revenue)
                t_done = now()
                queries += 1
                if timed and t_done <= end:
                    win.latencies_s.append(t_done - t_start)
                continue
            break
        win.seconds = (end - t0) if timed else now() - t0
        while pending:
            settle()
        return win

    # ------------------------------------------------------------ check
    def host_records(self) -> list[dict]:
        if len(self._host) != len(self.records):
            self._host = self._fetch()
        return self._host

    def _fetch(self) -> list[dict]:
        import jax

        rows = jax.device_get([r for _, _, r, _ in self.records])
        keys = ("n_kept", "chk", "t_pass", "t_fail", "t_amb", "epoch")
        out = []
        for (part, checked, _, gate), row in zip(self.records, rows):
            rec = dict(zip(keys, (int(v) for v in row[:len(keys)])))
            rec["chk"] &= 0xFFFFFFFF
            rec.update(part=part, checked=checked, gate_s=gate,
                       perm=np.asarray(row[len(keys):]))
            rec["epoch"] = np.asarray(rec["epoch"])
            out.append(rec)
        return out

    def check(self) -> dict:
        """Every step's kept count against the reference's (a survivor lost
        to capacity changes it), each query's checked partition's survivors
        by checksum, and the ordering; each number exact (limit 0)."""
        recs = self.host_records()
        preds = self.config["predicates"]
        stride = int(self.config["ordering"]["collect_rate"])
        starts: dict = collections.defaultdict(set)
        for k, r in enumerate(recs):
            starts[r["part"]].add((-(k * self.rows)) % stride)
        want, cuts = [], {}
        for p, part in enumerate(self.table):
            cols = np.asarray(part)
            mask = ref.chain_mask(cols, preds)
            want.append((int(np.count_nonzero(mask)),
                         ref.survivor_checksum(cols[:, mask])))
            for s in starts[p]:
                cuts[p, s] = ref.cut_counts(cols, preds, s, stride)
        wrong = sum(r["n_kept"] != want[r["part"]][0]
                    or (r["checked"] and r["chk"] != want[r["part"]][1])
                    for r in recs)

        def cuts_of(k, start, _stride):
            return cuts[recs[k]["part"], start]

        out = {"survivors_wrong": wrong}
        out.update(ordering_checks(self.config, self.rows, 1, recs, cuts_of))
        return out

    def layer_counts(self, win: Window) -> dict:
        recs = self.host_records()[win.first_step:win.first_step + win.steps]
        return {"gate_s": [r["gate_s"] for r in recs],
                "rows": [self.rows] * len(recs),
                "n_kept": [int(r["n_kept"]) for r in recs],
                "tile_counts": [(int(r["t_pass"]), int(r["t_fail"]),
                                 int(r["t_amb"])) for r in recs]}
