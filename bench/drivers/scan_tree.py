"""Queries whose filter is a tree (OR-groups of AND-terms, IN lists) over a
table cached on the device: TPC-H Q19 over ``lineitem ⋈ part``.

The loop is ``scan``'s: every partition of the table through
``FilterSession.step`` (chain kernel, fused compaction) in order, each
partition's packed survivors reduced on the device into the query's
answer, queries back to back with ``traffic["in_flight"]`` partitions
dispatched ahead. What differs:

  * the session is built from the configuration's ``group``/``term``/
    ``codes`` keys (``tree_session``) — and first, before the table is
    made, so a program without trees fails at once;
  * the answer is Q19's revenue, sum(l_extendedprice · (1 - l_discount));
  * each step's row also carries the chain kernel's ``n_atom_tiles``;
  * the check compares with ``bench/reference_tree.py``.
"""

from __future__ import annotations

import collections
import sys

import numpy as np

from bench import program
from bench import reference_tree as ref
from bench.drivers import scan

KEYS = ("n_kept", "chk", "t_pass", "t_fail", "t_amb", "epoch", "atom_tiles")


def tree_chain(config: dict) -> list:
    """The program's ``Predicate`` chain of the configuration's
    ``predicates``, with their ``group``/``term`` labels and IN ``codes``."""
    from repro.core import predicates as P

    ops = {"gt": P.OP_GT, "lt": P.OP_LT, "between": P.OP_BETWEEN,
           "eq": P.OP_EQ, "in": P.OP_IN}
    chain = []
    for p in config["predicates"]:
        t1, t2 = P.in_set(p["codes"]) if p["op"] == "in" \
            else (p.get("t1", 0.0), p.get("t2", 0.0))
        chain.append(P.Predicate(
            p["name"], column=p["column"], op=ops[p["op"]], t1=t1, t2=t2,
            static_cost=p.get("static_cost", 1.0), group=p.get("group"),
            term=p.get("term")))
    return chain


def tree_session(config: dict):
    """``build_session(FilterPlan(...))`` for a chain with groups, terms
    and IN lists."""
    from repro.core import FilterPlan, build_session
    from repro.core.ordering import OrderingConfig

    chain = tree_chain(config)
    unknown = set(config["plan"]) - set(program.PLAN_KEYS)
    if unknown:
        raise ValueError(f"unknown plan keys {sorted(unknown)}")
    return build_session(FilterPlan(
        predicates=chain, ordering=OrderingConfig(**config["ordering"]),
        **config["plan"]))


def _consumer(price_col: int, disc_col: int, checksum: bool):
    """``(revenue, packed, n_kept, counters, perm) -> (revenue, row)`` as
    in ``scan``, with Q19's revenue: price · (1 - discount/100)."""
    import jax
    import jax.numpy as jnp

    w_mul = jnp.uint32(0x9E3779B1)
    w_add = jnp.uint32(0x7F4A7C15)

    def consume(revenue, packed, n_kept, counters, perm):
        n_cols, cap = packed.shape
        idx = jnp.arange(cap, dtype=jnp.uint32)
        keep = idx < n_kept.astype(jnp.uint32)
        part = jnp.sum(jnp.where(
            keep, packed[price_col] * (1.0 - packed[disc_col] / 100.0), 0.0))
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


class _CountingSession:
    """The session, keeping its last step's ``n_atom_tiles`` (a device
    scalar, read by nobody until the check) for the consumer's row."""

    def __init__(self, session):
        self.session = session
        self.atom_tiles = 0

    def init_state(self):
        return self.session.init_state()

    def step(self, state, batch):
        state, res = self.session.step(state, batch)
        self.atom_tiles = getattr(res.metrics, "n_atom_tiles", 0)
        return state, res


class Driver(scan.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int,
                 session_factory=tree_session):
        session = _CountingSession(session_factory(config))
        super().__init__(config, traffic, seed,
                         session_factory=lambda _: session)
        q = config["answer"]
        self.consume = [self._with_atom_tiles(_consumer(
            q["price_column"], q["discount_column"], c)) for c in (False, True)]

    def _with_atom_tiles(self, consume):
        def run(revenue, packed, n_kept, counters, perm):
            return consume(revenue, packed, n_kept,
                           (*counters, self.session.atom_tiles), perm)
        return run

    def _fetch(self) -> list[dict]:
        import jax

        rows = jax.device_get([r for _, _, r, _ in self.records])
        out = []
        for (part, checked, _, gate), row in zip(self.records, rows):
            rec = dict(zip(KEYS, (int(v) for v in row[:len(KEYS)])))
            rec["chk"] &= 0xFFFFFFFF
            rec.update(part=part, checked=checked, gate_s=gate,
                       perm=np.asarray(row[len(KEYS):]),
                       epoch=np.asarray(rec["epoch"]))
            out.append(rec)
        return out

    def check(self) -> dict:
        """Every step's kept count against the reference's, each query's
        checked partition's survivors by checksum, and the three-level
        ordering replayed over every step; each number exact (limit 0)."""
        recs = self.host_records()
        preds = self.config["predicates"]
        order = ref.Ordering(preds, self.config["ordering"])
        starts: dict = collections.defaultdict(set)
        for k, r in enumerate(recs):
            starts[r["part"]].add((-(k * self.rows)) % order.collect)
        want, cuts = [], {}
        for p, part in enumerate(self.table):
            cols = np.asarray(part)
            mask = ref.tree_mask(cols, preds)
            want.append((int(np.count_nonzero(mask)),
                         ref.survivor_checksum(cols[:, mask])))
            for s in starts[p]:
                cuts[p, s] = ref.cut_counts(cols, preds, s, order.collect)
        n_pass = sum(n for n, _ in want)
        print(f"[bench] reference: {n_pass} of {len(want) * self.rows} rows "
              f"pass ({n_pass / (len(want) * self.rows):.3e})",
              file=sys.stderr, flush=True)
        wrong = misordered = epoch_wrong = 0
        for r in recs:
            wrong += r["n_kept"] != want[r["part"]][0] or (
                r["checked"] and r["chk"] != want[r["part"]][1])
            misordered += order.misordered(r["perm"])
            *c, n_mon = cuts[r["part"], order.sample_start()]
            order.fold(c, n_mon, self.rows)
            epoch_wrong += {int(e) for e in r["epoch"].reshape(-1)} \
                != {order.epoch}
        return {"survivors_wrong": int(wrong),
                "perm_misordered": int(misordered),
                "epoch_wrong": int(epoch_wrong)}

    def layer_counts(self, win) -> dict:
        """``scan``'s, with each step's ``n_atom_tiles`` and the (tile,
        atom) pairs it counts over: the kernel grid's tiles, of the
        program's ``CHAIN_TILE`` rows, times the chain's atoms."""
        from repro.core.engine.base import CHAIN_TILE

        out = super().layer_counts(win)
        recs = self.host_records()[win.first_step:win.first_step + win.steps]
        n_atoms = len(self.config["predicates"])
        out["atom_tiles"] = [int(r["atom_tiles"]) for r in recs]
        out["atom_pairs"] = [-(-rows // CHAIN_TILE) * n_atoms
                             for rows in out["rows"]]
        return out
