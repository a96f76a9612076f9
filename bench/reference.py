"""The plain reference: numpy evaluation of a configuration's predicate
chain and of the paper's epoch ordering. It imports nothing of the program.

Predicates come from the configuration file as dicts
``{"name", "column", "op", "t1", "t2", "rounds", "static_cost"}`` with
``op`` one of ``gt`` (x > t1), ``lt`` (x < t1), ``between``
(t1 < x < t2) and ``hashmix`` (the paper's expensive string predicate:
``rounds`` of y·MUL + ADD mod 2^20, then > t1); the chain is their AND.
All arithmetic is float32, as the configurations state.
"""

from __future__ import annotations

import numpy as np

MIX_MUL = np.float32(1.0000019073486328)
MIX_ADD = np.float32(0.31830987334251404)
MIX_MOD = np.float32(1048576.0)
EPS = 1e-6


def hashmix(x: np.ndarray, rounds: int) -> np.ndarray:
    y = x.astype(np.float32)
    for _ in range(max(int(rounds), 1)):
        y = y * MIX_MUL + MIX_ADD
        y = y - np.floor(y / MIX_MOD) * MIX_MOD
    return y


def predicate(x: np.ndarray, p: dict) -> np.ndarray:
    """bool[R]: rows of column values ``x`` (f32) that pass ``p``."""
    t1 = np.float32(p.get("t1", 0.0))
    op = p["op"]
    if op == "gt":
        return x > t1
    if op == "lt":
        return x < t1
    if op == "between":
        return (x > t1) & (x < np.float32(p["t2"]))
    if op == "hashmix":
        return hashmix(x, p["rounds"]) > t1
    raise ValueError(f"unknown predicate op {op!r}")


def chain_mask(cols: np.ndarray, predicates: list[dict]) -> np.ndarray:
    """bool[R]: rows passing every predicate. HASHMIX runs only on the rows
    the cheap predicates let through (the same answer)."""
    if any(p.get("group") is not None for p in predicates):
        raise ValueError("the reference covers flat (AND) chains")
    mask = np.ones(cols.shape[1], bool)
    for p in sorted(predicates, key=lambda p: p["op"] == "hashmix"):
        idx = np.flatnonzero(mask)
        mask[idx] = predicate(cols[p["column"], idx], p)
    return mask


def cut_counts(cols: np.ndarray, predicates: list[dict], start: int,
               stride: int) -> tuple[np.ndarray, int]:
    """Rows at ``start, start+stride, ...`` that fail each predicate (the
    paper's monitor lane), and how many rows that is."""
    rows = cols[:, start::stride]
    fails = np.array([np.count_nonzero(
        ~predicate(rows[p["column"]], p)) for p in predicates], np.float64)
    return fails, rows.shape[1]


def as_bfloat16(cols: np.ndarray) -> np.ndarray:
    """Columns rounded to bfloat16 (the control's precision), held as f32."""
    import ml_dtypes

    return cols.astype(ml_dtypes.bfloat16).astype(np.float32)


# ------------------------------------------------------------ the ordering
class Ordering:
    """The paper's epoch ordering (§2.1-2.2, Table 1) for a flat chain.

    Every ``collect_rate``-th row of each executor's rows is monitored and
    every predicate is evaluated on it; after ``calculate_rate`` rows per
    executor the epoch closes: selectivity s = 1 - cut/monitored, cost
    normalised by the largest, rank = cost / (1 - s), smoothed by
    ``momentum`` (the first epoch takes the rank as it is), and the chain
    runs in ascending rank. Statistics of all executors are summed at the
    boundary (centralized scope).
    """

    def __init__(self, predicates: list[dict], ordering: dict):
        self.preds = predicates
        self.collect = int(ordering["collect_rate"])
        self.calculate = int(ordering["calculate_rate"])
        self.momentum = float(ordering["momentum"])
        cost = np.array([p.get("static_cost", 1.0) for p in predicates])
        self.norm_cost = cost / max(cost.max(), EPS)
        n = len(predicates)
        self.phase = 0
        self.rows = 0
        self.epoch = 0
        self.adj = None
        self.cut = np.zeros(n)
        self.n_mon = 0

    def sample_start(self) -> int:
        """First monitored local row of the next step: row j is monitored
        when (j + phase) is a multiple of collect_rate."""
        return (-self.phase) % self.collect

    def fold(self, cuts: np.ndarray, n_mon: int, local_rows: int) -> bool:
        """Fold one step's monitor counts (summed over executors); returns
        True when the step closes an epoch."""
        self.cut += cuts
        self.n_mon += n_mon
        self.phase = (self.phase + local_rows) % self.collect
        self.rows += local_rows
        if self.rows < self.calculate:
            return False
        self.rows %= self.calculate
        if self.n_mon > 0:
            sel = np.clip(1.0 - self.cut / self.n_mon, 0.0, 1.0)
            rank = self.norm_cost / np.maximum(1.0 - sel, EPS)
            self.adj = rank if self.adj is None else \
                (1.0 - self.momentum) * rank + self.momentum * self.adj
            self.epoch += 1
        self.cut = np.zeros_like(self.cut)
        self.n_mon = 0
        return True


def misordered(perm, adj, rel: float = 1e-4) -> bool:
    """True when ``perm`` is not a permutation, or does not run the chain in
    ascending reference rank (``adj``; None before the first epoch, when
    the statement order is due). Ranks within ``rel`` of each other may run
    in either order: the program keeps them in float32."""
    perm = np.atleast_2d(np.asarray(perm))
    n = perm.shape[1]
    for p in perm:                       # one row per executor
        if sorted(p.tolist()) != list(range(n)):
            return True
        if adj is None:
            if p.tolist() != list(range(n)):
                return True
            continue
        r = np.asarray(adj)[p]
        if np.any(r[:-1] > r[1:] * (1.0 + rel) + 1e-12):
            return True
    return False


# ------------------------------------------------------------- survivors
_W_MUL = np.uint64(0x9E3779B1)
_W_ADD = np.uint64(0x7F4A7C15)
_MASK32 = np.uint64(0xFFFFFFFF)


def survivor_checksum(survivors: np.ndarray) -> int:
    """Position-weighted sum, mod 2^32, of the f32 bit patterns of packed
    survivors f32[C, n]: any changed value, order or count changes it."""
    c, n = survivors.shape
    bits = np.ascontiguousarray(survivors, np.float32).view(np.uint32)
    idx = np.arange(n, dtype=np.uint64)[None, :] * np.uint64(c) \
        + np.arange(c, dtype=np.uint64)[:, None]
    w = ((idx * _W_MUL + _W_ADD) & _MASK32) | np.uint64(1)
    return int(np.sum((bits.astype(np.uint64) * w) & _MASK32,
                      dtype=np.uint64) & _MASK32)
