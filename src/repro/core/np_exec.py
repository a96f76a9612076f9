"""Row-exact numpy backend: compacted short-circuit evaluation.

This is the host-side execution path used by the ``numpy`` engine, the
benchmarks, and ``executor_sim.py``. It mirrors what Spark's generated
``processNext`` does — a row is never evaluated against predicates later in
the order once its fate is decided — by *compacting* the active row set
between predicates (boolean-index gather). Wall time therefore genuinely
depends on the evaluation order, which is what Figures 1–4 of the paper
measure.

CNF semantics match the jnp / Pallas engines exactly (cross-checked in
tests): within an OR-group a row stops evaluating members once one passes;
a row that fails every member of a group is dropped before the next group.
In a tree (AND-terms inside groups) a row leaves a term at its first
failing atom, and the group at its first passing term.

Semantics are bit-identical to ``core.filter_exec`` / the Pallas kernel;
only the execution strategy differs.
"""

from __future__ import annotations

import numpy as np

from repro.core import predicates as pred_lib
from repro.core.engine.base import CHAIN_TILE


def eval_pred_np(op: int, t1: float, t2: float, rounds: int,
                 x: np.ndarray) -> np.ndarray:
    if op == pred_lib.OP_GT:
        return x > t1
    if op == pred_lib.OP_LT:
        return x < t1
    if op == pred_lib.OP_BETWEEN:
        return (x > t1) & (x < t2)
    if op == pred_lib.OP_EQ:
        return np.round(x) == np.round(t1)
    if op == pred_lib.OP_HASHMIX:
        y = x.astype(np.float32)
        for _ in range(max(rounds, 1)):
            y = y * np.float32(pred_lib.MIX_MUL) + np.float32(pred_lib.MIX_ADD)
            y = y - np.floor(y / np.float32(pred_lib.MIX_MOD)) * np.float32(pred_lib.MIX_MOD)
        return y > t1
    if op == pred_lib.OP_IN:
        codes = np.asarray(pred_lib.in_codes(t1, t2), np.float64)
        return np.isin(np.round(x), codes)
    raise ValueError(f"unknown op {op}")


def _groups_for(preds, groups) -> np.ndarray:
    if groups is None:
        return np.arange(len(preds))
    g = np.asarray(groups, np.int64)
    if g.shape != (len(preds),):
        raise ValueError("groups must give one id per predicate")
    return g


def _tiles_with_rows(rows: np.ndarray, n_rows: int) -> int:
    """CHAIN_TILE-row tiles holding a row of ``rows`` (sorted row ids):
    one binary search per tile boundary, no pass over the rows."""
    bounds = np.arange(0, n_rows + CHAIN_TILE, CHAIN_TILE)
    return int(np.count_nonzero(np.diff(np.searchsorted(rows, bounds))))


def run_chain_np(columns: np.ndarray, preds, perm, groups=None, terms=None,
                 count_tiles: bool = True) -> tuple[np.ndarray, float,
                                                    np.ndarray, int | None]:
    """Short-circuit chain in ``perm`` order with compaction.

    Returns (mask bool[R], work_units, active_before f32[P], n_atom_tiles:
    the (CHAIN_TILE-row tile, position) pairs with a row pending, as the
    tiled kernel counts them, or None without ``count_tiles``). ``preds``
    is a sequence of ``Predicate``; ``groups`` the dense group-id-per-
    predicate tuple (None → singletons, the flat conjunction); ``terms``
    the dense term ids of a tree (None or () → one term per atom, the CNF
    chain). Group and term members must be contiguous in ``perm``. Work
    accounting matches the jnp/Pallas paths: position k is charged
    static_cost × rows pending before it.
    """
    g = _groups_for(preds, groups)
    t = np.asarray(terms) if terms else np.arange(len(preds))
    n_rows = columns.shape[1]
    work = 0.0
    active_before = np.zeros(len(preds), np.float32)
    atom_tiles = 0 if count_tiles else None

    perm = [int(i) for i in perm]
    for ids in (g, t):
        seq = [int(ids[i]) for i in perm]
        runs = [x for j, x in enumerate(seq) if j == 0 or seq[j - 1] != x]
        if len(set(runs)) != len(runs):
            raise ValueError("group and term members must be contiguous "
                             "in perm")

    alive_idx = np.arange(n_rows)        # survivors of all closed groups
    k = 0
    while k < len(perm):
        gid = g[perm[k]]
        # pending = alive rows no closed term of this OR-group passed
        pending = alive_idx
        passed = np.zeros(0, np.int64)
        while k < len(perm) and g[perm[k]] == gid:
            tid = t[perm[k]]
            rows = pending               # rows every atom so far passed
            kept = None                  # bool over pending: rows, as a mask
            while k < len(perm) and t[perm[k]] == tid:
                p = preds[perm[k]]
                active_before[k] = rows.size
                if count_tiles:
                    atom_tiles += _tiles_with_rows(rows, n_rows)
                work += rows.size * p.static_cost
                if rows.size:
                    x = columns[p.column, rows]
                    res = eval_pred_np(p.op, p.t1, p.t2, p.rounds, x)
                    rows = rows[res]     # AND short-circuit on first fail
                    if kept is None:
                        kept = res
                    else:
                        kept[kept] = res
                k += 1
            passed = np.concatenate([passed, rows])
            if kept is not None:
                pending = pending[~kept]  # OR short-circuit on first pass
        # group closes: rows that passed no term are cut
        alive_idx = np.sort(passed)

    mask = np.zeros(n_rows, dtype=bool)
    mask[alive_idx] = True
    return mask, float(work), active_before, atom_tiles


def eval_tree_np(columns: np.ndarray, preds) -> np.ndarray:
    """bool[R]: the plain reference of a chain of any shape — every atom on
    every row, then AND within each term, OR across a group's terms, AND
    across groups. No order, no short-circuit, no tiling."""
    groups = pred_lib.normalize_groups(preds)
    terms = pred_lib.normalize_terms(preds) or tuple(range(len(preds)))
    term_pass: dict = {}
    for p, g, t in zip(preds, groups, terms):
        res = eval_pred_np(p.op, p.t1, p.t2, p.rounds, columns[p.column])
        key = (g, t)
        term_pass[key] = term_pass[key] & res if key in term_pass else res
    group_pass: dict = {}
    for (g, _), res in term_pass.items():
        group_pass[g] = group_pass[g] | res if g in group_pass else res
    mask = np.ones(columns.shape[1], bool)
    for res in group_pass.values():
        mask &= res
    return mask


def run_monitor_np(columns: np.ndarray, preds, collect_rate: int,
                   sample_phase: int, groups=None, terms=None
                   ) -> tuple[np.ndarray, np.ndarray, int, np.ndarray,
                              np.ndarray]:
    """Monitor lane: all predicates on stride-sampled rows (paper §2.1).

    Returns (cut_counts f64[P], group_cut f64[G], n_monitored,
    per-predicate measured seconds, term_cut f64[T] — empty without
    terms). The measured clock here is the numpy analogue of the paper's
    ``System.nanoTime`` around each predicate evaluation.
    """
    import time

    g = _groups_for(preds, groups)
    n_groups = int(g.max()) + 1
    n_rows = columns.shape[1]
    first = (-sample_phase) % collect_rate
    idx = np.arange(first, n_rows, collect_rate)
    cut = np.zeros(len(preds), np.float64)
    group_cut = np.zeros(n_groups, np.float64)
    secs = np.zeros(len(preds), np.float64)
    t = np.asarray(terms if terms else range(len(preds)))
    term_cut = np.zeros(int(t.max()) + 1 if terms else 0, np.float64)
    if idx.size == 0:
        return cut, group_cut, 0, secs, term_cut
    term_pass = np.ones((int(t.max()) + 1, idx.size), bool)
    for i, p in enumerate(preds):
        x = columns[p.column, idx]
        t0 = time.perf_counter()
        res = eval_pred_np(p.op, p.t1, p.t2, p.rounds, x)
        secs[i] = time.perf_counter() - t0
        cut[i] = np.sum(~res)
        term_pass[t[i]] &= res
    group_fail = np.ones((n_groups, idx.size), bool)
    for i in range(len(preds)):          # a term's group, once per term
        group_fail[g[i]] &= ~term_pass[t[i]]
    group_cut[:] = group_fail.sum(axis=1)
    if terms:
        term_cut[:] = (~term_pass).sum(axis=1)
    return cut, group_cut, int(idx.size), secs, term_cut
