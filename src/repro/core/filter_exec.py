"""Chain execution + monitor lane (pure-jnp reference path).

This module implements the ``jnp`` engine's math (see ``core/engine/`` for
the registry; three engines share the ``ChainResult`` contract and are
cross-checked by tests):

  * ``jnp`` (here)     — fully vectorized masked evaluation. Exact row-level
                         *work counters* (what Spark would have evaluated),
                         usable inside a jitted training pipeline.
  * ``numpy``          — host path in ``engine/numpy_engine.py`` /
                         ``executor_sim.py``: boolean-index compaction
                         between predicates, so wall time genuinely tracks
                         the chosen order (row-exact short-circuit, like
                         Spark's processNext).
  * ``pallas``         — ``kernels/filter_chain``: fused single-HBM-pass tile
                         kernel with tile-level early exit (the TPU target).

CNF semantics (all engines): predicates sharing a group OR together; groups
AND together. Evaluation short-circuits at both levels — a row stops
evaluating an OR-group's members once one passes, and stops entirely once a
group rejects it. ``perm`` must keep each group's members contiguous
(``stats.cnf_order`` guarantees it); flat chains (all singleton groups) are
the degenerate case and reproduce the paper's conjunction bit-exactly.
Trees add AND-terms inside groups: a row evaluates a term's atoms until one
fails, the group's terms until one passes, and the groups until one fails.

Monitor lane (paper §2.1): rows with (global_row_index % collect_rate == 0)
are sampled; *all* predicates are evaluated on them (correlation-bias-free),
and numCut / cost accumulate only from those rows — plus, for CNF, the exact
per-group cut counts (no member passed). Sampling is a deterministic stride
— no PRNG — carried across batches by ``sample_phase``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import predicates as pred_lib
from repro.core.engine.base import CHAIN_TILE, ChainResult
from repro.core.predicates import PredicateSpecs

__all__ = ["ChainResult", "monitor_indices", "run_monitor", "run_chain",
           "run_chain_masks", "compact", "compact_fixed",
           "compact_fixed_argsort"]


def monitor_indices(n_rows: int, collect_rate: int, sample_phase):
    """Deterministic-stride sample positions for one batch.

    Returns (idx_i32[max_samples], valid_bool[max_samples]); static shapes so
    the whole thing jits. ``sample_phase`` = global row offset of this batch
    modulo collect_rate.
    """
    max_samples = n_rows // collect_rate + 1
    first = (-sample_phase) % collect_rate
    idx = first + jnp.arange(max_samples, dtype=jnp.int32) * collect_rate
    valid = idx < n_rows
    return jnp.clip(idx, 0, n_rows - 1), valid


def run_monitor(columns: jnp.ndarray, specs: PredicateSpecs,
                collect_rate: int, sample_phase):
    """Evaluate ALL predicates on the sampled rows only.

    Returns (cut f32[P], group_cut f32[G], n_monitored f32[],
    monitor_cost f32[P], term_cut f32[T] — empty without terms).
    """
    n_rows = columns.shape[1]
    idx, valid = monitor_indices(n_rows, collect_rate, sample_phase)
    sampled = columns[:, idx]                      # f32[C, max_samples]
    results = pred_lib.eval_all(specs, sampled)    # bool[P, max_samples]
    cut = jnp.sum(jnp.logical_and(~results, valid[None, :]), axis=1)
    # group cut: a sampled row is cut by group g iff NO member passes —
    # exact (the monitor lane sees the full outcome matrix, so group
    # selectivities carry no independence assumption). In a tree the
    # members are terms: a term fails when any of its atoms fails.
    if specs.is_tree:
        term_fail = jnp.stack(
            [jnp.any(~results[jnp.asarray(m)], axis=0)
             for m in specs.term_members])         # bool[T, max_samples]
        term_cut = jnp.sum(jnp.logical_and(term_fail, valid[None, :]),
                           axis=1).astype(jnp.float32)
        group_terms = specs.group_terms
    else:                                          # one term per atom
        term_fail, term_cut = ~results, jnp.zeros((0,), jnp.float32)
        group_terms = specs.group_members
    group_fail = jnp.stack(
        [jnp.all(term_fail[jnp.asarray(t)], axis=0)
         for t in group_terms])                    # bool[G, max_samples]
    group_cut = jnp.sum(jnp.logical_and(group_fail, valid[None, :]), axis=1)
    n_monitored = jnp.sum(valid).astype(jnp.float32)
    # STATIC cost model: each sampled row pays every predicate's calibrated
    # per-row cost (the monitor lane evaluates all of them, as in the paper).
    monitor_cost = specs.static_cost * n_monitored
    return (cut.astype(jnp.float32), group_cut.astype(jnp.float32),
            n_monitored, monitor_cost, term_cut)


def tiles_pending(pending) -> jnp.ndarray:
    """i32[]: CHAIN_TILE-row tiles of ``pending`` (bool[R]) with a row set."""
    pad = (-pending.shape[0]) % CHAIN_TILE
    p = jnp.pad(pending, (0, pad)) if pad else pending
    return jnp.sum(jnp.any(p.reshape(-1, CHAIN_TILE), axis=1),
                   dtype=jnp.int32)


def run_chain_masks(columns: jnp.ndarray, specs: PredicateSpecs,
                    perm: jnp.ndarray, valid=None, count_tiles: bool = True):
    """Chain lane only (no monitor): masked evaluation in ``perm`` order.

    Returns (mask bool[R], work f32[], active_before f32[P], n_atom_tiles
    i32[] — see ``ChainResult`` — or None without ``count_tiles``, static:
    the skip tier's gathered buffer has no tiles of its own). ``valid``
    (bool[R], optional) pre-cuts rows before the first predicate: the skip
    tier's gathered ambiguous buffer uses it so padding and unused gather
    slots are neither kept nor charged to the work counters.
    """
    n_rows = columns.shape[1]
    last = specs.n - 1
    flat = specs.is_flat                  # static → branch folds at trace
    tree = specs.is_tree                  # static: a CNF chain has no terms
    garr = jnp.asarray(specs.groups, jnp.int32)
    tarr = jnp.asarray(specs.term, jnp.int32) if tree else None

    # survivors of all CLOSED groups
    mask = jnp.ones((n_rows,), bool) if valid is None else valid
    group_or = jnp.zeros((n_rows,), bool)  # passes within the OPEN group
    term_and = None                        # rows the OPEN term keeps
    work = jnp.zeros((), jnp.float32)
    active_before = []
    atom_tiles = jnp.zeros((), jnp.int32) if count_tiles else None

    for k in range(specs.n):          # P is small & static → unrolled, lazy ops
        i = perm[k]
        # group and term boundary flags: static True when flat (groups) or
        # without terms (every atom its own term), traced scalars otherwise
        # (perm is dynamic under jit).
        is_first = True if (flat or k == 0) else (garr[perm[k - 1]] != garr[i])
        closes = True if (flat or k == last) \
            else (garr[perm[k + 1]] != garr[i])
        opens_term = True if (not tree or k == 0) \
            else (tarr[perm[k - 1]] != tarr[i])
        closes_term = True if (not tree or k == last) \
            else (tarr[perm[k + 1]] != tarr[i])
        pending = mask if is_first is True \
            else jnp.where(is_first, mask, jnp.logical_and(mask, ~group_or))
        if opens_term is not True:
            pending = jnp.where(opens_term, pending,
                                jnp.logical_and(pending, term_and))
        alive = jnp.sum(pending).astype(jnp.float32)
        active_before.append(alive)
        if count_tiles:
            atom_tiles = atom_tiles + tiles_pending(pending)
        work = work + alive * specs.static_cost[i]
        x = jnp.take(columns, specs.column[i], axis=0)
        res = pred_lib.eval_one(specs, i, x)
        term_and = res if opens_term is True \
            else jnp.where(opens_term, res, jnp.logical_and(term_and, res))
        term_pass = term_and if closes_term is True \
            else jnp.logical_and(term_and, closes_term)
        group_or = term_pass if is_first is True \
            else jnp.where(is_first, False, group_or) | term_pass
        new_mask = jnp.logical_and(mask, group_or)
        mask = new_mask if closes is True else jnp.where(closes, new_mask, mask)

    return mask, work, jnp.stack(active_before), atom_tiles


def run_chain(columns: jnp.ndarray, specs: PredicateSpecs, perm: jnp.ndarray,
              collect_rate: int, sample_phase) -> ChainResult:
    """Masked CNF chain in ``perm`` order + monitor lane.

    The boolean outcome is order-invariant (AND/OR commute); the work
    counters are not — they are the paper's objective function, measured
    exactly: predicate ``perm[k]`` is charged for every row still *pending*
    at position k — alive through all closed groups AND not yet passed by an
    earlier member of the current group (what a row-at-a-time engine with
    both short-circuits would evaluate).
    """
    mask, work, active_before, atom_tiles = run_chain_masks(columns, specs,
                                                            perm)

    cut, group_cut, n_mon, mon_cost, term_cut = run_monitor(
        columns, specs, collect_rate, sample_phase)

    return ChainResult(
        mask=mask,
        work_units=work,
        active_before=active_before,
        cut_counts=cut,
        n_monitored=n_mon,
        monitor_cost=mon_cost,
        group_cut_counts=group_cut,
        term_cut_counts=term_cut,
        n_atom_tiles=atom_tiles,
    )


def compact(columns: jnp.ndarray, mask: jnp.ndarray, fill: float = 0.0):
    """Stable stream compaction of surviving rows (cumsum + scatter).

    Returns (packed f32[C, R], n_survivors i32[]): survivors are moved to the
    front in order; the tail is ``fill``. Static output shape keeps it
    jit-able; downstream stages read only the first n_survivors rows.
    """
    n_rows = columns.shape[1]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1           # target slot per survivor
    dest = jnp.where(mask, pos, n_rows)                     # dump non-survivors
    out = jnp.full((columns.shape[0], n_rows + 1), fill, columns.dtype)
    out = out.at[:, dest].set(columns)
    return out[:, :n_rows], jnp.sum(mask.astype(jnp.int32))


def compact_fixed(columns: jnp.ndarray, mask: jnp.ndarray, capacity: int,
                  fill: float = 0.0):
    """Fixed-capacity device-side compaction: mask → cumsum positions → O(R)
    scatter.

    Returns (packed f32[C, capacity], n_kept i32[]). Survivors keep their
    stream order in the first ``n_kept`` slots; the tail is ``fill``. Unlike
    ``compact`` the output width is a static ``capacity`` independent of the
    batch width, so survivors flow to downstream device stages — or a single
    dense host copy — without ever round-tripping through a host boolean
    index. Shared by every traceable engine: the engines produce the mask,
    this gather consumes it (the fused compacting step). Survivors
    beyond ``capacity`` are dropped and ``n_kept`` saturates — size capacity
    from the stream's expected pass rate (capacity = batch width is always
    lossless; ``compact_capacity="auto"`` tracks the monitor lane's
    pass-rate).

    Each survivor's destination slot is its exclusive rank in the mask
    (cumsum − 1) — the same position math the fused Pallas kernel computes
    per tile — so there is no ``O(R log R)`` sort anywhere in the ingestion
    path. Non-survivors and overflow survivors scatter into a dump column
    that is sliced off, keeping the scatter index map free of duplicates on
    the live region.
    """
    if capacity < 1:
        raise ValueError("capacity must be >= 1")
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1   # exclusive survivor rank
    dest = jnp.where(jnp.logical_and(mask, pos < capacity), pos, capacity)
    out = jnp.full((columns.shape[0], capacity + 1), fill, columns.dtype)
    out = out.at[:, dest].set(columns, mode="drop")
    n_pass = jnp.sum(mask.astype(jnp.int32))
    return out[:, :capacity], jnp.minimum(n_pass, capacity)


def compact_fixed_argsort(columns: jnp.ndarray, mask: jnp.ndarray,
                          capacity: int, fill: float = 0.0):
    """Legacy ``O(R log R)`` compaction (mask → stable argsort → gather).

    Kept only as the baseline for ``benchmarks/ingest.py`` and the parity
    tests — production paths use the ``O(R)`` cumsum scatter above. Output
    is bit-identical to ``compact_fixed``.
    """
    if capacity < 1:
        raise ValueError("capacity must be >= 1")
    keep = jnp.logical_not(mask)
    order = jnp.argsort(keep, stable=True)        # survivors first, in order
    slots = jnp.arange(capacity, dtype=jnp.int32)
    idx = jnp.take(order, slots, mode="fill", fill_value=0)
    n_pass = jnp.sum(mask.astype(jnp.int32))
    valid = slots < n_pass
    packed = jnp.where(valid[None, :], columns[:, idx], fill)
    return packed, jnp.minimum(n_pass, capacity)
