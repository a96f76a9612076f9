"""Epoch controller: collectRate sampling, calculateRate epochs, momentum.

Functional port of the paper's per-executor metadata (§2.2). One
``OrderState`` is the JVM-global state of one Spark executor; in the JAX
pipeline one lives per data shard (see ``scope.py``). Because the state is
threaded functionally through ``jax.lax`` control flow, the paper's lock is
unnecessary here — exactly one epoch update happens per boundary by
construction. (The thread/lock semantics of real Spark executors, including
deferred updates, are reproduced separately in ``executor_sim.py``.)

Counters are kept modulo the relevant rates in int32 so the state never
overflows on unbounded streams (the paper's counters are JVM longs; we keep
an epoch counter + in-epoch offsets instead, which is equivalent and
checkpoint-friendly).

Backend-agnostic: every function takes an array-namespace ``xp``
(``jax.numpy`` for the jitted device path, ``numpy`` for host streaming) and
runs the SAME code on both — this module is the single source of truth for
the ordering math; there is no host-side mirror. The only divergence is the
epoch-boundary conditional, which lowers to ``jax.lax.cond`` under jnp and a
plain python branch under numpy.

CNF (AND of OR-groups): ranks are computed per *group* (selectivity =
exact P(group passes) from the monitor lane, cost = Σ member costs) and
momentum-smoothed at group granularity; members are ordered within their
group by miss-rate each epoch. For flat chains (all singleton groups) this
reduces bit-exactly to the paper's per-predicate ordering.

Trees (AND-terms inside OR-groups, ``terms`` given): groups keep their
momentum-smoothed ranks, with a group's cost its expected cost under the
term order; terms are ordered inside each group and atoms inside each term
every epoch, from the same epoch's evidence (``stats.tree_levels``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import predicates as pred_lib
from repro.core import stats as stats_lib
from repro.core.stats import FilterStats


@dataclasses.dataclass(frozen=True)
class OrderingConfig:
    """Table 1 of the paper (defaults reproduced verbatim)."""

    collect_rate: int = 1000        # sample 1 row in every collect_rate
    calculate_rate: int = 1_000_000  # re-rank after this many rows
    momentum: float = 0.3            # past-preservation factor
    # Beyond-paper (EXPERIMENTS §Perf): snap-on-flip. Momentum smooths noisy
    # epochs but delays regime changes; if the CURRENT order's expected
    # per-row cost under the FRESH epoch stats exceeds snap_threshold × the
    # fresh-optimal order's cost, the update bypasses momentum entirely.
    # 0.0 disables (paper-faithful default).
    snap_threshold: float = 0.0

    def __post_init__(self) -> None:
        if self.collect_rate < 1:
            raise ValueError("collect_rate must be >= 1")
        if self.calculate_rate < 1:
            raise ValueError("calculate_rate must be >= 1")
        if not 0.0 <= self.momentum < 1.0:
            raise ValueError("momentum must be in [0, 1)")
        if self.snap_threshold < 0.0:
            raise ValueError("snap_threshold must be >= 0")


class OrderState(NamedTuple):
    """The adaptive filter's full mutable state (checkpointable pytree).

    Arrays are jnp on the device path and numpy on the host path; the shapes
    and dtypes match element-wise (f32/i32), so checkpoints round-trip.
    """

    perm: Any          # i32[P] current evaluation order (groups contiguous)
    adj_rank: Any      # f32[G] momentum-smoothed GROUP ranks (G == P if flat)
    stats: FilterStats  # accumulators for the current epoch
    rows_into_epoch: Any   # i32[] rows processed since last re-rank
    sample_phase: Any      # i32[] global row offset mod collect_rate
    epoch: Any             # i32[] completed epochs (0 → no history yet)
    group_perm: Any = None  # i32[G] current group evaluation order


def init_order_state(n_predicates: int, n_groups: int | None = None,
                     xp=jnp, n_terms: int = 0) -> OrderState:
    """Initial order = the user-given statement order, as in Spark."""
    if n_groups is None:
        n_groups = n_predicates
    return OrderState(
        perm=xp.arange(n_predicates, dtype=xp.int32),
        adj_rank=xp.zeros((n_groups,), xp.float32),
        stats=stats_lib.init_stats(n_predicates, n_groups, xp=xp,
                                   n_terms=n_terms),
        rows_into_epoch=xp.zeros((), xp.int32),
        sample_phase=xp.zeros((), xp.int32),
        epoch=xp.zeros((), xp.int32),
        group_perm=xp.arange(n_groups, dtype=xp.int32),
    )


def _default_groups(state: OrderState) -> tuple:
    return tuple(range(int(state.perm.shape[0])))


def epoch_update(state: OrderState, cfg: OrderingConfig,
                 groups: tuple | None = None, xp=jnp,
                 terms: tuple | None = None) -> OrderState:
    """Re-rank at an epoch boundary; reset accumulators; keep momentum memory.

    ``groups`` is the static CNF structure (dense group id per predicate);
    None means all-singleton groups (flat conjunction). ``terms`` is the
    static term structure of a tree (dense term id per predicate); None
    or () for chains without terms.

    Guard: if the epoch collected no monitored rows (possible with tiny
    epochs), keep the previous order — reordering on zero evidence is the
    kind of thrash the momentum term exists to prevent.
    """
    groups = tuple(groups) if groups is not None else _default_groups(state)
    n_preds = int(state.perm.shape[0])
    n_groups = int(state.adj_rank.shape[0])
    have_evidence = state.stats.n_monitored > 0.0

    if terms:
        term_members, group_terms = pred_lib.tree_structure(groups, terms)
        rank_now, term_rank, atom_rank, gnc = stats_lib.tree_levels(
            state.stats, term_members, group_terms, xp=xp)
    else:
        rank_now = stats_lib.group_ranks(state.stats, groups, xp=xp)
    adj = stats_lib.momentum_update(state.adj_rank, rank_now, cfg.momentum,
                                    first_epoch=state.epoch == 0, xp=xp)
    if cfg.snap_threshold > 0.0:
        nc = gnc if terms else stats_lib.group_normalized_costs(
            state.stats, groups, xp=xp)
        s = stats_lib.group_selectivities(state.stats, xp=xp)
        cost_cur = stats_lib.expected_chain_cost(nc, s, state.group_perm,
                                                 xp=xp)
        fresh = stats_lib.order_from_ranks(rank_now, xp=xp)
        cost_fresh = stats_lib.expected_chain_cost(nc, s, fresh, xp=xp)
        snap = cost_cur > cfg.snap_threshold * cost_fresh
        adj = xp.where(snap, rank_now, adj)
    if terms:
        new_perm, new_group_perm = stats_lib.tree_order(
            adj, term_rank, atom_rank, groups, terms, xp=xp)
    else:
        mrank = stats_lib.member_ranks(state.stats, xp=xp)
        new_perm, new_group_perm = stats_lib.cnf_order(adj, mrank, groups,
                                                       xp=xp)

    perm = xp.where(have_evidence, new_perm, state.perm)
    group_perm = xp.where(have_evidence, new_group_perm, state.group_perm)
    adj_rank = xp.where(have_evidence, adj, state.adj_rank)
    epoch = state.epoch + xp.where(have_evidence, 1, 0).astype(xp.int32)

    return OrderState(
        perm=perm,
        adj_rank=adj_rank,
        stats=stats_lib.init_stats(n_preds, n_groups, xp=xp,
                                   n_terms=len(set(terms or ()))),
        rows_into_epoch=xp.zeros((), xp.int32),
        sample_phase=state.sample_phase,
        epoch=epoch,
        group_perm=group_perm,
    )


def advance(state: OrderState, cfg: OrderingConfig,
            cut_counts, costs, n_monitored, n_rows: int,
            group_cut=None, groups: tuple | None = None,
            xp=jnp, defer_epoch: bool = False, term_cut=None,
            terms: tuple | None = None) -> OrderState:
    """Fold one batch's monitor results in; fire the epoch boundary if crossed.

    Epoch boundaries are honored at batch granularity (a batch is the unit of
    work, like a Spark task's row group); with batch ≪ calculate_rate this is
    the paper's behavior. ``n_rows`` must be a static python int (batch
    shape), so the modulo bookkeeping stays in int32 regardless of stream
    length.

    ``defer_epoch=True`` (static) accumulates evidence but NEVER fires the
    boundary — the caller owns it (deferred epoch exchange: the driver calls
    ``exchange_update`` once per ``calculate_rate`` rows, merging stats
    across the mesh in ONE collective instead of one per step; the per-step
    compiled module then contains no all-reduce at all).
    """
    new_stats = stats_lib.accumulate(state.stats, cut_counts, costs,
                                     n_monitored, group_cut=group_cut, xp=xp,
                                     term_cut=term_cut)
    rows = state.rows_into_epoch + xp.asarray(n_rows, xp.int32)
    state = state._replace(
        stats=new_stats,
        rows_into_epoch=rows,
        sample_phase=(state.sample_phase + n_rows) % cfg.collect_rate,
    )
    if defer_epoch:
        return state

    def fire(s: OrderState) -> OrderState:
        updated = epoch_update(s, cfg, groups=groups, xp=xp, terms=terms)
        # carry the overshoot so epoch length is exact on average
        return updated._replace(rows_into_epoch=s.rows_into_epoch % cfg.calculate_rate)

    if xp is jnp:
        return jax.lax.cond(rows >= cfg.calculate_rate, fire, lambda s: s,
                            state)
    return fire(state) if rows >= cfg.calculate_rate else state


def boundary_update(state: OrderState, cfg: OrderingConfig,
                    groups: tuple | None = None, xp=jnp,
                    stats_override: FilterStats | None = None,
                    terms: tuple | None = None) -> OrderState:
    """Explicit epoch-boundary update for the deferred-exchange path.

    Equivalent to the ``fire`` branch of ``advance`` — re-rank, reset
    accumulators, keep the row overshoot — but driven by the caller instead
    of the per-step conditional. ``stats_override`` substitutes the evidence
    used for the re-rank (the psum-merged global stats under deferred
    CENTRALIZED, or the one-epoch-stale merged stats under deferred-async).
    """
    if stats_override is not None:
        state = state._replace(stats=stats_override)
    updated = epoch_update(state, cfg, groups=groups, xp=xp, terms=terms)
    return updated._replace(
        rows_into_epoch=state.rows_into_epoch % cfg.calculate_rate)
