"""Statistics scope policies (paper §2.2).

The paper weighs three lifetimes for the adaptive metadata (ranks + epoch
accumulators) and picks *per-executor*:

  per-task     — state dies with each task: too little evidence accumulates.
  centralized  — one global state at the driver: network traffic + contention.
  per-executor — JVM-global state per executor: long-lived, zero network
                 cost, and locally adaptive under heterogeneous data.

Mapping here: an "executor" is one data shard of the ingestion pipeline (one
host process, or one mesh data-row when the filter runs jitted under
``shard_map``). A "task" is one micro-batch step.

  PER_BATCH    — reset the epoch evidence every step (per-task analogue);
                 the monitor stride and the re-rank counter persist — they
                 are stream properties, not evidence.
  PER_SHARD    — default; state persists per shard, NO collectives: the
                 lowered HLO of the sharded filter step contains no
                 all-reduce (asserted by tests/test_sharded_filter.py),
                 matching the paper's "no data transferred through the
                 network".
  CENTRALIZED  — batch monitor counters are merged across the given mesh
                 axes so every shard accumulates identical global statistics
                 and adopts the global order at each epoch boundary. WHEN
                 they merge is the ``AdaptiveFilterConfig.exchange`` policy:

                   eager          — psum every step (one small 2P+G+1-float
                                    all-reduce per micro-batch; the original
                                    behaviour, still the default).
                   deferred       — accumulate locally, psum ONCE per
                                    ``calculate_rate`` rows at the epoch
                                    boundary (``exchange_update``); the
                                    per-step compiled module contains no
                                    all-reduce at all (HLO-pinned). Sums are
                                    associative, so the merged epoch totals
                                    — and hence the adopted perm — are
                                    IDENTICAL to eager's.
                   deferred-async — same single boundary collective, but its
                                    result is folded in one epoch LATE (the
                                    paper's deferred per-executor update
                                    generalized to the mesh), so the merge
                                    can overlap the next epoch's filter
                                    work.

``core.sharded.ShardedAdaptiveFilter`` is the execution layer that runs all
of it under real ``shard_map``.
"""

from __future__ import annotations

import enum
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.stats import FilterStats


class Scope(enum.Enum):
    PER_BATCH = "per_batch"
    PER_SHARD = "per_shard"
    CENTRALIZED = "centralized"


#: Statistics-exchange cadence for CENTRALIZED (see module docstring).
EXCHANGE_MODES = ("eager", "deferred", "deferred-async")


def reduce_stats(stats: FilterStats, scope: Scope,
                 axis_names: Sequence[str] = ()) -> FilterStats:
    """Apply the scope's reduction to epoch accumulators.

    Must be called inside ``shard_map``/``pmap`` for CENTRALIZED to see the
    named axes; PER_SHARD / PER_BATCH are identity (no communication).
    """
    if scope is Scope.CENTRALIZED and axis_names:
        return FilterStats(
            num_cut=jax.lax.psum(stats.num_cut, axis_names),
            cost_acc=jax.lax.psum(stats.cost_acc, axis_names),
            n_monitored=jax.lax.psum(stats.n_monitored, axis_names),
            group_cut=None if stats.group_cut is None
            else jax.lax.psum(stats.group_cut, axis_names),
            term_cut=None if stats.term_cut is None
            else jax.lax.psum(stats.term_cut, axis_names),
        )
    return stats


def scope_from_str(name: str) -> Scope:
    try:
        return Scope(name)
    except ValueError as exc:
        raise ValueError(
            f"unknown scope {name!r}; pick from "
            f"{[s.value for s in Scope]}") from exc
