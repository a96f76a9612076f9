"""``numpy`` engine: row-exact compacted host path (wall-clock-true).

The one engine that is NOT jit-traceable: it runs python loops over numpy
arrays so that (a) wall time genuinely tracks the evaluation order and
(b) the monitor lane can measure real per-predicate seconds
(``cost_mode="measured"`` — the paper's System.nanoTime analogue).
"""

from __future__ import annotations

import numpy as np

from repro.core import engine as engine_lib
from repro.core import np_exec
from repro.core.engine.base import ChainResult, MonitorSpec
from repro.core.predicates import Predicate, PredicateSpecs, _OP_NAMES


def _preds_from_specs(specs: PredicateSpecs) -> list[Predicate]:
    """Host-side view of the packed ABI (cheap: P is small)."""
    col = np.asarray(specs.column)
    op = np.asarray(specs.op)
    t1 = np.asarray(specs.t1)
    t2 = np.asarray(specs.t2)
    rounds = np.asarray(specs.rounds)
    cost = np.asarray(specs.static_cost)
    return [Predicate(name=f"p{i}_{_OP_NAMES[int(op[i])]}",
                      column=int(col[i]), op=int(op[i]),
                      t1=float(t1[i]), t2=float(t2[i]),
                      rounds=int(rounds[i]), static_cost=float(cost[i]))
            for i in range(specs.n)]


@engine_lib.register("numpy")
class NumpyEngine:
    """Compacted short-circuit CNF chain on the host (Spark's processNext)."""

    traceable = False
    supports_skip = True
    skip_gathers = False     # host path indexes ambiguous rows directly

    def _monitor(self, columns, preds, monitor: MonitorSpec, specs):
        cut, group_cut, n_mon, secs, term_cut = np_exec.run_monitor_np(
            columns, preds, monitor.collect_rate,
            int(monitor.sample_phase), groups=specs.groups, terms=specs.term)
        if monitor.cost_mode == "measured":
            monitor_cost = secs
        else:
            monitor_cost = np.asarray(
                [p.static_cost for p in preds], np.float64) * n_mon
        return cut, group_cut, n_mon, monitor_cost, \
            term_cut.astype(np.float32)

    def run_chain(self, columns, specs, perm,
                  monitor: MonitorSpec) -> ChainResult:
        columns = np.asarray(columns)
        preds = _preds_from_specs(specs)
        perm = np.asarray(perm)

        mask, work, active_before, atom_tiles = np_exec.run_chain_np(
            columns, preds, perm, groups=specs.groups, terms=specs.term)
        cut, group_cut, n_mon, monitor_cost, term_cut = self._monitor(
            columns, preds, monitor, specs)
        return ChainResult(
            mask=mask,
            work_units=np.float32(work),
            active_before=active_before,
            cut_counts=cut.astype(np.float32),
            n_monitored=np.float32(n_mon),
            monitor_cost=monitor_cost.astype(np.float32),
            group_cut_counts=group_cut.astype(np.float32),
            term_cut_counts=term_cut,
            n_atom_tiles=np.int32(atom_tiles),
        )

    # ------------------------------------------------------- skip tier
    def triage(self, columns, specs, *, bloom: bool):
        """Reference zone-map/Bloom triage (shared math, xp=numpy)."""
        from repro.core import skip_tier
        return skip_tier.triage(np.asarray(columns), specs, bloom=bloom,
                                xp=np)

    def run_chain_skip(self, columns, specs, perm, monitor: MonitorSpec,
                       skip=None, *, bloom: bool = False,
                       amb_cap: int = 0) -> ChainResult:
        """Row-exact reference of the skip tier: decided 128-row tiles
        bypass ``run_chain_np``; only ambiguous tiles' rows are evaluated
        (and charged). ``skip=None`` computes the triage internally (host
        streaming path); ``amb_cap`` is ignored — the host indexes the
        ambiguous rows directly. Monitor lane: full batch, unchanged."""
        from repro.core import skip_tier

        columns = np.asarray(columns)
        if skip is None:
            skip = self.triage(columns, specs, bloom=bloom)
        preds = _preds_from_specs(specs)
        groups = specs.groups
        perm = np.asarray(perm)
        n_rows = columns.shape[1]
        tile = skip_tier.SKIP_TILE

        pass_t = np.asarray(skip.pass_tiles)
        fail_t = np.asarray(skip.fail_tiles)
        amb_tiles = np.nonzero(~(pass_t | fail_t))[0]
        rows = (amb_tiles[:, None] * tile +
                np.arange(tile)[None, :]).reshape(-1)
        rows = rows[rows < n_rows]

        sub_mask, work, active_before, _ = np_exec.run_chain_np(
            columns[:, rows], preds, perm, groups=groups, count_tiles=False)
        mask = np.zeros(n_rows, bool)
        prows = (np.nonzero(pass_t)[0][:, None] * tile +
                 np.arange(tile)[None, :]).reshape(-1)
        mask[prows[prows < n_rows]] = True
        mask[rows] = sub_mask

        cut, group_cut, n_mon, monitor_cost, _ = self._monitor(
            columns, preds, monitor, specs)
        n_pass_t, n_fail_t, n_amb_t = skip_tier.tile_counters(skip, np)
        return ChainResult(
            mask=mask,
            work_units=np.float32(work),
            active_before=active_before,
            cut_counts=cut.astype(np.float32),
            n_monitored=np.float32(n_mon),
            monitor_cost=monitor_cost.astype(np.float32),
            group_cut_counts=group_cut.astype(np.float32),
            n_tiles_pass=np.int32(n_pass_t),
            n_tiles_fail=np.int32(n_fail_t),
            n_tiles_ambiguous=np.int32(n_amb_t),
        )
