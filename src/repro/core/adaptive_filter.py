"""The adaptive filter operator: the functional step math.

This is the framework's analogue of the paper's Catalyst extension: a
pipeline stage that can replace any static conjunctive (or CNF) filter.
The USER-FACING surface is the plan/session API (``core.plan.FilterPlan``
→ ``core.session.build_session`` → one ``session.step``); this class is
the math core a session compiles — ``step``/``_step_compact`` (and their
skip-tier variants) are pure functions of (state, batch) traced under
jit/shard_map.

All execution semantics live behind the ``FilterEngine`` registry
(``core/engine/``) and all ordering math in ``core.ordering`` /
``core.stats`` (one implementation, numpy or jnp via the ``xp`` namespace
argument) — this module only wires them together:

  cfg.adaptive=False  → behaves exactly like Spark's default Filter
                        (user-statement order, no monitoring) — the paper's
                        baseline, kept so both can be benchmarked.
  cfg.backend         → any registered engine: "jnp" (jit-able vectorized),
                        "pallas" (fused TPU kernel; interpret-mode on CPU),
                        "numpy" (row-exact host path used by benchmarks).
  cfg.cost_mode       → "static" (calibrated per-predicate weights; works
                        inside jit) or "measured" (host clock per predicate
                        per batch over the monitor sample — the paper's
                        System.nanoTime, at epoch granularity).
  cfg.exchange        → when CENTRALIZED: "eager" psum-merges monitor
                        counters every step; "deferred" accumulates locally
                        and issues ONE collective per ``calculate_rate``
                        rows at the epoch boundary (``exchange_update``,
                        driven by ``maybe_exchange``); "deferred-async"
                        additionally folds the merged stats in one epoch
                        LATE (the paper's deferred per-executor update,
                        generalized to the mesh).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Iterable, Iterator, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import ordering as ordering_lib
from repro.core import predicates as pred_lib
from repro.core.engine import MonitorSpec, get_engine
from repro.core.ordering import OrderingConfig, OrderState
from repro.core import skip_tier as skip_tier_lib
from repro.core.plan import validate_combo
from repro.core.scope import Scope, reduce_stats, scope_from_str
from repro.core.predicates import Predicate
from repro.core.stats import FilterStats

log = logging.getLogger(__name__)

CAPACITY_QUANTUM = 128   # auto capacities are multiples of this (VPU lanes)


def drive_exchange(owner, state: OrderState) -> OrderState:
    """Shared deferred-exchange driver (host side).

    ``owner`` is an ``AdaptiveFilter`` or ``ShardedAdaptiveFilter`` — any
    object with ``config.exchange``, ``exchange_due``, the two jitted
    exchange callables, and a ``_pending_stats`` slot. One implementation so
    the subtle deferred-async stash semantics (first boundary falls back to
    the synchronous merge; stash is transient across restores) cannot drift
    between the single and sharded drivers.
    """
    if not owner.exchange_due(state):
        return state
    if owner.config.exchange == "deferred-async" \
            and owner._pending_stats is not None:
        state, merged = owner.jit_exchange_with(state, owner._pending_stats)
    else:
        state, merged = owner.jit_exchange(state)
    owner._pending_stats = merged \
        if owner.config.exchange == "deferred-async" else None
    return state


@dataclasses.dataclass(frozen=True)
class AdaptiveFilterConfig:
    ordering: OrderingConfig = OrderingConfig()
    scope: str = "per_shard"
    cost_mode: str = "static"
    backend: str = "jnp"
    adaptive: bool = True
    # Device-side survivor compaction: ``step_compact`` packs survivors
    # into a padded fixed-width [C, capacity] buffer + count entirely on
    # device (fused in-kernel for pallas, O(R) cumsum scatter for jnp), so
    # downstream stages never host-boolean-index the batch.
    #   capacity None   → batch width (lossless)
    #   capacity int    → fixed width (survivors beyond it are dropped and
    #                     counted in ``StepMetrics.n_dropped``)
    #   capacity "auto" → derived from the monitor lane's observed
    #                     pass-rate × batch width × ``compact_slack``,
    #                     re-quantized to a multiple of 128 at epoch
    #                     boundaries (bounded jit-cache churn).
    compact_output: bool = False
    compact_capacity: int | str | None = None
    compact_slack: float = 1.5
    # Statistics exchange cadence for the CENTRALIZED scope (see module
    # docstring): "eager" | "deferred" | "deferred-async".
    exchange: str = "eager"
    # Tile-statistics skip tier (``core.skip_tier``): "off" | "zonemap" |
    # "zonemap+bloom" | "auto". Zone maps (+ Bloom bits) resolve whole
    # 128-row tiles before the row-level chain; never changes survivors or
    # ordering statistics, only speed. "auto" is driven by the session.
    skip_tier: str = "off"

    def __post_init__(self) -> None:
        # every cross-field rule lives in ONE place: core.plan.validate_combo
        # (the FilterPlan docstring is the single source of truth for valid
        # combinations; this config is the legacy per-filter surface).
        validate_combo(scope=self.scope, cost_mode=self.cost_mode,
                       backend=self.backend,
                       compact_output=self.compact_output,
                       compact_capacity=self.compact_capacity,
                       compact_slack=self.compact_slack,
                       exchange=self.exchange,
                       skip_tier=self.skip_tier)


class StepMetrics(NamedTuple):
    work_units: jnp.ndarray     # row-level cost-weighted work for this batch
    n_pass: jnp.ndarray         # surviving rows (mask popcount)
    perm: jnp.ndarray           # order used for this batch
    epoch: jnp.ndarray          # epochs completed so far
    adj_rank: jnp.ndarray       # current smoothed GROUP ranks
    n_dropped: jnp.ndarray      # survivors lost to compact_capacity overflow
    # skip-tier tile counters (i32; all zero when the tier is off)
    n_tiles_pass: jnp.ndarray       # tiles bulk-kept by the zone-map proof
    n_tiles_fail: jnp.ndarray       # tiles dropped without row-level work
    n_tiles_ambiguous: jnp.ndarray  # tiles that reached the row-level chain


class AdaptiveFilter:
    """Adaptive CNF filter with epoch-based predicate/group reordering."""

    def __init__(self, predicates: Sequence[Predicate],
                 config: AdaptiveFilterConfig | None = None,
                 axis_names: Sequence[str] = ()):
        if not predicates:
            raise ValueError("need at least one predicate")
        self.predicates = list(predicates)
        self.config = config or AdaptiveFilterConfig()
        self.specs = pred_lib.pack(self.predicates)
        self.groups = self.specs.groups          # static CNF structure
        self.axis_names = tuple(axis_names)
        self._scope = scope_from_str(self.config.scope)
        self._engine = get_engine(self.config.backend)
        # the jit-traceable engine driving ``step`` (host engines run via
        # ``process_stream``; step falls back to the jnp reference engine)
        self._step_engine = self._engine if self._engine.traceable \
            else get_engine("jnp")
        self._jit_step = None
        self._jit_step_compact = None
        self._jit_step_triage = None
        self._jit_step_skip = None
        self._jit_step_skip_compact = None
        self._jit_exchange = None
        self._jit_exchange_with = None
        # deferred-async: merged stats from the previous boundary, applied
        # one epoch late (host-held; transient across checkpoint restarts —
        # the first post-restore boundary falls back to synchronous merge).
        self._pending_stats: FilterStats | None = None
        # auto-capacity: current quantized width + last epoch it was tuned
        self._auto_cap: int | None = None
        self._auto_cap_epoch = 0

    # ---------------------------------------------------------------- state
    def init_state(self, xp=jnp) -> OrderState:
        return ordering_lib.init_order_state(
            len(self.predicates), self.specs.n_groups, xp=xp)

    @property
    def jit_step(self):
        """``jax.jit(self.step)``, compiled once per instance and reused."""
        if self._jit_step is None:
            self._jit_step = jax.jit(self.step)
        return self._jit_step

    @property
    def _jit_compact(self):
        """Jitted ``_step_compact``; ``capacity`` is static (one compile per
        distinct quantized width — auto mode changes it only at epoch
        boundaries, in multiples of 128)."""
        if self._jit_step_compact is None:
            self._jit_step_compact = jax.jit(
                self._step_compact, static_argnames=("capacity",))
        return self._jit_step_compact

    # ------------------------------------------------------------ skip tier
    @property
    def _jit_triage(self):
        """Jitted zone-map triage; ``bloom`` is static (two traces max)."""
        if self._jit_step_triage is None:
            self._jit_step_triage = jax.jit(
                lambda columns, bloom: self._step_engine.triage(
                    columns, self.specs, bloom=bloom),
                static_argnames=("bloom",))
        return self._jit_step_triage

    @property
    def _jit_skip(self):
        """Jitted ``_step_skip``; ``amb_cap`` is static (quantized widths)."""
        if self._jit_step_skip is None:
            self._jit_step_skip = jax.jit(
                self._step_skip, static_argnames=("amb_cap",))
        return self._jit_step_skip

    @property
    def _jit_skip_compact(self):
        if self._jit_step_skip_compact is None:
            self._jit_step_skip_compact = jax.jit(
                self._step_skip_compact,
                static_argnames=("capacity", "amb_cap"))
        return self._jit_step_skip_compact

    def skip_on_mode(self) -> str:
        """The arm ``skip_tier="auto"`` tunes against "off": Bloom bits only
        pay when the chain has an equality predicate to consult them."""
        return "zonemap+bloom" \
            if any(p.op == pred_lib.OP_EQ for p in self.predicates) \
            else "zonemap"

    def skip_amb_cap(self, info, n_rows: int) -> int:
        """Static gather width (tiles) for one step — 0 when the engine
        predicates in-kernel instead of gathering (no host sync needed)."""
        if not getattr(self._step_engine, "skip_gathers", False):
            return 0
        n_tiles = -(-n_rows // skip_tier_lib.SKIP_TILE)
        with obs.sync("AdaptiveFilter.skip_amb_cap"):
            n_amb = int(info.n_ambiguous)
        return skip_tier_lib.quantize_amb_cap(n_amb, n_tiles)

    # ----------------------------------------------------------- jit'd step
    def _advance_state(self, state: OrderState, res, costs,
                       n_rows: int) -> OrderState:
        """Fold one batch's monitor evidence into the order state."""
        cfg = self.config
        if not cfg.adaptive:
            return state._replace(
                sample_phase=(state.sample_phase + n_rows)
                % cfg.ordering.collect_rate)
        if self._scope is Scope.PER_BATCH:
            # per-task analogue: evidence dies with the batch — but the
            # monitor lane's stride and the re-rank counter are *stream*
            # properties, not evidence. Resetting sample_phase too would
            # make every batch sample the same row offsets (correlation
            # bias the deterministic stride exists to avoid).
            state = self.init_state()._replace(
                sample_phase=state.sample_phase, epoch=state.epoch)
        cut, gcut, n_mon = (res.cut_counts, res.group_cut_counts,
                            res.n_monitored)
        deferred = self.exchange_deferred
        if (self._scope is Scope.CENTRALIZED and self.axis_names
                and not deferred):
            merged = reduce_stats(
                FilterStats(cut, costs, n_mon, gcut), self._scope,
                self.axis_names)
            cut, costs, n_mon, gcut = (merged.num_cut, merged.cost_acc,
                                       merged.n_monitored, merged.group_cut)
        return ordering_lib.advance(
            state, cfg.ordering, cut, costs, n_mon, n_rows=n_rows,
            group_cut=gcut, groups=self.groups, defer_epoch=deferred)

    def _metrics(self, res, perm, new_state, n_dropped=None) -> StepMetrics:
        return StepMetrics(
            work_units=res.work_units,
            n_pass=jnp.sum(res.mask.astype(jnp.int32)),
            perm=perm,
            epoch=new_state.epoch,
            adj_rank=new_state.adj_rank,
            n_dropped=jnp.zeros((), jnp.int32) if n_dropped is None
            else n_dropped,
            # concrete i32 arrays always (ChainResult defaults them to the
            # python int 0, which tree ops downstream cannot stack)
            n_tiles_pass=jnp.asarray(res.n_tiles_pass, jnp.int32),
            n_tiles_fail=jnp.asarray(res.n_tiles_fail, jnp.int32),
            n_tiles_ambiguous=jnp.asarray(res.n_tiles_ambiguous, jnp.int32),
        )

    def _perm(self, state: OrderState):
        return state.perm if self.config.adaptive else jnp.arange(
            len(self.predicates), dtype=jnp.int32)

    def _monitor_spec(self, state: OrderState) -> MonitorSpec:
        return MonitorSpec(collect_rate=self.config.ordering.collect_rate,
                           sample_phase=state.sample_phase)

    def step(self, state: OrderState, columns: jnp.ndarray,
             measured_costs: jnp.ndarray | None = None
             ) -> tuple[OrderState, jnp.ndarray, StepMetrics]:
        """One micro-batch: filter + monitor + (maybe) epoch re-rank.

        ``columns``: f32[C, R]. jit/shard_map-compatible for traceable
        engines. Returns (new_state, mask bool[R], metrics).
        """
        perm = self._perm(state)
        res = self._step_engine.run_chain(
            columns, self.specs, perm, self._monitor_spec(state))
        costs = res.monitor_cost if measured_costs is None else measured_costs
        new_state = self._advance_state(state, res, costs,
                                        int(columns.shape[1]))
        return new_state, res.mask, self._metrics(res, perm, new_state)

    def _step_skip(self, state: OrderState, columns: jnp.ndarray,
                   pass_tiles, fail_tiles, *, amb_cap: int
                   ) -> tuple[OrderState, jnp.ndarray, StepMetrics]:
        """``step`` behind the zone-map skip tier.

        ``pass_tiles``/``fail_tiles`` come from ``_jit_triage`` on the same
        batch; ``amb_cap`` (static) from ``skip_amb_cap`` — the one host
        sync of the tier. Ordering statistics advance identically to
        ``step``: the monitor lane runs row-level on the full batch.
        """
        perm = self._perm(state)
        skip = skip_tier_lib.SkipInfo(pass_tiles, fail_tiles, None)
        res = self._step_engine.run_chain_skip(
            columns, self.specs, perm, self._monitor_spec(state), skip,
            amb_cap=amb_cap)
        new_state = self._advance_state(state, res, res.monitor_cost,
                                        int(columns.shape[1]))
        return new_state, res.mask, self._metrics(res, perm, new_state)

    def _step_skip_compact(self, state: OrderState, columns: jnp.ndarray,
                           pass_tiles, fail_tiles, *, amb_cap: int,
                           capacity: int):
        """``_step_compact`` behind the zone-map skip tier."""
        perm = self._perm(state)
        skip = skip_tier_lib.SkipInfo(pass_tiles, fail_tiles, None)
        res, packed, n_kept = self._step_engine.run_chain_compact_skip(
            columns, self.specs, perm, self._monitor_spec(state), skip,
            amb_cap=amb_cap, capacity=capacity)
        new_state = self._advance_state(state, res, res.monitor_cost,
                                        int(columns.shape[1]))
        n_pass = jnp.sum(res.mask.astype(jnp.int32))
        metrics = self._metrics(res, perm, new_state,
                                n_dropped=n_pass - n_kept)
        return new_state, packed, n_kept, res.mask, metrics

    def _step_compact(self, state: OrderState, columns: jnp.ndarray,
                      measured_costs: jnp.ndarray | None = None,
                      *, capacity: int | None = None):
        """``step`` + single-pass device-side survivor compaction.

        Returns (new_state, packed f32[C, cap], n_kept i32[], mask bool[R],
        metrics). ``packed[:, :n_kept]`` is bit-identical to the host
        boolean-mask path ``columns[:, mask]`` (up to padding) but never
        leaves the device unpacked — and never takes a second full-width
        pass over HBM: the pallas engine packs survivors in-kernel while
        each tile is in VMEM, the jnp engine fuses an O(R) cumsum scatter
        (no argsort). jit/shard_map-compatible; ``capacity`` must be static
        under jit (``_jit_compact`` handles that).
        """
        if capacity is None:
            if self.config.compact_capacity == "auto":
                # capacity=None bakes the width into the trace and the jit
                # cache would never see later re-tunes — auto callers must
                # thread resolve_capacity() per call (the pipelines do).
                raise ValueError(
                    "compact_capacity='auto' needs an explicit per-call "
                    "capacity: pass capacity=filt.resolve_capacity(n_rows)")
            capacity = self.resolve_capacity(int(columns.shape[1]))
        cap = capacity
        perm = self._perm(state)
        res, packed, n_kept = self._step_engine.run_chain_compact(
            columns, self.specs, perm, self._monitor_spec(state),
            capacity=cap)
        costs = res.monitor_cost if measured_costs is None else measured_costs
        new_state = self._advance_state(state, res, costs,
                                        int(columns.shape[1]))
        n_pass = jnp.sum(res.mask.astype(jnp.int32))
        metrics = self._metrics(res, perm, new_state,
                                n_dropped=n_pass - n_kept)
        return new_state, packed, n_kept, res.mask, metrics

    # --------------------------------------------------- capacity auto-tune
    def resolve_capacity(self, n_rows: int) -> int:
        """Current compaction width for an ``n_rows``-wide batch."""
        cap = self.config.compact_capacity
        if cap is None:
            return n_rows
        if cap == "auto":
            return min(self._auto_cap, n_rows) if self._auto_cap else n_rows
        return int(cap)

    def observe_for_capacity(self, evidence_state: OrderState,
                             new_state: OrderState, n_rows: int) -> None:
        """Host hook: re-derive the auto capacity at epoch boundaries.

        ``evidence_state`` is the state whose ``stats`` still hold the
        (almost) full epoch's monitor accumulators — i.e. the state BEFORE
        the step/exchange that fired the boundary. Estimated pass-rate =
        Π_g S_g over the exact per-group selectivities; correlation between
        groups is absorbed by ``compact_slack``. No-op unless
        ``compact_capacity="auto"`` and an epoch boundary was crossed.
        """
        if self.config.compact_capacity != "auto":
            return
        with obs.sync("AdaptiveFilter.observe_for_capacity"):
            epoch = int(np.max(np.asarray(new_state.epoch)))
            if epoch <= self._auto_cap_epoch:
                return
            self._auto_cap_epoch = epoch
            stats = jax.tree.map(np.asarray, evidence_state.stats)
            n_mon = np.maximum(np.asarray(stats.n_monitored, np.float64),
                               0.0)
            if np.max(n_mon) <= 0.0:
                return                  # no evidence — keep current width
            gcut = np.asarray(stats.group_cut, np.float64)
            sel = np.clip(1.0 - gcut / np.maximum(n_mon, 1.0)[..., None],
                          0.0, 1.0)
            pass_rate = float(np.max(np.prod(sel, axis=-1)))  # over shards
            want = pass_rate * n_rows * self.config.compact_slack
            quant = int(np.ceil(want / CAPACITY_QUANTUM)) * CAPACITY_QUANTUM
            self._auto_cap = int(np.clip(quant, CAPACITY_QUANTUM, n_rows))

    # ------------------------------------------------------ deferred epochs
    @property
    def exchange_deferred(self) -> bool:
        """True when epoch boundaries are driver-owned (deferred modes)."""
        return (self.config.adaptive and self.config.exchange != "eager"
                and self._scope is Scope.CENTRALIZED)

    def exchange_due(self, state: OrderState) -> bool:
        """Host-side boundary check for the deferred-exchange driver."""
        if not self.exchange_deferred:
            return False
        with obs.sync("AdaptiveFilter.exchange_due"):
            rows = int(np.max(np.asarray(state.rows_into_epoch)))
        return rows >= self.config.ordering.calculate_rate

    def exchange_update(self, state: OrderState,
                        use_stats: FilterStats | None = None
                        ) -> tuple[OrderState, FilterStats]:
        """One epoch boundary: merge stats across the mesh, re-rank.

        The ONLY collective of the deferred CENTRALIZED mode lives here —
        one psum of (2P + G + 1) floats per ``calculate_rate`` rows, issued
        from a separate jitted call so the per-step module compiles with no
        all-reduce at all. Returns (new_state, merged_stats); with
        ``use_stats`` the re-rank consumes those (one-epoch-stale) stats
        instead while the freshly merged ones are returned for the next
        boundary (deferred-async).
        """
        merged = state.stats
        if self.axis_names:
            merged = reduce_stats(merged, Scope.CENTRALIZED, self.axis_names)
        new_state = ordering_lib.boundary_update(
            state, self.config.ordering, groups=self.groups,
            stats_override=merged if use_stats is None else use_stats)
        return new_state, merged

    @property
    def jit_exchange(self):
        if self._jit_exchange is None:
            self._jit_exchange = jax.jit(lambda s: self.exchange_update(s))
        return self._jit_exchange

    @property
    def jit_exchange_with(self):
        if self._jit_exchange_with is None:
            self._jit_exchange_with = jax.jit(
                lambda s, st: self.exchange_update(s, st))
        return self._jit_exchange_with

    def maybe_exchange(self, state: OrderState) -> OrderState:
        """Drive the deferred epoch boundary if one is due (host helper).

        Eager mode / off-boundary: returns ``state`` unchanged. In
        "deferred-async" the merged stats are stashed and applied at the
        NEXT boundary (first boundary degenerates to the synchronous
        merge), overlapping the collective with an epoch of filter work.
        """
        return drive_exchange(self, state)

    # ------------------------------------------------------- host streaming
    def process_stream(self, batches: Iterable[np.ndarray]
                       ) -> Iterator[tuple[np.ndarray, np.ndarray, dict]]:
        """Drive the filter over a host-side stream of f32[C, R] batches.

        Yields (surviving_rows f32[C, n_pass], mask, metrics_dict) per batch.
        Uses the configured host engine when one is selected (row-exact wall
        time, measured costs); otherwise calls the jitted step. Under
        ``compact_output`` the survivors come back through the device-side
        packed buffer; overflow (``n_dropped``) is surfaced in the metrics
        dict and warned about once per offending batch.
        """
        if not self._engine.traceable:
            yield from self._process_stream_host(batches)
            return

        # the session owns ALL of the driving (jit dispatch, capacity
        # resolution, deferred exchange, auto-retune, overflow warning,
        # metrics encoding) — this loop is a thin host iterator over it.
        # A FRESH session per invocation: each drives exactly one state
        # stream (its deferred-boundary row counter must not be shared
        # with a pipeline or another stream over the same filter; the jit
        # caches live on the filter and stay shared).
        from repro.core.session import FilterSession
        session = FilterSession.from_filter(self)
        state = session.init_state()
        for batch in batches:
            state, res = session.step(state, batch)
            yield res.survivors(batch), res.mask_np, res.metrics_dict()

    def _process_stream_host(self, batches):
        """Host streaming loop: SAME ordering math as the jitted step, run
        through ``ordering.advance(..., xp=numpy)`` — no host-side mirror."""
        cfg = self.config
        n_preds = len(self.predicates)
        state = self.init_state(xp=np)
        defer = self.exchange_deferred
        for batch in batches:
            perm = state.perm if cfg.adaptive else np.arange(n_preds)
            monitor = MonitorSpec(collect_rate=cfg.ordering.collect_rate,
                                  sample_phase=int(state.sample_phase),
                                  cost_mode=cfg.cost_mode)
            if cfg.skip_tier != "off":
                # "auto" is rejected for host engines by validate_combo;
                # the host engine triages internally (skip=None)
                res = self._engine.run_chain_skip(
                    batch, self.specs, perm, monitor,
                    bloom=cfg.skip_tier == "zonemap+bloom")
            else:
                res = self._engine.run_chain(batch, self.specs, perm,
                                             monitor)
            if cfg.adaptive:
                state = ordering_lib.advance(
                    state, cfg.ordering, res.cut_counts, res.monitor_cost,
                    res.n_monitored, n_rows=batch.shape[1],
                    group_cut=res.group_cut_counts, groups=self.groups,
                    xp=np, defer_epoch=defer)
                if defer and state.rows_into_epoch >= \
                        cfg.ordering.calculate_rate:
                    # no mesh on the host path: the "exchange" is the
                    # identity merge — the boundary cadence still matches
                    # the deferred device path.
                    state = ordering_lib.boundary_update(
                        state, cfg.ordering, groups=self.groups, xp=np)
            else:
                state = state._replace(
                    sample_phase=(state.sample_phase + batch.shape[1])
                    % cfg.ordering.collect_rate)
            yield batch[:, res.mask], res.mask, {
                "work_units": float(res.work_units),
                "n_pass": int(res.mask.sum()),
                "perm": [int(i) for i in perm],
                "epoch": int(state.epoch),
                "n_dropped": 0,
                "n_tiles_skipped_pass": int(res.n_tiles_pass),
                "n_tiles_skipped_fail": int(res.n_tiles_fail),
                "n_tiles_ambiguous": int(res.n_tiles_ambiguous),
            }


def static_filter(predicates: Sequence[Predicate],
                  order: Sequence[int] | None = None,
                  backend: str = "jnp") -> AdaptiveFilter:
    """Spark's default Filter: fixed order, no monitoring overhead.

    ``order`` permutes the user statement order up-front (used by the fig-1
    benchmark to sweep all 24 static orders).
    """
    preds = list(predicates)
    if order is not None:
        preds = [preds[i] for i in order]
    cfg = AdaptiveFilterConfig(adaptive=False, backend=backend)
    return AdaptiveFilter(preds, cfg)
