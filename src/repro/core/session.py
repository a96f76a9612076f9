"""FilterSession: a compiled FilterPlan with ONE step entry point.

``build_session(plan, mesh=None)`` compiles a declarative ``FilterPlan``
(core/plan.py) into a ``FilterSession`` that owns the jitted step /
exchange / retune callables and exposes exactly one

    state, result = session.step(state, batch)

for every engine × scope × compaction × exchange × tokenize combination —
the plan-then-compile shape of adaptive stream engines (Strider, arXiv
1705.05688), with the adaptivity itself a drop-in primitive (Cuttlefish,
arXiv 1802.09180). All of the driving logic — capacity resolution, the
skip-tier triage/tuner, deferred epoch exchange, auto-capacity retune,
overflow accounting, JSON metrics — lives here exactly once.

``StepResult`` is the uniform step ABI replacing the four divergent legacy
return shapes (mask-only, packed+count, sharded variants):

    mask      bool[R] | bool[S·R]   rows passing the chain (always)
    packed    f32[C, cap] | f32[S, C, cap] | None   compacted survivors
    n_kept    i32[] | i32[S] | None  survivors kept per shard (compaction)
    tokens    i32[N] | None          packed device token stream (tokenize)
    n_tokens  i32[] | None           live prefix length of ``tokens``
    metrics   StepMetrics            per-group monitor stats, ``n_dropped``
                                     (leading shard axis when sharded)

Checkpoints are versioned (schema v2: plan fingerprint + shard layout +
state arrays) and **elastic**: ``restore_state`` accepts a blob written on
S shards into a session over S′ shards. Epoch accumulators are sums, so
the S→S′ split/merge is exact (bit-exact for power-of-two rescale); ranks
and permutations are re-derived from the merged statistics when the source
shards disagree. Unversioned v1 blobs (the raw ``fstate_to_arrays`` dicts
every pre-session checkpoint holds) still load.
"""

from __future__ import annotations

import logging
import warnings
import zlib
from typing import Any, NamedTuple

import numpy as np

from repro import obs
from repro.core import stats as stats_lib
from repro.core.adaptive_filter import (AdaptiveFilter, AdaptiveFilterConfig,
                                        StepMetrics)
from repro.core.ordering import OrderState
from repro.core.plan import FilterPlan, TokenizeSpec
from repro.core.sharded import ShardedAdaptiveFilter, data_mesh

log = logging.getLogger(__name__)

#: checkpoint schema written by ``FilterSession.save_state``
CKPT_VERSION = 2
CKPT_FORMAT = "filter-session"


# ================================================================== StepResult
class StepResult(NamedTuple):
    """Uniform per-step output of ``FilterSession.step`` (module docstring).

    Device arrays stay on device until a host accessor (``mask_np``,
    ``survivors``, ``host_tokens``, ``metrics_dict``) is called.
    """

    mask: Any
    packed: Any | None
    n_kept: Any | None
    tokens: Any | None
    n_tokens: Any | None
    metrics: StepMetrics
    capacity: int | None = None   # compaction width used (None = no limit)
    # per-result once-cell for the overflow warning (fresh list per step;
    # None disables — e.g. hand-built results)
    warn_cell: list | None = None
    # True when the guarded runtime refused this batch (poisoned data):
    # the mask is all-False, the metrics are zeros, and the state did NOT
    # advance — downstream consumers skip the batch instead of folding
    # garbage into the stream (see repro.runtime.guard.GuardedSession)
    quarantined: bool = False
    # host time in ``step``, ending at dispatch: the ``session.step``
    # span's duration (the host→device copy, dispatch and the sanctioned
    # triage/boundary syncs; no extra device round-trip). The admission
    # server's per-step gate cost lives here per the ROADMAP seam rule —
    # on StepResult, not a side API.
    gate_s: float | None = None

    # ------------------------------------------------------- host accessors
    @property
    def mask_np(self) -> np.ndarray:
        return np.asarray(self.mask)

    @property
    def n_pass(self) -> int:
        """Survivors actually KEPT (what downstream stages see): the packed
        count under compaction (saturation-aware), the mask popcount
        otherwise."""
        self._maybe_warn_overflow()
        if self.n_kept is not None:
            return int(np.asarray(self.n_kept).sum())
        return int(self.mask_np.sum())

    def _maybe_warn_overflow(self) -> None:
        """Warn ONCE per step result when capacity overflow dropped rows.

        Hooked into every accessor that observes the survivors or the
        metrics (the step itself stays sync-free), so any consumer that
        looks at its output learns about the loss exactly once."""
        if self.capacity is None or self.warn_cell is None or self.warn_cell:
            return
        self.warn_cell.append(True)
        nd = int(np.asarray(self.metrics.n_dropped).sum())
        if nd:
            log.warning(
                "compaction overflow: %d survivors dropped this step "
                "(capacity %s); raise compact_capacity or use 'auto'",
                nd, self.capacity)

    @property
    def n_dropped(self) -> int:
        """Survivors lost to capacity overflow, summed over shards."""
        self._maybe_warn_overflow()
        return int(np.asarray(self.metrics.n_dropped).sum())

    @property
    def n_dropped_per_shard(self) -> list[int]:
        self._maybe_warn_overflow()
        nd = np.asarray(self.metrics.n_dropped)
        return [int(x) for x in np.atleast_1d(nd)]

    # skip-tier tile counters (all zero when the tier is off for this step)
    @property
    def n_tiles_skipped_pass(self) -> int:
        """128-row tiles bulk-kept by the zone-map proof (no row-level work)."""
        return int(np.sum(np.asarray(self.metrics.n_tiles_pass)))

    @property
    def n_tiles_skipped_fail(self) -> int:
        """128-row tiles dropped by the zone-map proof (no row-level work)."""
        return int(np.sum(np.asarray(self.metrics.n_tiles_fail)))

    @property
    def n_tiles_ambiguous(self) -> int:
        """128-row tiles that reached the row-level chain."""
        return int(np.sum(np.asarray(self.metrics.n_tiles_ambiguous)))

    def survivors(self, columns: np.ndarray | None = None) -> np.ndarray:
        """Surviving rows as a host f32[C, n_pass] array (shard-major).

        Under compaction (incl. tokenize plans) this slices the packed
        device buffer(s); otherwise it boolean-indexes ``columns``
        (required then). Tokenize-plan pipelines prefer ``host_tokens`` —
        only the dense token stream crosses to the host there."""
        self._maybe_warn_overflow()
        if self.packed is not None:
            packed = np.asarray(self.packed)
            counts = np.atleast_1d(np.asarray(self.n_kept))
            if packed.ndim == 2:                       # [C, cap]
                return packed[:, :int(counts[0])]
            return np.concatenate(                     # [S, C, cap]
                [packed[s][:, :int(counts[s])]
                 for s in range(packed.shape[0])], axis=1)
        if columns is None:
            raise ValueError("no compaction in this session: pass the "
                             "original columns to slice by mask")
        return np.asarray(columns)[:, self.mask_np]

    def host_tokens(self) -> np.ndarray:
        """Dense packed token stream (device tokenize sessions only).

        Sharded sessions tokenize+pack per shard (no cross-shard
        collectives); the shard-major concatenation here is bit-identical
        to the single-stream pack."""
        if self.tokens is None:
            raise ValueError("session has no tokenize stage "
                            "(FilterPlan.tokenize is None)")
        toks = np.asarray(self.tokens)
        if toks.ndim == 2:                    # [S, cap·T] per-shard packs
            counts = np.asarray(self.n_tokens)
            return np.concatenate([toks[s, :int(counts[s])]
                                   for s in range(toks.shape[0])])
        return toks[:int(self.n_tokens)]

    def metrics_dict(self) -> dict:
        """THE JSON metrics encoding (pipelines / serve / train all agree).

        ``n_pass`` is the mask popcount (monitor semantics, matching the
        host streaming path); ``n_dropped`` is summed over shards with the
        per-shard breakdown alongside when the step was sharded."""
        self._maybe_warn_overflow()
        nd = np.asarray(self.metrics.n_dropped)
        out = {
            "work_units": float(np.sum(np.asarray(self.metrics.work_units))),
            "n_pass": int(np.sum(np.asarray(self.metrics.n_pass))),
            "perm": np.asarray(self.metrics.perm).tolist(),
            "epoch": int(np.max(np.asarray(self.metrics.epoch))),
            "n_dropped": int(nd.sum()),
            "n_tiles_skipped_pass": self.n_tiles_skipped_pass,
            "n_tiles_skipped_fail": self.n_tiles_skipped_fail,
            "n_tiles_ambiguous": self.n_tiles_ambiguous,
            "quarantined": bool(self.quarantined),
        }
        if nd.ndim >= 1:
            out["n_dropped_per_shard"] = [int(x) for x in nd]
        return out


# ============================================================ state validation
def state_invariants(state: OrderState, *, n_predicates: int, n_groups: int,
                     collect_rate: int, calculate_rate: int,
                     rows_bounded: bool = True, xp=None):
    """ONE fused boolean over every structural invariant of an OrderState.

    Works on single ``[P]``-shaped states and stacked ``[S, P]`` sharded
    states alike (every check broadcasts over a leading shard axis):

      * all stat accumulators and ``adj_rank`` finite; accumulators and
        counters non-negative (a NaN/Inf here poisons every future rank);
      * ``num_cut``/``group_cut`` never exceed ``n_monitored`` (a count
        above the monitored total cannot arise from any real batch);
      * ``perm`` is a permutation of [0, P) and ``group_perm`` of [0, G)
        (a clamped out-of-bounds gather silently evaluates the wrong
        predicate — the worst kind of corruption: no crash, wrong masks);
      * ``rows_into_epoch`` >= 0 and — when the session owns its epoch
        boundaries (``rows_bounded``) — below ``calculate_rate``;
        ``sample_phase`` in [0, collect_rate); ``epoch`` >= 0.

    Returns a scalar bool ARRAY (no host sync): callers jit this and
    choose where to pay the one transfer (``FilterSession.validate_state``).
    """
    if xp is None:
        import jax.numpy as xp

    st = state.stats

    def clean(a):      # finite AND non-negative
        return xp.all(xp.isfinite(a)) & xp.all(a >= 0)

    ok = clean(st.num_cut) & clean(st.cost_acc) & clean(st.n_monitored)
    ok &= xp.all(xp.isfinite(state.adj_rank))
    n_mon = st.n_monitored[..., None]        # broadcast over [.., P]
    ok &= xp.all(st.num_cut <= n_mon)
    if st.group_cut is not None:
        ok &= clean(st.group_cut) & xp.all(st.group_cut <= n_mon)
    ok &= xp.all(xp.sort(state.perm.astype(xp.int32), axis=-1)
                 == xp.arange(n_predicates, dtype=xp.int32))
    if state.group_perm is not None:
        ok &= xp.all(xp.sort(state.group_perm.astype(xp.int32), axis=-1)
                     == xp.arange(n_groups, dtype=xp.int32))
    ok &= xp.all(state.rows_into_epoch >= 0)
    if rows_bounded:
        ok &= xp.all(state.rows_into_epoch < calculate_rate)
    ok &= xp.all((state.sample_phase >= 0)
                 & (state.sample_phase < collect_rate))
    ok &= xp.all(state.epoch >= 0)
    return ok


# ======================================================== checkpoint integrity
def arrays_crc32(arrays: dict) -> int:
    """CRC32 over a state-arrays dict (key order canonicalized).

    Folds each array's name, dtype, shape, and raw bytes into one running
    checksum. Computed on the HOST numpy views, after any serialization
    round trip — the TrainDriver's JSON ``tolist``/``asarray(dtype)``
    round trip is value- and dtype-exact, so the checksum survives it.
    """
    crc = 0
    for k in sorted(arrays):
        v = np.ascontiguousarray(np.asarray(arrays[k]))
        crc = zlib.crc32(f"{k}|{v.dtype.str}|{v.shape}".encode(), crc)
        crc = zlib.crc32(v.tobytes(), crc)
    return crc


# ================================================================== session
class FilterSession:
    """A compiled ``FilterPlan``; see the module docstring.

    Build with ``build_session`` (or ``FilterSession.from_filter`` to adopt
    a legacy filter instance). The underlying ``AdaptiveFilter`` /
    ``ShardedAdaptiveFilter`` is the functional math core; every host-side
    driving decision goes through here.
    """

    def __init__(self, plan: FilterPlan, mesh=None, *, _filter=None):
        self.plan = plan
        if _filter is not None:
            self.filter = _filter
        else:
            cfg = AdaptiveFilterConfig(
                ordering=plan.ordering, scope=plan.scope,
                cost_mode=plan.cost_mode, backend=plan.engine,
                adaptive=plan.adaptive, compact_output=plan.compact,
                compact_capacity=plan.capacity, compact_slack=plan.slack,
                exchange=plan.exchange, skip_tier=plan.skip_tier)
            # an explicit mesh forces the shard_mapped execution layer even
            # for shards=1 (a live 1-device mesh is how the sharded path is
            # exercised without multiple devices — benches/tests rely on it)
            if plan.shards > 1 or mesh is not None:
                if plan.skip_tier != "off":
                    raise ValueError(
                        "skip_tier needs the unsharded execution layer: "
                        "a mesh forces shard_map, whose static shapes the "
                        "per-step ambiguous-tile sync cannot drive")
                if mesh is None:
                    mesh = data_mesh(plan.shards, plan.axis_name)
                elif plan.axis_name in mesh.axis_names \
                        and int(mesh.shape[plan.axis_name]) != plan.shards:
                    raise ValueError(
                        f"plan.shards={plan.shards} but mesh axis "
                        f"{plan.axis_name!r} has size "
                        f"{mesh.shape[plan.axis_name]}")
                self.filter = ShardedAdaptiveFilter(
                    list(plan.predicates), cfg, mesh=mesh,
                    axis_name=plan.axis_name)
            else:
                self.filter = AdaptiveFilter(list(plan.predicates), cfg)
        self._jit_tokenize = None   # sharded per-shard tokenize (lazy)
        # skip_tier="auto": the online us_per_row tuner (lazy; host-owned)
        self._skip_tuner = None
        # guarded-runtime integrity probe (lazy jit of state_invariants)
        self._jit_validate = None
        # host-side mirror of rows_into_epoch for the deferred-exchange
        # boundary check: rows per shard are deterministic (every step adds
        # the static local batch width), so the due-test needs NO
        # device→host sync in the hot loop; re-anchored by init_state /
        # restore_state, reduced modulo calculate_rate at each boundary
        self._rows_local = 0

    # -------------------------------------------------------------- shape
    @property
    def sharded(self) -> bool:
        return isinstance(self.filter, ShardedAdaptiveFilter)

    @property
    def num_shards(self) -> int:
        return self.filter.num_shards if self.sharded else 1

    @property
    def _core(self) -> AdaptiveFilter:
        """The unsharded math core (engine, specs, ordering config)."""
        return self.filter.inner if self.sharded else self.filter

    @classmethod
    def from_filter(cls, filt, tokenize: TokenizeSpec | None = None
                    ) -> "FilterSession":
        """Adopt a legacy filter instance under a synthesized plan."""
        core = filt.inner if isinstance(filt, ShardedAdaptiveFilter) \
            else filt
        cfg = core.config
        plan = FilterPlan(
            predicates=tuple(core.predicates), ordering=cfg.ordering,
            engine=cfg.backend, scope=cfg.scope,
            shards=filt.num_shards
            if isinstance(filt, ShardedAdaptiveFilter) else 1,
            axis_name=filt.axis_name
            if isinstance(filt, ShardedAdaptiveFilter) else "data",
            adaptive=cfg.adaptive, cost_mode=cfg.cost_mode,
            compact=cfg.compact_output, capacity=cfg.compact_capacity,
            slack=cfg.compact_slack, exchange=cfg.exchange,
            tokenize=tokenize, skip_tier=cfg.skip_tier)
        return cls(plan, _filter=filt)

    def with_tokenize(self, tokenize: TokenizeSpec) -> "FilterSession":
        """Same compiled filter, plus the device tokenize stage."""
        import dataclasses
        plan = dataclasses.replace(self.plan, tokenize=tokenize)
        return FilterSession(plan, _filter=self.filter)

    # -------------------------------------------------------------- state
    def init_state(self) -> OrderState:
        self._rows_local = 0
        return self.filter.init_state()

    # ------------------------------------------------------------ skip tier
    def _skip_step_mode(self) -> str:
        """The skip-tier arm for the CURRENT step ("off" disables it).

        Fixed tiers pass through; "auto" asks the online tuner
        (``skip_tier.SkipTierTuner``) which arm to run — it alternates
        during warmup, then follows the faster measured us_per_row, and
        structurally forces "off" when the observed ambiguous-tile
        fraction says the tier cannot pay (shuffled layouts).
        """
        from repro.core import skip_tier as skip_tier_lib

        tier = self.plan.skip_tier
        if tier in ("off", None) or self.sharded:
            return "off"
        if tier != "auto":
            return tier
        if self._skip_tuner is None:
            self._skip_tuner = skip_tier_lib.SkipTierTuner(
                self._core.skip_on_mode())
        return self._skip_tuner.choose()

    @property
    def skip_tier_active(self) -> str:
        """The arm a step would run right now (bench/telemetry hook)."""
        if self.plan.skip_tier != "auto":
            return "off" if self.sharded else self.plan.skip_tier
        return self._skip_tuner.active_mode if self._skip_tuner else "auto"

    # ---------------------------------------------------------------- step
    def step(self, state: OrderState, batch) -> tuple[OrderState, StepResult]:
        """One micro-batch through the whole compiled plan.

        ``batch``: f32[C, R] (host or device; [C, S·R] when sharded, shard i
        owning the contiguous block i). Drives — in order — the skip-tier
        triage (when the plan enables it), the jitted
        filter(+compact+tokenize) step, the deferred epoch exchange if one
        is due, and the auto-capacity retune; returns the post-exchange
        state and a uniform ``StepResult``.
        """
        import time

        import jax
        import jax.numpy as jnp

        with obs.step_span("session.step") as root:
            with obs.span("session.h2d"):
                cols = jnp.asarray(batch, jnp.float32)
            if not isinstance(batch, jax.Array):
                obs.count("session.h2d_bytes", cols.nbytes)
            n_local = int(cols.shape[1]) // self.num_shards
            f = self.filter
            prev = state
            packed = n_kept = tokens = n_tokens = None
            cap = None
            skip_mode = self._skip_step_mode()
            auto = self.plan.skip_tier == "auto" and not self.sharded
            if auto:
                t0 = time.perf_counter()
            info = None
            if skip_mode != "off":
                # the tier's one host sync: the triage result sizes the jnp
                # gather width (quantized — bounded jit cache); the pallas
                # engine predicates in-kernel and skips the sync entirely
                with obs.span("session.triage"):
                    info = f._jit_triage(
                        cols, bloom=skip_mode == "zonemap+bloom")
                    amb_cap = f.skip_amb_cap(info, n_local)
            with obs.span("session.dispatch"):
                if self.plan.compact:
                    cap = f.resolve_capacity(n_local)
                    if info is not None:
                        state, packed, n_kept, mask, metrics = \
                            f._jit_skip_compact(
                                state, cols, info.pass_tiles,
                                info.fail_tiles, amb_cap=amb_cap,
                                capacity=cap)
                    else:
                        state, packed, n_kept, mask, metrics = \
                            f._jit_compact(state, cols, capacity=cap)
                    if self.plan.tokenize is not None:
                        if self.sharded:
                            tokens, n_tokens = self._tokenize_sharded(
                                packed, n_kept)
                        else:
                            from repro.data import tokenizer
                            ts = self.plan.tokenize
                            tokens, n_tokens = tokenizer.tokens_from_padded(
                                packed, n_kept, ts.vocab_size,
                                ts.tokens_per_row)
                elif info is not None:
                    state, mask, metrics = f._jit_skip(
                        state, cols, info.pass_tiles, info.fail_tiles,
                        amb_cap=amb_cap)
                else:
                    state, mask, metrics = f.jit_step(state, cols)
            if auto:
                self._observe_skip_arm(skip_mode, mask, metrics, t0,
                                       int(cols.shape[1]))
            if self._core.exchange_deferred:
                with obs.span("session.exchange"):
                    state = self._drive_boundary(state, n_local)
            f.observe_for_capacity(prev, state, n_local)
            # a deferred exchange may have just fired the epoch boundary —
            # the metrics must report the post-exchange epoch (one uniform
            # answer)
            metrics = metrics._replace(epoch=state.epoch)
        # no host sync here — overflow accounting surfaces through the
        # StepResult accessors (which warn once per result), keeping the
        # hot step free of forced device round-trips
        return state, StepResult(mask, packed, n_kept, tokens, n_tokens,
                                 metrics, cap, warn_cell=[],
                                 gate_s=root.seconds)

    def _drive_boundary(self, state: OrderState, n_local: int) -> OrderState:
        """The deferred epoch boundary, counted on the host: no per-step
        device sync until the host counter says one may be due (the jitted
        exchange itself checks/derives everything it needs). Then
        ``maybe_exchange`` reads the device counter once and fires the
        exchange if it is due. One session drives one state stream; if the
        counter has drifted anyway (states advanced outside this session),
        a presumed boundary that was not due re-anchors it from the
        device."""
        self._rows_local += n_local
        rate = self.plan.ordering.calculate_rate
        if self._rows_local < rate:
            return state
        new = self.filter.maybe_exchange(state)
        if new is state:             # not due: the counter drifted
            self._rows_local = self._sync_rows_into_epoch(state)
            return state
        obs.count("session.exchanges")
        self._rows_local %= rate
        return new

    # ------------------------------------------------- sanctioned host syncs
    # These two helpers are the session driver's ONLY deliberate
    # device→host syncs outside the skip-tier/boundary counters owned by
    # AdaptiveFilter; each is allowlisted by qualname (with its reason) in
    # ``repro.analysis.hotpath_lint.ALLOWLIST`` — a new sync anywhere else
    # in the reachable step graph fails the hot-path lint.
    def _observe_skip_arm(self, skip_mode: str, mask, metrics,
                          t0: float, n_rows: int) -> None:
        """Feed the skip_tier="auto" tuner one honest per-arm wall clock.

        The tuner compares ARMS, so both pay the same block_until_ready
        sync; the ambiguous-tile fraction rides along for the structural
        shutoff on adversarial (shuffled) layouts.
        """
        import time

        import jax

        with obs.sync("FilterSession._observe_skip_arm"):
            jax.block_until_ready(mask)
            dt = time.perf_counter() - t0
            ambig_frac = None
            if skip_mode != "off":
                n_amb = float(np.sum(np.asarray(metrics.n_tiles_ambiguous)))
                n_tot = n_amb \
                    + float(np.sum(np.asarray(metrics.n_tiles_pass))) \
                    + float(np.sum(np.asarray(metrics.n_tiles_fail)))
                ambig_frac = n_amb / max(n_tot, 1.0)
        self._skip_tuner.observe(skip_mode, dt * 1e6 / max(n_rows, 1),
                                 ambig_frac)

    def _sync_rows_into_epoch(self, state: OrderState) -> int:
        """Re-anchor the host boundary counter from the device state — one
        sync per presumed boundary, only when the counter drifted (states
        advanced outside this session)."""
        with obs.sync("FilterSession._sync_rows_into_epoch"):
            return int(np.max(np.asarray(state.rows_into_epoch)))

    def _tokenize_sharded(self, packed, counts):
        """Per-shard device tokenize+pack under shard_map.

        The hash is elementwise and the pack cumsum is per-shard, so the
        whole stage is collective-free on the mesh (a GLOBAL pack over the
        shard-sharded buffer would drag a cross-device cumsum through the
        SPMD partitioner — pathological; per-shard packs concatenated
        shard-major by ``StepResult.host_tokens`` are bit-identical).
        Returns (tokens i32[S, cap·T] packed-front, n_valid i32[S]).
        """
        if self._jit_tokenize is None:
            import jax
            from jax.sharding import PartitionSpec as P

            from repro.core.sharded import shard_map
            from repro.data import tokenizer

            ts = self.plan.tokenize
            mesh, a = self.filter.mesh, self.filter.axis_name
            tok = tokenizer._jit_tokens_from_padded()

            def local(p, c):          # p f32[1, C, cap], c i32[1]
                t, n = tok(p, c, vocab_size=ts.vocab_size,
                           tokens_per_row=ts.tokens_per_row)
                return t[None], n[None]

            self._jit_tokenize = jax.jit(shard_map(
                local, mesh=mesh, in_specs=(P(a), P(a)),
                out_specs=(P(a), P(a))))
        return self._jit_tokenize(packed, counts)

    # ----------------------------------------------------------- validation
    def validate_state(self, state: OrderState) -> bool:
        """On-device structural integrity check of an ``OrderState``.

        Every invariant — finite, non-negative accumulators; counts within
        ``n_monitored``; ``perm``/``group_perm`` true permutations;
        epoch/rows/phase counters in range — is fused into ONE jitted
        boolean, so the whole probe costs a single device→host sync. The
        guarded runtime (``repro.runtime.guard``) calls this once per
        validation boundary, never per step; the qualname is allowlisted in
        ``hotpath_lint`` with that contract.
        """
        if self._jit_validate is None:
            import jax

            self._jit_validate = jax.jit(self._invariants_fn())
        ok = self._jit_validate(state)
        with obs.sync("FilterSession.validate_state"):
            return bool(np.asarray(ok))

    def _invariants_fn(self):
        """The fused invariant check ``validate_state`` jits (also traced
        un-jitted by ``make_jaxprs`` for the IR lint)."""
        cfg = self.plan.ordering
        n_p = len(self.plan.predicates)
        n_g = self._core.specs.n_groups
        # deferred exchange legitimately lets rows_into_epoch overshoot
        # calculate_rate until the driver fires the boundary
        bounded = not self._core.exchange_deferred

        def check(s):
            return state_invariants(
                s, n_predicates=n_p, n_groups=n_g,
                collect_rate=cfg.collect_rate,
                calculate_rate=cfg.calculate_rate, rows_bounded=bounded)

        return check

    def make_jaxprs(self, batch) -> dict:
        """Traced (uncompiled) ``ClosedJaxpr`` per jitted callable this
        session drives — the IR surface ``repro.analysis.jaxpr_lint``
        audits.

        Keys: ``step``, ``exchange``, ``validate_state``, plus
        ``compact`` / ``tokenize`` / ``skip_step`` / ``skip_compact``
        when the plan enables them. ``batch``: f32[C, R] shaped like a
        live step's input ([C, S·R] when sharded). Tracing only — nothing
        compiles or executes except the skip tier's triage, which sizes
        the static gather width exactly the way a live step would.
        """
        import jax
        import jax.numpy as jnp

        cols = jnp.asarray(batch, jnp.float32)
        n_local = int(cols.shape[1]) // self.num_shards
        f = self.filter
        state = self.init_state()
        out: dict = {}
        if self.sharded:
            out["step"] = jax.make_jaxpr(f.sharded_step)(state, cols)
            out["exchange"] = jax.make_jaxpr(
                lambda s: f._sharded_exchange(s))(state)
        else:
            out["step"] = jax.make_jaxpr(f.step)(state, cols)
            out["exchange"] = jax.make_jaxpr(
                lambda s: f.exchange_update(s))(state)
        if self.plan.compact:
            cap = f.resolve_capacity(n_local)
            if self.sharded:
                out["compact"] = jax.make_jaxpr(
                    lambda s, c: f.sharded_step_compact(
                        s, c, capacity=cap))(state, cols)
            else:
                out["compact"] = jax.make_jaxpr(
                    lambda s, c: f._step_compact(
                        s, c, capacity=cap))(state, cols)
            if self.plan.tokenize is not None:
                from repro.data import tokenizer
                ts = self.plan.tokenize
                # per-shard local shapes: the sharded path shard_maps the
                # same per-shard tokenize body, so this IS its local IR
                packed = jax.ShapeDtypeStruct((int(cols.shape[0]), cap),
                                              jnp.float32)
                cnt = jax.ShapeDtypeStruct((), jnp.int32)
                out["tokenize"] = jax.make_jaxpr(
                    lambda p, c: tokenizer.tokens_from_padded(
                        p, c, ts.vocab_size, ts.tokens_per_row))(packed,
                                                                 cnt)
        skip_mode = self._skip_step_mode()
        if skip_mode != "off":
            info = f._jit_triage(cols, bloom=skip_mode == "zonemap+bloom")
            amb_cap = f.skip_amb_cap(info, n_local)
            if self.plan.compact:
                cap = f.resolve_capacity(n_local)
                out["skip_compact"] = jax.make_jaxpr(
                    lambda s, c, p, fl: f._step_skip_compact(
                        s, c, p, fl, amb_cap=amb_cap, capacity=cap))(
                    state, cols, info.pass_tiles, info.fail_tiles)
            else:
                out["skip_step"] = jax.make_jaxpr(
                    lambda s, c, p, fl: f._step_skip(
                        s, c, p, fl, amb_cap=amb_cap))(
                    state, cols, info.pass_tiles, info.fail_tiles)
        out["validate_state"] = jax.make_jaxpr(self._invariants_fn())(state)
        return out

    # ------------------------------------------------------------ analysis
    def compiled_step_texts(self, state: OrderState, batch) -> dict:
        """Compiled HLO of every jitted module ``step`` runs for this plan
        (``"triage"`` when the skip tier is on, and ``"step"``: the plain,
        compacting or skip-tier step the plan dispatches to) — what the
        collective-freedom audits and a chip run's compiled-kernel check
        grep."""
        import jax.numpy as jnp

        cols = jnp.asarray(batch, jnp.float32)
        if self.sharded:
            return {"step": self.filter.compiled_text(state, cols)}
        f, n = self.filter, int(cols.shape[1])
        texts, info = {}, None
        mode = self._skip_step_mode()
        if mode != "off":
            bloom = mode == "zonemap+bloom"
            texts["triage"] = f._jit_triage.lower(
                cols, bloom=bloom).compile().as_text()
            info = f._jit_triage(cols, bloom=bloom)
            skip = dict(amb_cap=f.skip_amb_cap(info, n))
            decisions = (info.pass_tiles, info.fail_tiles)
        if self.plan.compact:
            cap = f.resolve_capacity(n)
            lowered = f._jit_compact.lower(state, cols, capacity=cap) \
                if info is None else f._jit_skip_compact.lower(
                    state, cols, *decisions, capacity=cap, **skip)
        elif info is not None:
            lowered = f._jit_skip.lower(state, cols, *decisions, **skip)
        else:
            lowered = f.jit_step.lower(state, cols)
        texts["step"] = lowered.compile().as_text()
        return texts

    def compiled_exchange_text(self, state: OrderState) -> str:
        return self.filter.compiled_exchange_text(state) if self.sharded \
            else self.filter.jit_exchange.lower(state).compile().as_text()

    # =========================================================== checkpoints
    @property
    def _stats_replicated(self) -> bool:
        """Accumulator layout of THIS session's states.

        Under eager CENTRALIZED every batch's monitor counters are
        psum-merged BEFORE they fold in, so each shard's epoch accumulator
        already holds the identical GLOBAL totals (replicated). Every
        other combination accumulates locally (partitioned) and merges —
        if ever — at the boundary. Elastic restore must convert between
        the two or it over/under-counts carried evidence by S×.
        """
        return (self.sharded and self.plan.scope == "centralized"
                and self.plan.exchange == "eager" and self.plan.adaptive)

    def save_state(self, state: OrderState) -> dict:
        """Versioned checkpoint blob (schema v2).

        Embeds the plan fingerprint (semantic identity of the adaptive
        state), the shard layout, and the accumulator layout
        (replicated vs partitioned — see ``_stats_replicated``), so a
        restore can verify compatibility and reshard elastically."""
        from repro.data.pipeline import fstate_to_arrays
        arrays = fstate_to_arrays(state)
        return {
            "format": CKPT_FORMAT,
            "version": CKPT_VERSION,
            "fingerprint": self.plan.fingerprint(),
            "shards": self.num_shards if self.sharded else 0,
            "stats_layout": "replicated" if self._stats_replicated
            else "partitioned",
            "crc32": arrays_crc32(arrays),
            "arrays": arrays,
        }

    def restore_state(self, blob: dict) -> OrderState:
        """Load a v1 (raw arrays) or v2 (versioned) blob, resharding S→S′.

        * fingerprint mismatch (v2 only) → ValueError with both prints;
        * same shard + accumulator layout → verbatim (bit-identical);
        * otherwise → elastic reshard: epoch accumulators are merged to
          one logical executor (sum over shards when the source
          accumulated locally; first row when the source was
          replicated-global, i.e. eager CENTRALIZED) and re-laid-out for
          this session (split evenly for partitioned targets — the next
          boundary merge recovers the source totals exactly, bit-exact
          for power-of-two rescales; broadcast whole for replicated
          targets); ranks/perms are re-derived from the merged stats when
          the source shards disagree.
        """
        from repro.data.pipeline import fstate_from_arrays
        src_replicated = None
        if "arrays" in blob:                     # versioned (v2) envelope
            fmt = blob.get("format")
            if fmt is not None and fmt != CKPT_FORMAT:
                raise ValueError(
                    f"not a filter-session checkpoint (format {fmt!r})")
            version = blob.get("version")
            if version not in (CKPT_VERSION,):
                raise ValueError(
                    f"unknown filter-session checkpoint version {version!r} "
                    f"(this build reads v1 raw-array blobs and v2)")
            want = self.plan.fingerprint()
            got = blob.get("fingerprint")
            if got is not None and got != want:
                raise ValueError(
                    f"checkpoint plan fingerprint {got} does not match this "
                    f"session's {want}: the predicate chain, ordering "
                    "config, scope, adaptivity, or cost mode differ — "
                    "elastic restore only spans engines and shard counts")
            if "stats_layout" in blob:
                src_replicated = blob["stats_layout"] == "replicated"
            arrays = blob["arrays"]
            stored_crc = blob.get("crc32")
            if stored_crc is None:
                warnings.warn(
                    "repro: loading a checksum-less v2 filter-session "
                    "checkpoint (written before the crc32 integrity field); "
                    "corruption cannot be detected — re-save to upgrade",
                    UserWarning, stacklevel=2)
            else:
                got_crc = arrays_crc32(arrays)
                if got_crc != int(stored_crc):
                    raise ValueError(
                        f"corrupt checkpoint: crc32 mismatch (stored "
                        f"{int(stored_crc):#010x}, computed {got_crc:#010x})"
                        " — the blob was truncated or bit-flipped in "
                        "storage; refusing to deserialize garbage state")
        else:                                    # v1: raw fstate_to_arrays
            arrays = blob
        arrays = {k: np.asarray(v) for k, v in arrays.items()}
        old_shards = _layout_of(arrays)
        if blob.get("shards") is not None and blob["shards"] != old_shards:
            raise ValueError(
                f"corrupt checkpoint: envelope says {blob['shards']} "
                f"shard(s) but the state arrays carry {old_shards}")
        if src_replicated is None:
            # v1 blobs carry no layout tag: replicated shards are bitwise
            # identical (the eager-CENTRALIZED invariant); anything else
            # accumulated locally
            src_replicated = old_shards > 1 and all(
                bool(np.all(arrays[k] == arrays[k][0]))
                for k in _SUM_KEYS if k in arrays)
        new_shards = self.num_shards if self.sharded else 0
        if old_shards != new_shards \
                or src_replicated != self._stats_replicated:
            arrays = reshard_state_arrays(
                arrays, new_shards, groups=self._core.specs.groups,
                src_replicated=src_replicated,
                tgt_replicated=self._stats_replicated)
        restored = fstate_from_arrays(arrays)
        # re-anchor the host-side deferred-boundary row counter
        self._rows_local = int(np.max(np.asarray(restored.rows_into_epoch)))
        return restored


#: chain-lint findings already warned about this process (warn once per
#: (code, location) — plans are rebuilt constantly in benches/tests)
_LINT_WARNED: set[tuple[str, str]] = set()


def _lint_plan_chain(plan: FilterPlan) -> None:
    """Plan-compile-time chain lint (the Liu & Ives point: canonicalize
    BEFORE adaptive re-optimization). Error findings — unsatisfiable
    predicates/groups/conjunctions — raise; redundancy findings warn once;
    info notes stay silent (the CLI surfaces them)."""
    import warnings

    from repro.analysis import chain_lint, diagnostics

    diags = chain_lint.lint_chain(plan.predicates)
    errs = diagnostics.errors(diags)
    if errs:
        raise ValueError(
            "FilterPlan chain fails the semantics lint:\n"
            + diagnostics.render_report(errs)
            + "\n(run `python -m repro.analysis --chain` for the full "
            "report)")
    for d in diagnostics.warnings_of(diags):
        key = (d.code, d.location)
        if key not in _LINT_WARNED:
            _LINT_WARNED.add(key)
            warnings.warn(f"repro chain lint: {d.render()}", UserWarning,
                          stacklevel=3)


def build_session(plan: FilterPlan, mesh=None) -> FilterSession:
    """Compile a declarative ``FilterPlan`` into a ``FilterSession``.

    ``mesh``: optional ``jax.sharding.Mesh`` carrying ``plan.axis_name``
    (default when ``plan.shards > 1``: a fresh 1-axis mesh over
    ``plan.shards`` devices). Passing a mesh forces the shard_mapped
    execution layer even for ``shards=1``.

    Runs the chain semantics linter (``repro.analysis.chain_lint``) before
    compiling: a provably-unsatisfiable chain raises here — at plan time,
    with the predicate named — instead of silently cutting every row;
    provably-redundant predicates warn once per process. (The legacy
    ``FilterSession.from_filter`` path skips the lint: it adopts an
    already-validated filter.)
    """
    _lint_plan_chain(plan)
    return FilterSession(plan, mesh=mesh)


# ========================================================== elastic reshard
def _layout_of(arrays: dict) -> int:
    """Shard layout of a state-arrays dict: 0 = unsharded (no leading
    axis), S >= 1 = stacked [S, ...] leaves."""
    rows = np.asarray(arrays["rows_into_epoch"])
    return 0 if rows.ndim == 0 else int(rows.shape[0])

_SUM_KEYS = ("stats.num_cut", "stats.cost_acc", "stats.n_monitored",
             "stats.group_cut", "rows_into_epoch")


def reshard_state_arrays(arrays: dict, new_shards: int, groups: tuple,
                         src_replicated: bool = False,
                         tgt_replicated: bool = False) -> dict:
    """S→S′ elastic reshard of a checkpointed OrderState (pure numpy).

    Epoch stat accumulators (``stats.*``) are merged to one logical
    executor according to the SOURCE layout — sum over the shard axis when
    the source accumulated locally (partitioned: per_shard / per_batch /
    deferred CENTRALIZED), first row when every shard already held the
    psum-merged global totals (replicated: eager CENTRALIZED) — and laid
    out for the TARGET: an even split for partitioned targets (the next
    boundary merge recovers the global totals exactly; bit-exact when S′
    is a power of two, since f32 division by 2^k only changes the
    exponent), the whole merged value broadcast for replicated targets and
    for ``new_shards=0`` (unsharded — its boundary merge is the identity).

    ``rows_into_epoch`` is a per-shard PHASE counter in every mode (the
    lockstep pipelines feed every shard the same batch width), so the
    maximum phase is broadcast — boundary cadence survives the rescale.

    Derived quantities (perm, group_perm, adj_rank) are taken verbatim
    when every source shard agrees (the CENTRALIZED invariant) and
    otherwise re-derived from the merged statistics via the same
    ``cnf_order`` math the epoch boundary uses.
    """
    old = _layout_of(arrays)
    stacked = {k: np.asarray(v) for k, v in arrays.items()}
    if old == 0:                      # promote to a 1-shard stack
        stacked = {k: v[None] for k, v in stacked.items()}
        old = 1

    # ---- merge to one logical executor ------------------------------------
    merged: dict[str, np.ndarray] = {}
    for k, v in stacked.items():
        if k == "rows_into_epoch":
            merged[k] = v.max(axis=0)
        elif k in _SUM_KEYS:
            if src_replicated:
                merged[k] = v[0].astype(np.float64)
            elif np.issubdtype(v.dtype, np.integer):
                merged[k] = v.sum(axis=0, dtype=np.int64)
            else:
                merged[k] = v.astype(np.float64).sum(axis=0)
        else:
            merged[k] = v[0]
    shards_agree = all(
        bool(np.all(v == v[0])) for k, v in stacked.items()
        if k not in _SUM_KEYS)

    if not shards_agree:
        # heterogeneous source shards (PER_SHARD scope): re-derive one
        # consensus order from the merged evidence — the exact rank math of
        # the epoch boundary, on the summed accumulators.
        mstats = stats_lib.FilterStats(
            num_cut=merged["stats.num_cut"].astype(np.float32),
            cost_acc=merged["stats.cost_acc"].astype(np.float32),
            n_monitored=merged["stats.n_monitored"].astype(np.float32),
            group_cut=merged.get("stats.group_cut",
                                 merged["stats.num_cut"]).astype(np.float32))
        adj = stacked["adj_rank"].astype(np.float64).mean(axis=0) \
            .astype(np.float32)
        merged["adj_rank"] = adj
        if float(mstats.n_monitored) > 0.0:
            grank = stats_lib.group_ranks(mstats, groups, xp=np)
            mrank = stats_lib.member_ranks(mstats, xp=np)
            perm, gperm = stats_lib.cnf_order(grank, mrank, groups, xp=np)
            merged["perm"] = perm.astype(np.int32)
            merged["group_perm"] = gperm.astype(np.int32)
        merged["epoch"] = stacked["epoch"].max(axis=0)

    # ---- split over the new layout ----------------------------------------
    split_by = 1 if (tgt_replicated or new_shards == 0) \
        else max(new_shards, 1)
    out: dict[str, np.ndarray] = {}
    for k, v in merged.items():
        src_dtype = stacked[k].dtype
        if k in _SUM_KEYS and k != "rows_into_epoch":
            if np.issubdtype(src_dtype, np.integer):
                piece = (v // split_by).astype(src_dtype)
            else:
                piece = (v / split_by).astype(src_dtype)
        else:
            piece = v.astype(src_dtype)
        if new_shards == 0:
            out[k] = piece
        else:
            out[k] = np.broadcast_to(
                piece[None], (new_shards,) + piece.shape).copy()
    return out
