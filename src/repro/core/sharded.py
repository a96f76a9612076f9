"""Sharded execution layer: the adaptive filter under ``jax.shard_map``.

This is where the paper's central design decision (§2.2 — *where does the
adaptive metadata live?*) becomes executable instead of descriptive. One
``ShardedAdaptiveFilter`` runs the single-shard ``AdaptiveFilter.step``
under ``shard_map`` over a data mesh axis; the ``OrderState`` pytree gains
a leading shard axis (one per-executor state per mesh row) and the scope
policy decides what crosses the network:

  PER_SHARD    — the paper's choice: every shard adapts to its own slice,
                 zero collectives. The lowered HLO of the step contains NO
                 all-reduce (pinned by tests/test_sharded_filter.py), the
                 machine-checkable analogue of "no data transferred through
                 the network". Shards diverge under heterogeneous drift —
                 which is the feature, not a bug.
  CENTRALIZED  — the driver-state alternative the paper rejects for
                 contention: batch monitor counters are psum-merged across
                 the axis (``scope.reduce_stats``) before they fold into the
                 epoch accumulators, so every shard accumulates identical
                 global statistics and adopts the identical global order at
                 every epoch boundary. With ``exchange="eager"`` that is one
                 small (2P+G+1 floats) all-reduce per step; with
                 ``exchange="deferred"`` the counters accumulate locally and
                 ONE collective fires per ``calculate_rate`` rows at the
                 epoch boundary (``sharded_exchange`` — a separate jitted
                 call, so the per-step module compiles with no all-reduce;
                 sums are associative, so the adopted perm is identical).
                 ``"deferred-async"`` folds the merged stats in one epoch
                 late, overlapping the collective with filter work.
  PER_BATCH    — the per-task strawman: evidence dies with each batch on
                 each shard (monitor stride and epoch counter persist).

Data contract: ``columns`` is f32[C, S·R] with shard i owning the
contiguous row block [i·R, (i+1)·R) — exactly what ``in_specs=P(None,
"data")`` hands each mesh row, and what ``data.pipeline.ShardedPipeline``
assembles from per-shard ``LogStream``s. Epochs fire per *local* rows
(``calculate_rate`` rows per shard, as per-executor counters do in Spark);
under CENTRALIZED all shards fire in lockstep because every shard sees the
same batch shape.

With ``compact_output`` the per-shard survivors additionally come back as a
padded on-device [S, C, cap] gather + counts (``filter_exec.compact_fixed``
applied inside the shard_map body), so a multi-shard ingestion step moves
exactly one dense buffer per shard to the host — never a boolean index.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.adaptive_filter import (AdaptiveFilter, AdaptiveFilterConfig,
                                        drive_exchange, exchange_stash)
from repro.core.ordering import OrderState
from repro.core.plan import validate_combo
from repro.core.predicates import Predicate


def data_mesh(n_shards: int, axis_name: str) -> jax.sharding.Mesh:
    """1-D data mesh with an ``Auto`` axis (``jax.make_mesh`` defaults to
    ``Explicit``): the shard_mapped step places its own blocks, and the
    host-side glue around it must not carry sharding in its types."""
    return jax.make_mesh((n_shards,), (axis_name,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-axes type check: the ordering
    math's ``lax.cond`` epoch boundary returns freshly reset (unvarying)
    accumulators from one branch and the shard's own from the other."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def stack_states(state: OrderState, num_shards: int) -> OrderState:
    """Replicate one OrderState onto a leading shard axis: leaf → [S, ...]."""
    return jax.tree.map(
        lambda x: jnp.tile(x[None], (num_shards,) + (1,) * x.ndim), state)


def shard_slice(state: OrderState, shard: int) -> OrderState:
    """Extract shard ``shard``'s OrderState from the stacked pytree."""
    return jax.tree.map(lambda x: x[shard], state)


class ShardedAdaptiveFilter:
    """Data-parallel adaptive CNF filter: one OrderState per mesh shard.

    ``mesh`` defaults to a 1-axis mesh over every visible device. All three
    scopes of ``AdaptiveFilterConfig.scope`` are honoured as described in
    the module docstring; the backend must be a traceable engine (jnp /
    pallas) — host engines cannot run under shard_map.
    """

    def __init__(self, predicates: Sequence[Predicate],
                 config: AdaptiveFilterConfig | None = None,
                 *, mesh: jax.sharding.Mesh | None = None,
                 axis_name: str = "data"):
        cfg = config or AdaptiveFilterConfig()
        self.inner = AdaptiveFilter(predicates, cfg, axis_names=(axis_name,))
        if mesh is None:
            mesh = data_mesh(jax.device_count(), axis_name)
        if axis_name not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis_name!r}: "
                             f"{mesh.axis_names}")
        self.config = cfg
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_shards = int(mesh.shape[axis_name])
        # the sharded-execution rules live with every other cross-field
        # rule in core.plan.validate_combo (single source of truth); the
        # sharded filter is the shards>=1-under-shard_map case
        validate_combo(scope=cfg.scope, cost_mode=cfg.cost_mode,
                       backend=cfg.backend,
                       compact_output=cfg.compact_output,
                       compact_capacity=cfg.compact_capacity,
                       compact_slack=cfg.compact_slack,
                       exchange=cfg.exchange,
                       shards=max(self.num_shards, 2),
                       skip_tier=cfg.skip_tier)
        self._jit_step = None
        self._jit_step_compact = None
        self._jit_exchange = None
        self._stash = None   # deferred-async: last boundary's merge (+flag)

    # ---------------------------------------------------------------- state
    def init_state(self) -> OrderState:
        """Stacked per-shard state: every leaf leads with the shard axis."""
        return stack_states(self.inner.init_state(), self.num_shards)

    # ----------------------------------------------------------------- step
    def _specs(self, n_out: int):
        a = self.axis_name
        return ((P(a), P(None, a)), (P(a),) * n_out)

    def sharded_step(self, state: OrderState, columns: jnp.ndarray):
        """One micro-batch on every shard: columns f32[C, S·R], row-sharded.

        Returns (new_state [S, ...], mask bool[S·R], metrics with leading
        shard axis on every field). Trace it with ``jax.jit`` (or use
        ``jit_step``) — shard_map placement only happens under jit.
        """

        def local(st, cols):
            st = shard_slice(st, 0)       # [1, ...] per-shard block → [...]
            new_st, mask, metrics = self.inner.step(st, cols)
            return (jax.tree.map(lambda x: x[None], new_st), mask,
                    jax.tree.map(lambda x: x[None], metrics))

        in_specs, out_specs = self._specs(3)
        return shard_map(local, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs)(state, columns)

    def sharded_step_compact(self, state: OrderState, columns: jnp.ndarray,
                             *, capacity: int | None = None):
        """``sharded_step`` + per-shard single-pass device compaction.

        Returns (new_state, packed f32[S, C, cap], n_kept i32[S],
        mask bool[S·R], metrics). ``packed[i, :, :n_kept[i]]`` equals shard
        i's host boolean-mask survivors bit-exactly. ``capacity`` is the
        per-shard width (static under jit; None → local batch width).
        """

        def local(st, cols):
            st = shard_slice(st, 0)
            new_st, packed, n_kept, mask, metrics = self.inner._step_compact(
                st, cols, capacity=capacity)
            return (jax.tree.map(lambda x: x[None], new_st), packed[None],
                    n_kept[None], mask, jax.tree.map(lambda x: x[None],
                                                     metrics))

        in_specs, out_specs = self._specs(5)
        return shard_map(local, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs)(state, columns)

    @property
    def jit_step(self):
        if self._jit_step is None:
            self._jit_step = jax.jit(self.sharded_step)
        return self._jit_step

    @property
    def _jit_compact(self):
        if self._jit_step_compact is None:
            self._jit_step_compact = jax.jit(
                self.sharded_step_compact, static_argnames=("capacity",))
        return self._jit_step_compact

    # ------------------------------------------------------ deferred epochs
    def _sharded_exchange(self, state: OrderState, stash=None):
        """Shard_mapped ``AdaptiveFilter.exchange_update``: the deferred
        mode's single per-epoch collective (psum inside the shard_map body,
        the due flag riding it), returning (new_state [S,...], new_stash —
        every shard row of its stats holds the identical global sums; None
        under "deferred")."""

        def local(st, sh):                # sh: None under "deferred"
            new = self.inner.exchange_update(shard_slice(st, 0),
                                             shard_slice(sh, 0))
            return jax.tree.map(lambda x: x[None], new)

        a = self.axis_name
        return shard_map(local, mesh=self.mesh, in_specs=(P(a), P(a)),
                         out_specs=P(a))(state, stash)

    @property
    def jit_exchange(self):
        if self._jit_exchange is None:
            self._jit_exchange = jax.jit(self._sharded_exchange)
        return self._jit_exchange

    @property
    def exchange_deferred(self) -> bool:
        return self.inner.exchange_deferred

    def maybe_exchange(self, state: OrderState) -> OrderState:
        """Drive the deferred epoch boundary if due (host helper; the shared
        driver with the shard_mapped exchange callable)."""
        return drive_exchange(self, state)

    # -------------------------------------------------- capacity auto-tune
    def resolve_capacity(self, n_rows_local: int) -> int:
        return self.inner.resolve_capacity(n_rows_local)

    def observe_for_capacity(self, evidence_state, new_state,
                             n_rows_local: int) -> None:
        self.inner.observe_for_capacity(evidence_state, new_state,
                                        n_rows_local)

    # ------------------------------------------------------------- analysis
    def compiled_text(self, state: OrderState, columns: jnp.ndarray) -> str:
        """Compiled HLO of one sharded step — what the collective-freedom
        assertions grep (PER_SHARD ⇒ no all-reduce; deferred CENTRALIZED ⇒
        no all-reduce in the per-STEP module either)."""
        return jax.jit(self.sharded_step).lower(
            state, columns).compile().as_text()

    def compiled_exchange_text(self, state: OrderState) -> str:
        """Compiled HLO of the boundary exchange the driver dispatches —
        deferred CENTRALIZED must show its one all-reduce HERE and only
        here."""
        return self.jit_exchange.lower(
            state, exchange_stash(self, state)).compile().as_text()
