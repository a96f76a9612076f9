"""FilterEngine contract: the one ABI all execution backends implement.

An engine turns (columns, packed predicate specs, permutation, monitor
config) into a ``ChainResult``. The semantics are fixed — CNF evaluation
(OR within a group, AND across groups, short-circuit at both levels, exact
row-level work accounting; trees add AND-terms inside groups, with the AND
short-circuit inside each term) plus the paper's §2.1 monitor lane — and are
pinned across engines by the conformance tests; only the execution strategy
(masked jnp, fused Pallas tiles, compacted numpy) differs.

Engines never touch ordering state: the epoch controller
(``core.ordering``) consumes the monitor counters an engine reports. That
seam is what makes backends pluggable — a new engine only has to produce a
correct ``ChainResult``.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Protocol, runtime_checkable

#: rows per grid tile of the tiled chain lane (the pallas kernel's default
#: tile), and the tile every engine counts ``n_atom_tiles`` over
CHAIN_TILE = 2048


class ChainResult(NamedTuple):
    """Uniform output contract of every filter engine."""

    mask: Any             # bool[R] — rows passing the whole CNF chain
    work_units: Any       # f32[] — row-level cost-weighted work (Spark model)
    active_before: Any    # f32[P] — rows pending evaluation at each position
    cut_counts: Any       # f32[P] — monitor lane: rows failing each predicate
    n_monitored: Any      # f32[] — monitor lane: sampled row count
    monitor_cost: Any     # f32[P] — per-predicate monitor cost contribution
    group_cut_counts: Any  # f32[G] — monitor lane: rows cut by each OR-group
    # skip-tier counters (core/skip_tier.py); zero whenever the tier is off.
    # Work/active counters above charge only ambiguous-tile rows when the
    # tier resolves tiles — the row-level work actually performed.
    n_tiles_pass: Any = 0       # i32[] — tiles provably passing every group
    n_tiles_fail: Any = 0       # i32[] — tiles provably failing some group
    n_tiles_ambiguous: Any = 0  # i32[] — tiles sent to the row-level chain
    # monitor lane: rows cut by each AND-term of a tree (empty f32[0] for
    # chains without terms; None where an engine does not report it)
    term_cut_counts: Any = None  # f32[T] | None
    # (CHAIN_TILE-row tile, chain position) pairs with a row pending: the
    # pairs at which a tiled chain lane evaluates the atom (the pallas
    # kernel's tile short-circuit skips the others); 0 where the skip
    # tier's gather changes the tiling (the jnp skip path)
    n_atom_tiles: Any = 0       # i32[]


class SkipInfo(NamedTuple):
    """Tri-state tile resolution produced by an engine's ``triage``.

    ``pass_tiles``/``fail_tiles`` are bool[T] over the engine's padded
    128-row tiling of the batch (mutually exclusive; everything else is
    ambiguous). ``n_ambiguous`` is an i32 scalar the session syncs once per
    step to size the jnp gather width (``skip_tier.quantize_amb_cap``).
    """

    pass_tiles: Any
    fail_tiles: Any
    n_ambiguous: Any


class MonitorSpec(NamedTuple):
    """Monitor-lane parameters threaded to an engine for one batch."""

    collect_rate: int      # static: sample 1 row in every collect_rate
    sample_phase: Any      # i32[] global row offset mod collect_rate
    cost_mode: str = "static"   # "static" | "measured" (host engines only)
    mode: str = "row"           # "row" | "block" (pallas tile sampling)


@runtime_checkable
class FilterEngine(Protocol):
    """The pluggable execution seam (register with ``engine.register``)."""

    name: str
    # True → run_chain is jit/shard_map traceable (device arrays in/out);
    # False → host engine (numpy in/out, may use wall clocks / python loops).
    traceable: bool

    def run_chain(self, columns, specs, perm,
                  monitor: MonitorSpec) -> ChainResult:
        """Evaluate the CNF chain in ``perm`` order + run the monitor lane."""
        ...

    def run_chain_compact(self, columns, specs, perm, monitor: MonitorSpec,
                          *, capacity: int, fill: float = 0.0):
        """``run_chain`` + fixed-capacity survivor compaction in one pass.

        Returns (ChainResult, packed f32[C, capacity], n_kept i32[]).
        Traceable engines must implement this so the fused compacting step
        never needs a second full-width pass over the batch: the jnp
        engine chains the O(R) cumsum scatter onto its masked evaluation
        (XLA fuses them), the pallas engine packs survivors in-kernel
        while the tile is still in VMEM. Host engines may omit it — their
        boolean-index short-circuit already emits compacted rows.
        """
        ...

    # --- optional skip-tier surface (core/skip_tier.py) -----------------
    # Engines that support the tile-statistics skip tier additionally
    # implement:
    #
    #   triage(columns, specs, *, bloom: bool) -> SkipInfo
    #       Zone-map (+ optional Bloom) summaries resolved against the
    #       chain. Specs must be trace-time constants (closed over, not
    #       traced) — resolution branches on each predicate's op in
    #       python.
    #
    #   run_chain_skip(columns, specs, perm, monitor, skip, *, amb_cap)
    #   run_chain_compact_skip(..., capacity, fill)
    #       ``run_chain``/``run_chain_compact`` with provably-decided
    #       tiles bypassing the row-level chain. ``amb_cap`` is the static
    #       gathered width in tiles for engines that gather (jnp); the
    #       pallas engine predicates in-kernel and ignores it. The monitor
    #       lane always runs row-level on the full batch, so ordering
    #       statistics are identical with the tier on or off.
    #
    # ``supports_skip`` (class attribute, default False) advertises this.
