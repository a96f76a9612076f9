"""jit'd wrappers around the fused filter-chain kernel.

Handles padding to tile multiples, packs the SMEM meta scalars, launches the
kernel, and reduces per-tile counters into the framework-wide
``ChainResult`` contract shared with ``core.filter_exec`` (jnp path) and
``ref.py`` (oracle). ``filter_chain_compact`` additionally fuses survivor
compaction into the same pass (in-register row pack + stitched DMA to the
packed HBM output — see ``filter_chain.py``). ``interpret=True`` on
non-TPU backends, so the same call validates on CPU and runs compiled on
TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.engine.base import ChainResult, SkipInfo
from repro.core.predicates import PredicateSpecs
from repro.kernels.filter_chain.filter_chain import (DEFAULT_TILE, STAT_TILE,
                                                     filter_chain_pallas,
                                                     tile_stats_pallas)


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pack_meta(n_rows, collect_rate, sample_phase, monitor_mode):
    return jnp.stack([jnp.asarray(n_rows, jnp.int32),
                      jnp.asarray(collect_rate, jnp.int32),
                      jnp.asarray(sample_phase, jnp.int32),
                      jnp.asarray(1 if monitor_mode == "block" else 0,
                                  jnp.int32)])


def _reduce_result(mask_i8, active, cut, gcut, nmon, specs, perm, n_rows,
                   skip: SkipInfo | None = None):
    active_before = jnp.sum(active[:, 0], axis=0)            # f32[P]
    cost_in_order = specs.static_cost[perm]
    work = jnp.sum(active_before * cost_in_order)
    n_monitored = jnp.sum(nmon)
    zero = jnp.zeros((), jnp.int32)
    if skip is None:
        n_pass_t = n_fail_t = n_amb_t = zero
    else:
        n_pass_t = jnp.sum(skip.pass_tiles.astype(jnp.int32))
        n_fail_t = jnp.sum(skip.fail_tiles.astype(jnp.int32))
        n_amb_t = skip.pass_tiles.shape[0] - n_pass_t - n_fail_t
    return ChainResult(
        mask=mask_i8.reshape(-1)[:n_rows].astype(bool),
        work_units=work,
        active_before=active_before,
        cut_counts=jnp.sum(cut[:, 0], axis=0),
        n_monitored=n_monitored,
        monitor_cost=specs.static_cost * n_monitored,
        group_cut_counts=jnp.sum(gcut[:, 0], axis=0),
        n_tiles_pass=n_pass_t,
        n_tiles_fail=n_fail_t,
        n_tiles_ambiguous=n_amb_t,
    )


def _pad_cols(columns, tile):
    n_rows = columns.shape[1]
    pad = (-n_rows) % tile
    if pad:
        columns = jnp.pad(columns, ((0, 0), (0, pad)))
    return columns


def _skip_decisions(skip: SkipInfo):
    return (skip.pass_tiles.astype(jnp.int32),
            skip.fail_tiles.astype(jnp.int32))


def skip_triage(columns: jnp.ndarray, specs: PredicateSpecs, *, bloom: bool,
                tile: int = DEFAULT_TILE) -> SkipInfo:
    """Zone-map (+ Bloom) triage pre-pass for the pallas skip tier.

    NOT jitted here: the CNF resolution branches on the predicate ops,
    which must be host constants — callers jit with ``specs`` closed over
    (the session's ``_jit_triage`` does exactly that).

    Pads to the kernel's grid tile with ZEROS (matching the chain launch's
    padding): zero lanes can only weaken a fail proof, and a pass proof they
    satisfy is still intersected with row validity in-kernel, so both
    proofs stay conservative. The min/max summaries come from the Pallas
    stats kernel; the Bloom bitmap and the CNF tile resolution are shared
    jnp glue (``core.skip_tier``) — trace-time constants of the chain, so
    the per-op branching folds away. Tile counts are over the PADDED
    tiling: a ragged tail contributes decided-but-empty sub-tiles.
    """
    from repro.core import skip_tier

    assert STAT_TILE == skip_tier.SKIP_TILE
    padded = _pad_cols(columns, tile)
    mins, maxs = tile_stats_pallas(padded, tile=tile,
                                   interpret=_should_interpret())
    bl = skip_tier.bloom_bitmap(padded, xp=jnp) if bloom else None
    pass_t, fail_t = skip_tier.resolve_tiles(mins, maxs, bl, specs, xp=jnp)
    n_amb = jnp.sum(~(pass_t | fail_t)).astype(jnp.int32)
    return SkipInfo(pass_tiles=pass_t, fail_tiles=fail_t, n_ambiguous=n_amb)


@functools.partial(jax.jit,
                   static_argnames=("collect_rate", "tile", "monitor_mode"))
def filter_chain(columns: jnp.ndarray, specs: PredicateSpecs,
                 perm: jnp.ndarray, *, collect_rate: int,
                 sample_phase, tile: int = DEFAULT_TILE,
                 monitor_mode: str = "row") -> ChainResult:
    """Fused adaptive CNF chain over f32[C, R]; same contract as run_chain.

    monitor_mode: "row" = the paper's stride sampling (bit-exact vs the
    oracle); "block" = contiguous 128-lane slices of every Nth tile — the
    same sampling fraction, vector-friendly on TPU (DESIGN §3.4).
    """
    if monitor_mode not in ("row", "block"):
        raise ValueError(monitor_mode)
    n_cols, n_rows = columns.shape
    pad = (-n_rows) % tile
    if pad:
        columns = jnp.pad(columns, ((0, 0), (0, pad)))
    meta = _pack_meta(n_rows, collect_rate, sample_phase, monitor_mode)

    mask_i8, active, cut, gcut, nmon = filter_chain_pallas(
        columns, specs, perm.astype(jnp.int32), meta, tile=tile,
        interpret=_should_interpret())

    return _reduce_result(mask_i8, active, cut, gcut, nmon, specs, perm,
                          n_rows)


@functools.partial(jax.jit,
                   static_argnames=("collect_rate", "tile", "monitor_mode",
                                    "capacity", "fill"))
def filter_chain_compact(columns: jnp.ndarray, specs: PredicateSpecs,
                         perm: jnp.ndarray, *, collect_rate: int,
                         sample_phase, capacity: int,
                         tile: int = DEFAULT_TILE, monitor_mode: str = "row",
                         fill: float = 0.0
                         ) -> tuple[ChainResult, jnp.ndarray, jnp.ndarray]:
    """Fused chain + single-pass in-kernel compaction (one launch).

    Returns (ChainResult, packed f32[C, capacity], n_kept i32[]). The
    launch streams each tile HBM→VMEM exactly once and, while the tile is
    resident, packs its survivors (no ``argsort``) and appends them to the
    packed HBM output at the running survivor count — the columns are never
    read a second time. Saturation semantics match
    ``filter_exec.compact_fixed``: survivors beyond ``capacity`` are
    dropped and ``n_kept`` saturates.
    """
    if monitor_mode not in ("row", "block"):
        raise ValueError(monitor_mode)
    n_cols, n_rows = columns.shape
    pad = (-n_rows) % tile
    if pad:
        columns = jnp.pad(columns, ((0, 0), (0, pad)))
    meta = _pack_meta(n_rows, collect_rate, sample_phase, monitor_mode)

    mask_i8, active, cut, gcut, nmon, packed, tile_cnt = \
        filter_chain_pallas(columns, specs, perm.astype(jnp.int32), meta,
                            tile=tile, interpret=_should_interpret(),
                            compact=True, capacity=capacity, fill=fill)
    n_kept = jnp.minimum(jnp.sum(tile_cnt), capacity).astype(jnp.int32)

    result = _reduce_result(mask_i8, active, cut, gcut, nmon, specs, perm,
                            n_rows)
    return result, packed, n_kept


@functools.partial(jax.jit,
                   static_argnames=("collect_rate", "tile", "monitor_mode"))
def filter_chain_skip(columns: jnp.ndarray, specs: PredicateSpecs,
                      perm: jnp.ndarray, skip: SkipInfo, *,
                      collect_rate: int, sample_phase,
                      tile: int = DEFAULT_TILE,
                      monitor_mode: str = "row") -> ChainResult:
    """``filter_chain`` with zone-map-decided sub-tiles bypassing the chain.

    ``skip`` comes from ``skip_triage`` on the same batch. Decided sub-tiles
    start with no pending rows (work counters charge only ambiguous rows —
    the row-level work actually done); the monitor lane is untouched, so
    ordering statistics match the unskipped launch bit-exactly.
    """
    if monitor_mode not in ("row", "block"):
        raise ValueError(monitor_mode)
    n_rows = columns.shape[1]
    columns = _pad_cols(columns, tile)
    meta = _pack_meta(n_rows, collect_rate, sample_phase, monitor_mode)

    mask_i8, active, cut, gcut, nmon = filter_chain_pallas(
        columns, specs, perm.astype(jnp.int32), meta, tile=tile,
        interpret=_should_interpret(), skip_decisions=_skip_decisions(skip))

    return _reduce_result(mask_i8, active, cut, gcut, nmon, specs, perm,
                          n_rows, skip=skip)


@functools.partial(jax.jit,
                   static_argnames=("collect_rate", "tile", "monitor_mode",
                                    "capacity", "fill"))
def filter_chain_compact_skip(columns: jnp.ndarray, specs: PredicateSpecs,
                              perm: jnp.ndarray, skip: SkipInfo, *,
                              collect_rate: int, sample_phase, capacity: int,
                              tile: int = DEFAULT_TILE,
                              monitor_mode: str = "row", fill: float = 0.0
                              ) -> tuple[ChainResult, jnp.ndarray,
                                         jnp.ndarray]:
    """``filter_chain_compact`` behind the skip tier.

    Provably-passing sub-tiles are bulk-copied by the same in-kernel
    pack (their mask lanes arrive pre-set, no predicate work); provably-
    failing sub-tiles contribute nothing to the pack. Saturation semantics
    are unchanged.
    """
    if monitor_mode not in ("row", "block"):
        raise ValueError(monitor_mode)
    n_rows = columns.shape[1]
    columns = _pad_cols(columns, tile)
    meta = _pack_meta(n_rows, collect_rate, sample_phase, monitor_mode)

    mask_i8, active, cut, gcut, nmon, packed, tile_cnt = \
        filter_chain_pallas(columns, specs, perm.astype(jnp.int32), meta,
                            tile=tile, interpret=_should_interpret(),
                            compact=True, capacity=capacity, fill=fill,
                            skip_decisions=_skip_decisions(skip))
    n_kept = jnp.minimum(jnp.sum(tile_cnt), capacity).astype(jnp.int32)

    result = _reduce_result(mask_i8, active, cut, gcut, nmon, specs, perm,
                            n_rows, skip=skip)
    return result, packed, n_kept
