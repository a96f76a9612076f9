"""Pallas TPU kernel: fused adaptive predicate chain over columnar tiles.

Spark evaluates the chain row-at-a-time inside ``processNext``; the TPU
adaptation (DESIGN §3) processes rows in VMEM tiles:

  * one grid step = one (C, TILE) column tile, streamed HBM→VMEM once —
    the whole chain is FUSED into a single pass over the data (Spark's
    operator iterator touches rows once too, but pays per-row dispatch;
    XLA's unfused jnp path would touch HBM once per predicate);
  * predicates are evaluated vector-wise in the adaptive permutation order.
    CNF structure (OR within a group, AND across groups) is tracked with a
    running per-tile OR accumulator: members of the open group only
    evaluate rows not yet passed (vector analogue of the OR short-circuit),
    and when a group closes its accumulator ANDs into the running mask; a
    tree (AND-terms inside groups, static ``terms``) adds a term AND
    accumulator, so an atom only evaluates rows its term has not failed;
  * when a tile has no pending rows for a position, that predicate is
    SKIPPED for the tile (``lax.cond`` — tile-granular short-circuit, the
    vector analogue of the row-level early exit);
  * the monitor lane (paper §2.1) evaluates ALL predicates on
    stride-sampled rows and emits per-tile numCut / per-GROUP cut (and,
    for a tree, per-TERM cut) / monitored counts;
  * per-tile ``active_before`` counters reproduce the row-level work model
    exactly (they count rows pending before each chain position), so the
    paper's cost accounting survives vectorization bit-exactly.

Memory layout: predicate spec arrays (i32/f32[P]) live in SMEM (scalar
dispatch data); column tiles in VMEM. Each grid step first relayouts its
(C, TILE) tile into a (C, TILE/128, 128) VMEM scratch, so every
predicate reads one column as full (8, 128) vregs by a dynamic index on
the leading dim. Row masks travel as f32 0/1 lanes of that (TILE/128,
128) shape — never as bool vectors through ``cond``/``switch``/``where``,
which Mosaic cannot legalize — and leave the kernel as an int8 block.
Per-tile counters are scalars, so they go to SMEM output blocks. The CNF
group ids ride twice: as an SMEM i32[P] vector for the perm-ordered chain
lane (the permutation is dynamic) and as a STATIC python tuple for the
monitor lane's group reduction (user order → unrolled at trace time).

Grid-step cost model (for §Roofline): bytes/tile = C·TILE·4 in + TILE
mask out; with fused compaction the tile additionally writes its
survivors (≈ p·C·TILE·4 at pass-rate p) — the columns are read once and
never again. FLOPs/tile ≈ TILE · Σ_{k ≤ stop} cost(perm[k]) —
memory-bound at ~0.25–2 FLOP/byte unless expensive (HASHMIX) predicates
dominate. (``benchmarks/roofline.py::filter_ingest_model`` renders this
model.)

Fused compaction (``compact=True``, one launch, no sort, sequential
grid): while the tile is resident in VMEM, each 128-row lane row of the
tile is packed in-register — an inclusive lane cumsum (7 ``roll`` steps)
gives every survivor's slot, a 7-step binary search over it gives every
output lane its source lane, and a lane gather (``take_along_axis``)
moves the values. The packed rows are then appended to a staging buffer
at the running survivor count: a dynamic ``roll`` by ``count % 128`` and
two stores at 128-aligned offsets stitch each row onto the stream. Every
TILE survivors the stage is copied out and DMA'd to the HBM output at the
next TILE-aligned offset; the last grid step flushes the partial stage
with a ``fill`` tail and DMAs ``fill`` blocks over the rest of the
capacity window. Offsets, the stage count and the in-flight DMA flag are
SMEM scratch carried across grid steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import predicates as pred_lib
from repro.core.engine.base import CHAIN_TILE

DEFAULT_TILE = CHAIN_TILE  # rows per grid step; multiple of 128 (VPU lanes)
STAT_TILE = 128      # zone-map statistics granularity (= skip_tier.SKIP_TILE)
LANES = 128


def _stats_kernel(cols_ref, min_ref, max_ref, *, tile: int):
    """Skip-tier pre-pass: per-STAT_TILE column min/max for one grid tile.

    One (C, TILE) tile in VMEM → (1, C, TILE/STAT_TILE) zone-map
    summaries. The reshape splits the lane dimension into (sub, 128) so
    each reduction runs over full VPU lanes; a production Mosaic kernel
    would fuse this into the ingest DMA, but as a separate launch it still
    reads each byte exactly once and writes only TILE/STAT_TILE summary
    lanes per column.
    """
    sub = tile // STAT_TILE
    x = cols_ref[:, :]                                   # f32[C, TILE]
    t3 = x.reshape(cols_ref.shape[0], sub, STAT_TILE)
    min_ref[0, :, :] = t3.min(axis=2)
    max_ref[0, :, :] = t3.max(axis=2)


def tile_stats_pallas(columns: jnp.ndarray, *, tile: int = DEFAULT_TILE,
                      interpret: bool = True):
    """Zone-map summaries of f32[C, Rp] (Rp % tile == 0).

    Returns (mins f32[C, Rp/STAT_TILE], maxs f32[C, Rp/STAT_TILE]).

    The launch writes tile-major f32[n_tiles, C, sub] blocks — each grid
    step owns one fully-covered (1, C, sub) block, so every block's
    minormost dim is its array's full lane extent (``kernel_audit``'s
    alignment rule; a (C, sub)-strided lane tile would make Mosaic retile
    the summary rows on every step). The transpose back to the external
    f32[C, Rp/STAT_TILE] contract is XLA glue over kilobytes.
    """
    n_cols, n_rows_p = columns.shape
    if n_rows_p % tile:
        raise ValueError(f"padded rows {n_rows_p} not a multiple of {tile}")
    n_tiles = n_rows_p // tile
    sub = tile // STAT_TILE
    kernel = functools.partial(_stats_kernel, tile=tile)
    out_spec = pl.BlockSpec((1, n_cols, sub), lambda i: (i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((n_tiles, n_cols, sub), jnp.float32)
    mins, maxs = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((n_cols, tile), lambda i: (0, i))],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        name="adaptive_filter_tile_stats",
    )(columns)

    def _flat(a):                    # [T, C, sub] → [C, T·sub]
        return a.transpose(1, 0, 2).reshape(n_cols, n_tiles * sub)

    return _flat(mins), _flat(maxs)


def _lanes(b):
    """bool lanes → f32 0/1 lanes (the kernel's only mask currency)."""
    return jnp.where(b, 1.0, 0.0)


def _eval_pred(x, op, t1, t2, rounds):
    """Evaluate one predicate on column values x f32[S, 128] → f32 0/1.

    ``op``/``t1``/... are dynamic scalars read from SMEM. The op dispatch
    is a scalar switch, so only the selected branch's vector work executes
    (HASHMIX's mix loop only runs for HASHMIX predicates — the cost
    heterogeneity the ordering exploits is preserved on-chip).
    """
    def _hashmix():
        def body(_, y):
            y = y * pred_lib.MIX_MUL + pred_lib.MIX_ADD
            return y - jnp.floor(y / pred_lib.MIX_MOD) * pred_lib.MIX_MOD
        mixed = jax.lax.fori_loop(0, jnp.maximum(rounds, 1), body, x)
        return _lanes(mixed > t1)

    return jax.lax.switch(op, [
        lambda: _lanes(x > t1),
        lambda: _lanes(x < t1),
        lambda: _lanes(jnp.logical_and(x > t1, x < t2)),
        lambda: _lanes(jnp.round(x) == jnp.round(t1)),
        _hashmix,
        lambda: _lanes(pred_lib.in_mask(x, t1, t2)),
    ])


def _lane_cumsum(m):
    """Inclusive prefix sum along the lanes of each row of f32[S, 128]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    shift = 1
    while shift < LANES:
        m = m + jnp.where(lane >= shift, pltpu.roll(m, shift, 1), 0.0)
        shift *= 2
    return m


def _pack_rows(values, mask, fill):
    """Pack each 128-lane row's survivors to the row's front, in order.

    ``values``: list of f32[S, 128] columns; ``mask``: f32 0/1[S, 128].
    Output lane j of a row takes the row's (j+1)-th survivor, found by a
    binary search of the inclusive lane cumsum (the count of lanes whose
    cumsum is ≤ j); lanes past the row's count hold ``fill``.
    """
    cs = _lane_cumsum(mask)
    lane = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1)
    lane_f = lane.astype(jnp.float32)
    src = jnp.zeros(mask.shape, jnp.int32)
    step = LANES // 2
    while step:
        probe = jnp.take_along_axis(cs, src + (step - 1), axis=1)
        src = jnp.where(probe <= lane_f, src + step, src)
        step //= 2
    # lane j is a survivor slot iff its source's cumsum passes j (past the
    # row's count the search saturates at lane 127, whose cumsum ≤ j)
    keep = jnp.take_along_axis(cs, src, axis=1) > lane_f
    return [jnp.where(keep, jnp.take_along_axis(v, src, axis=1), fill)
            for v in values]


def _kernel(# --- SMEM scalar/spec refs ---
            col_ref, op_ref, t1_ref, t2_ref, rounds_ref, perm_ref, group_ref,
            meta_ref,  # i32[4]: (n_rows, collect_rate, sample_phase, mode)
            # --- skip-tier SMEM blocks (skip=True), VMEM data, outputs,
            # scratch ---
            *refs,  # [term_ref,] [pass_ref, fail_ref,] cols_ref, mask_ref,
                    # active_ref, cut_ref, gcut_ref, nmon_ref [, tcut_ref]
                    # [, out_hbm, cnt_ref], xs_ref [, stage_ref, buf_ref,
                    # sem, state_ref]
            n_preds: int, tile: int, groups: tuple, terms: tuple = (),
            fill: float = 0.0, skip: bool = False, compact: bool = False,
            out_blocks: int = 0):
    if terms:
        term_ref, refs = refs[0], refs[1:]
    if skip:
        pass_ref, fail_ref = refs[0], refs[1]
        refs = refs[2:]
    cols_ref, mask_ref, active_ref, cut_ref, gcut_ref, nmon_ref = refs[:6]
    refs = refs[6:]
    if terms:
        tcut_ref, refs = refs[0], refs[1:]
    if compact:
        out_hbm, cnt_ref, xs_ref, stage_ref, buf_ref, sem, state_ref = refs
    else:
        (xs_ref,) = refs
    t = pl.program_id(0)
    n_rows = meta_ref[0]
    collect_rate = meta_ref[1]
    sample_phase = meta_ref[2]
    block_mode = meta_ref[3]
    flat = len(set(groups)) == len(groups)   # static: all-singleton groups
    n_cols = cols_ref.shape[0]
    sub = tile // LANES
    shape = (sub, LANES)

    # one relayout per tile: (C, TILE) → (C, TILE/128, 128) full vregs
    xs_ref[...] = cols_ref[...].reshape(n_cols, sub, LANES)

    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    local = row * LANES + lane
    gidx = t * tile + local
    valid = _lanes(gidx < n_rows)                           # f32[S, 128]

    # ------------------------------------------------------ skip-tier lanes
    # Zone-map triage (skip-tier pre-pass) resolved this grid tile's
    # STAT_TILE sub-tiles (one per lane row); broadcast the i32 decisions
    # from SMEM into lane masks. Decided sub-tiles start with no pending
    # rows, so the existing ``alive > 0`` cond gives tile-granular skip for
    # free — a fully decided grid tile evaluates ZERO predicates (with
    # BlockSpec streaming the tile regardless; DMA-gating failed tiles is
    # ROADMAP S2's open half).
    if skip:
        pass_lane = jnp.zeros(shape, jnp.float32)
        fail_lane = jnp.zeros(shape, jnp.float32)
        for j in range(sub):                 # static unroll: SMEM scalars
            pass_lane = jnp.where(row == j, pass_ref[0, j].astype(jnp.float32),
                                  pass_lane)
            fail_lane = jnp.where(row == j, fail_ref[0, j].astype(jnp.float32),
                                  fail_lane)
        pass_lane = jnp.minimum(pass_lane, 1.0)
        decided = jnp.minimum(pass_lane + jnp.minimum(fail_lane, 1.0), 1.0)

    # ----------------------------------------------------------- chain lane
    # survivors of closed groups; decided sub-tiles bypass the row level.
    # All masks are f32 0/1: AND = product, OR = max, and a dynamic group
    # boundary flag b selects by arithmetic (x·(1-b) + y·b), never a select
    # over bool vectors.
    mask = valid if not skip else valid * (1.0 - decided)
    if terms:
        mask = _tree_lane(mask, xs_ref, col_ref, op_ref, t1_ref, t2_ref,
                          rounds_ref, perm_ref, group_ref, term_ref,
                          active_ref, n_preds=n_preds, shape=shape)
    else:
        group_or = None                      # passes within the open group
        for k in range(n_preds):             # P static → unrolled on-chip
            pidx = perm_ref[k]
            # group-boundary flags: static when flat; dynamic SMEM scalar
            # comparisons otherwise (the permutation is data-dependent).
            if flat or k == 0:
                pending = mask
            else:
                first = jnp.where(
                    group_ref[perm_ref[k - 1]] != group_ref[pidx], 1.0, 0.0)
                group_or = group_or * (1.0 - first)
                pending = mask * (1.0 - group_or)
            alive = jnp.sum(pending)
            active_ref[0, k] = alive
            res = jax.lax.cond(
                alive > 0.0,
                lambda: _eval_pred(xs_ref[col_ref[pidx]], op_ref[pidx],
                                   t1_ref[pidx], t2_ref[pidx],
                                   rounds_ref[pidx]),
                lambda: jnp.zeros(shape, jnp.float32),  # tile short-circuit
            )
            group_or = res if (flat or k == 0) \
                else jnp.maximum(group_or, res)
            if flat or k == n_preds - 1:
                mask = mask * group_or
            else:
                closes = jnp.where(
                    group_ref[perm_ref[k + 1]] != group_ref[pidx], 1.0, 0.0)
                mask = mask * (1.0 - closes * (1.0 - group_or))
    if skip:
        # bulk-keep provably-passing sub-tiles (valid rows only — zero
        # padding can satisfy a proof but never survives); the fused
        # compaction below then bulk-copies them with no predicate work.
        mask = jnp.maximum(mask, pass_lane * valid)
    mask_ref[...] = mask.astype(jnp.int32).astype(jnp.int8)

    # ------------------------------------------------------ fused compaction
    if compact:
        _compact_tile(t, mask, xs_ref, out_hbm, cnt_ref, stage_ref, buf_ref,
                      sem, state_ref, tile=tile, fill=fill,
                      out_blocks=out_blocks)

    # --------------------------------------------------------- monitor lane
    # row mode (paper-exact): deterministic stride over the GLOBAL row index
    # (paper §2.1). block mode (TPU-native, DESIGN §3.4): the same sampling
    # FRACTION delivered as one contiguous 128-lane slice of every
    # ``tile_stride``-th tile — scattered single rows cost a full vector op
    # each on a VPU, a contiguous slice costs one.
    row_sampled = _lanes(((gidx + sample_phase) % collect_rate) == 0)
    tile_stride = jnp.maximum(collect_rate * LANES // tile, 1)
    block_tile = jnp.where((t + sample_phase) % tile_stride == 0, 1.0, 0.0)
    block_sampled = block_tile * _lanes(local < LANES)
    use_block = jnp.where(block_mode == 1, 1.0, 0.0)
    sampled = valid * (use_block * block_sampled
                       + (1.0 - use_block) * row_sampled)
    n_sampled = jnp.sum(sampled)
    nmon_ref[0, 0] = n_sampled

    members: list[list[int]] = [[] for _ in range(max(groups) + 1)]
    for i, g in enumerate(groups):
        members[g].append(i)
    term_members, group_terms = pred_lib.tree_structure(groups, terms)

    @pl.when(n_sampled > 0.0)
    def _monitor():
        fails = []
        for p in range(n_preds):             # ALL predicates, user order
            res = _eval_pred(xs_ref[col_ref[p]], op_ref[p], t1_ref[p],
                             t2_ref[p], rounds_ref[p])
            fail = 1.0 - res
            fails.append(fail)
            cut_ref[0, p] = jnp.sum(sampled * fail)
        if terms:                            # a group's members are terms
            tfails = []
            for ti, mem in enumerate(term_members):
                tpass = 1.0 - fails[mem[0]]
                for m in mem[1:]:
                    tpass = tpass * (1.0 - fails[m])
                tfails.append(1.0 - tpass)
                tcut_ref[0, ti] = jnp.sum(sampled * tfails[-1])
            fails, members_of = tfails, group_terms
        else:
            members_of = members
        for gi, mem in enumerate(members_of):   # static group reduction
            gfail = fails[mem[0]]
            for m in mem[1:]:
                gfail = gfail * fails[m]
            gcut_ref[0, gi] = jnp.sum(sampled * gfail)

    @pl.when(n_sampled == 0.0)
    def _no_monitor():
        for p in range(n_preds):
            cut_ref[0, p] = 0.0
        for gi in range(len(members)):
            gcut_ref[0, gi] = 0.0
        for ti in range(len(term_members)):
            tcut_ref[0, ti] = 0.0


def _tree_lane(mask, xs_ref, col_ref, op_ref, t1_ref, t2_ref, rounds_ref,
               perm_ref, group_ref, term_ref, active_ref, *, n_preds: int,
               shape):
    """The chain lane of a tree (AND-terms inside OR-groups).

    ``term_and`` holds the rows every atom of the open term has passed so
    far, ``group_or`` the rows a closed term of the open group passed, so
    a row is pending for position k when it is alive, its group has not
    passed yet and its term has not failed yet — the tile short-circuit
    then also fires inside a term. Term and group boundaries are dynamic
    SMEM comparisons of the perm-ordered ids, selected by arithmetic as in
    the CNF lane. Returns the mask after the last group closes.
    """
    group_or = jnp.zeros(shape, jnp.float32)
    term_and = jnp.ones(shape, jnp.float32)
    for k in range(n_preds):
        pidx = perm_ref[k]
        if k > 0:
            prev = perm_ref[k - 1]
            first_g = jnp.where(group_ref[prev] != group_ref[pidx], 1.0, 0.0)
            first_t = jnp.where(term_ref[prev] != term_ref[pidx], 1.0, 0.0)
            group_or = group_or * (1.0 - first_g)
            term_and = term_and * (1.0 - first_t) + first_t
        pending = mask * (1.0 - group_or) * term_and
        alive = jnp.sum(pending)
        active_ref[0, k] = alive
        res = jax.lax.cond(
            alive > 0.0,
            lambda: _eval_pred(xs_ref[col_ref[pidx]], op_ref[pidx],
                               t1_ref[pidx], t2_ref[pidx], rounds_ref[pidx]),
            lambda: jnp.zeros(shape, jnp.float32),   # tile short-circuit
        )
        term_and = term_and * res
        if k == n_preds - 1:
            closes_t = closes_g = 1.0
        else:
            nxt = perm_ref[k + 1]
            closes_t = jnp.where(term_ref[nxt] != term_ref[pidx], 1.0, 0.0)
            closes_g = jnp.where(group_ref[nxt] != group_ref[pidx], 1.0, 0.0)
        group_or = jnp.maximum(group_or, closes_t * term_and)
        mask = mask * (1.0 - closes_g * (1.0 - group_or))
    return mask


def _compact_tile(t, mask, xs_ref, out_hbm, cnt_ref, stage_ref, buf_ref, sem,
                  state_ref, *, tile: int, fill: float, out_blocks: int):
    """Append this tile's survivors to the packed HBM output.

    ``state_ref`` (SMEM i32[3], carried across the sequential grid):
    survivors in the stage, TILE blocks already sent, DMA in flight.
    ``stage_ref`` f32[rows, 2·TILE] holds < TILE survivors between steps,
    so one tile's ≤ TILE more always fit; ``buf_ref`` is the DMA source.
    Both have the output's ``dma_rows`` sublanes; rows past C are unused.
    """
    n_cols = xs_ref.shape[0]
    sub = tile // LANES
    last = pl.num_programs(0) - 1

    def dma(block):
        return pltpu.make_async_copy(
            buf_ref, out_hbm.at[:, pl.ds(pl.multiple_of(block * tile, tile),
                                         tile)], sem.at[0])

    def wait_inflight():
        @pl.when(state_ref[2] == 1)
        def _():
            dma(0).wait()
            state_ref[2] = 0

    @pl.when(t == 0)
    def _init():
        state_ref[0] = 0
        state_ref[1] = 0
        state_ref[2] = 0

    packed = _pack_rows([xs_ref[c] for c in range(n_cols)], mask, fill)
    total = jnp.sum(mask)
    cnt_ref[0, 0] = total.astype(jnp.int32)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    n = state_ref[0]
    for r in range(sub):                     # stitch rows onto the stream
        off = n % LANES
        lo = pl.ds(pl.multiple_of(n - off, LANES), LANES)
        hi = pl.ds(pl.multiple_of(n - off + LANES, LANES), LANES)
        for c in range(n_cols):
            rolled = pltpu.roll(packed[c][r:r + 1, :], off, 1)
            stage_ref[c:c + 1, lo] = jnp.where(
                lane >= off, rolled, stage_ref[c:c + 1, lo])
            stage_ref[c:c + 1, hi] = rolled
        n = n + jnp.sum(mask[r:r + 1, :]).astype(jnp.int32)
    state_ref[0] = n

    @pl.when(state_ref[0] >= tile)           # a full TILE of survivors
    def _flush():
        wait_inflight()
        buf_ref[...] = stage_ref[:, :tile]
        stage_ref[:, :tile] = stage_ref[:, tile:]
        state_ref[0] = state_ref[0] - tile

        @pl.when(state_ref[1] < out_blocks)  # saturated: drop survivors
        def _send():
            dma(state_ref[1]).start()
            state_ref[2] = 1

        state_ref[1] = state_ref[1] + 1

    @pl.when(t == last)                      # partial stage + fill tail
    def _finish():
        wait_inflight()
        lanes = jax.lax.broadcasted_iota(jnp.int32, buf_ref.shape, 1)
        buf_ref[...] = jnp.where(lanes < state_ref[0], stage_ref[:, :tile],
                                 fill)
        sent = state_ref[1]

        @pl.when(sent < out_blocks)
        def _tail():
            dma(sent).start()
            dma(sent).wait()
            buf_ref[...] = jnp.full(buf_ref.shape, fill, jnp.float32)

            def start(b, carry):
                dma(b).start()
                return carry

            def wait(b, carry):
                dma(b).wait()
                return carry

            jax.lax.fori_loop(sent + 1, out_blocks, start, 0)
            jax.lax.fori_loop(sent + 1, out_blocks, wait, 0)


def dma_rows(n_cols: int) -> int:
    """Sublane rows of the packed HBM output for ``n_cols`` columns.

    Mosaic tiles a small f32 array's sublanes by 1, 2, 4 or 8 rows, and a
    DMA may only move whole tiles; the output is padded to that tiling and
    the wrapper slices the real columns back out.
    """
    for rows in (1, 2, 4):
        if n_cols <= rows:
            return rows
    return -(-n_cols // 8) * 8


def filter_chain_pallas(columns: jnp.ndarray, specs, perm: jnp.ndarray,
                        meta: jnp.ndarray, *, tile: int = DEFAULT_TILE,
                        interpret: bool = True, compact: bool = False,
                        capacity: int = 0, fill: float = 0.0,
                        skip_decisions=None):
    """Launch the fused chain kernel.

    columns: f32[C, R_padded] with R_padded % tile == 0.
    meta:    i32[4] = (n_rows_actual, collect_rate, sample_phase, mode).
    skip_decisions: optional (pass i32[Rp/STAT_TILE], fail i32[Rp/STAT_TILE])
    from the zone-map triage pre-pass — decided sub-tiles bypass the
    row-level chain (the monitor lane still samples them row-level, keeping
    ordering statistics identical with the tier on or off).
    Returns (mask i8[n_tiles, TILE/128, 128], active f32[n_tiles, 1, P],
             cut f32[n_tiles, 1, P], gcut f32[n_tiles, 1, G],
             nmon f32[n_tiles, 1, 1]); with ``compact=True`` additionally
    (packed f32[C, capacity] — the survivors in row order, ``fill`` tail,
    survivors past ``capacity`` dropped — and cnt i32[n_tiles, 1, 1]); for
    a tree (``specs.term`` set) lastly tcut f32[n_tiles, 1, T], the
    monitor lane's rows cut per AND-term.
    """
    n_cols, n_rows_p = columns.shape
    if n_rows_p % tile or tile % LANES:
        raise ValueError(f"padded rows {n_rows_p} not a multiple of tile "
                         f"{tile} (a multiple of {LANES})")
    if compact and capacity < 1:
        raise ValueError(f"compact=True needs capacity >= 1, got {capacity}")
    n_tiles = n_rows_p // tile
    sub = tile // LANES
    n_preds = int(specs.column.shape[0])
    groups = specs.groups                    # static tuple (pytree aux)
    terms = specs.term                       # static; () → no terms
    n_groups = max(groups) + 1
    garr = jnp.asarray(groups, jnp.int32)

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)

    def counters(width, dtype=jnp.float32):
        return (smem((None, 1, width), lambda i: (i, 0, 0)),
                jax.ShapeDtypeStruct((n_tiles, 1, width), dtype))

    outs = [(pl.BlockSpec((None, sub, LANES), lambda i: (i, 0, 0)),
             jax.ShapeDtypeStruct((n_tiles, sub, LANES), jnp.int8)),
            counters(n_preds), counters(n_preds), counters(n_groups),
            counters(1)]
    if terms:
        outs.append(counters(specs.n_terms))
    scratch = [pltpu.VMEM((n_cols, sub, LANES), jnp.float32)]
    out_blocks = 0
    if compact:
        out_blocks = -(-capacity // tile)
        rows = dma_rows(n_cols)
        outs += [(pl.BlockSpec(memory_space=pl.ANY),
                  jax.ShapeDtypeStruct((rows, out_blocks * tile),
                                       jnp.float32)),
                 counters(1, jnp.int32)]
        scratch += [pltpu.VMEM((rows, 2 * tile), jnp.float32),
                    pltpu.VMEM((rows, tile), jnp.float32),
                    pltpu.SemaphoreType.DMA((1,)),
                    pltpu.SMEM((3,), jnp.int32)]

    skip = skip_decisions is not None
    kernel = functools.partial(_kernel, n_preds=n_preds, tile=tile,
                               groups=groups, terms=terms, fill=fill,
                               skip=skip, compact=compact,
                               out_blocks=out_blocks)
    in_specs = [smem()] * 8
    args = [specs.column, specs.op, specs.t1, specs.t2, specs.rounds, perm,
            garr, meta]
    if terms:
        in_specs.append(smem())
        args.append(jnp.asarray(terms, jnp.int32))
    if skip:
        in_specs += [smem((None, 1, sub), lambda i: (i, 0, 0))] * 2
        args += [d.reshape(n_tiles, 1, sub) for d in skip_decisions]
    in_specs.append(pl.BlockSpec((n_cols, tile), lambda i: (0, i)))
    args.append(columns)
    res = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[o[0] for o in outs],
        out_shape=[o[1] for o in outs],
        scratch_shapes=scratch,
        # compaction carries the stream position across grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if compact else "parallel",)),
        interpret=interpret,
        name="adaptive_filter_chain_skip" if skip else "adaptive_filter_chain",
    )(*args)
    res = list(res)
    tcut = res.pop(5) if terms else None
    if compact:
        res[5] = res[5][:n_cols, :capacity]
    return (*res, tcut) if terms else tuple(res)
