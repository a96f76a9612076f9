"""Tile-statistics skip tier: zone maps + Bloom bits ahead of the chain.

The paper's controller decides per *row*; this tier decides per *chunk* —
the zone-map / Bloom data-skipping pattern of Delta/Iceberg, put in front
of the CNF chain kernel. Every batch is summarized in 128-row tiles
(``SKIP_TILE``): per-column min/max, plus an optional 128-bit Bloom bitmap
of ``round(x) mod 128`` keys for equality predicates. A pre-pass then
resolves whole tiles against the current chain:

  provably PASS  — every OR-group has a member whose zone range proves
                   every row passes → the tile skips the row-level kernel
                   and is bulk-copied into the survivor set;
  provably FAIL  — some OR-group's every member provably fails every row
                   → the tile is dropped without row-level work;
  ambiguous      — the tile reaches the existing row-level chain.

Provability per op (f32 min/max ``mn``/``mx`` of the tile's column):

  GT       pass: mn > t1            fail: mx <= t1
  LT       pass: mx < t1            fail: mn >= t1
  BETWEEN  pass: mn > t1 & mx < t2  fail: mx <= t1 | mn >= t2
  EQ       pass: round(mn) == round(mx) == round(t1)   (round is monotone)
           fail: round(t1) outside [round(mn), round(mx)], or the Bloom
                 bit of round(t1) mod 128 is clear (zonemap+bloom mode)
  HASHMIX  never provable (the mix destroys ordering) — always ambiguous.

All proofs are conservative: padding lanes (NaN here, zeros in the Pallas
glue) can only *weaken* a proof, never fire one spuriously, and provably-
pass tiles are still intersected with row validity downstream. The monitor
lane is deliberately untouched by the tier — sampled rows always execute
row-level on the full batch, so cut counts, group selectivities, and the
adopted permutations are bit-identical with the tier on or off (pinned by
``tests/test_skip_tier.py``).

``SkipTierTuner`` is the Cuttlefish-style online arm (arXiv 1802.09180):
``skip_tier="auto"`` scores the tier by measured ``us_per_row`` against the
plain path and — structurally — disables it when the ambiguous-tile
fraction says it cannot pay (shuffled layouts), so adversarial row orders
degrade gracefully to the current path.
"""

from __future__ import annotations

import numpy as np

from repro.core import predicates as pred_lib
from repro.core.engine.base import ChainResult, SkipInfo

SKIP_TILE = 128          # rows per zone-map tile (VPU lane quantum)
BLOOM_BITS = 128         # Bloom bitmap width per (column, tile) — 4 u32
#: jnp gather capacities are quantized to this many tiles (bounded jit
#: cache churn, same trick as compaction's CAPACITY_QUANTUM)
AMBIG_QUANTUM_TILES = 16

SKIP_TIER_MODES = ("off", "zonemap", "zonemap+bloom", "auto")


def eq_round(t1: float) -> float:
    """The EQ threshold key: round() in f32, exactly as the packed specs
    and the row-level ``jnp.round(x) == jnp.round(t1)`` see it.

    Single-sourced here so the tile resolver and the chain linter
    (``repro.analysis.chain_lint``) cannot disagree on quantization —
    ``Predicate.t1`` is a python float64 but every engine compares against
    its float32 packing, so any analysis that reasons from the f64 value
    can prove facts the runtime will contradict.
    """
    return float(np.round(np.float32(t1)))


def bloom_key(t1: float) -> int:
    """Bloom bit index of an EQ threshold: round32(t1) mod BLOOM_BITS —
    the same fold ``bloom_bitmap`` applies to the data side."""
    return int(np.mod(eq_round(t1), float(BLOOM_BITS)))


def host_pred_rows(specs) -> list[tuple[int, int, float, float]]:
    """Static per-predicate (column, op, t1, t2) rows read host-side.

    The chain is a trace-time constant (specs are closed over, never traced
    arguments), so tile resolution can branch on the op in python — unlike
    the row-level engines, which must dispatch dynamically under ``perm``.
    """
    col = np.asarray(specs.column)
    op = np.asarray(specs.op)
    t1 = np.asarray(specs.t1)
    t2 = np.asarray(specs.t2)
    return [(int(col[i]), int(op[i]), float(t1[i]), float(t2[i]))
            for i in range(specs.n)]


# ------------------------------------------------------------- summaries
def pad_to_tiles(columns, *, xp, fill=np.nan):
    """Pad f32[C, R] to a SKIP_TILE multiple; NaN lanes stay ambiguous."""
    n_rows = columns.shape[1]
    pad = (-n_rows) % SKIP_TILE
    if pad:
        columns = xp.pad(columns, ((0, 0), (0, pad)),
                         constant_values=np.float32(fill))
    return columns


def tile_summaries(columns, *, bloom: bool, xp):
    """Zone maps (+ optional Bloom bitmap) of one batch.

    ``columns``: f32[C, R]. Returns (mins f32[C, T], maxs f32[C, T],
    bloom bool[C, T, BLOOM_BITS] | None) with T = ceil(R / SKIP_TILE).
    NaN padding propagates into min/max, keeping ragged tail tiles
    unprovable. The Bloom bitmap is carried unpacked (one lane per bit) —
    a TPU lowering packs it into 4 u32 words per (column, tile), which is
    what ``benchmarks/roofline.py`` charges.
    """
    padded = pad_to_tiles(columns, xp=xp)
    n_cols = padded.shape[0]
    n_tiles = padded.shape[1] // SKIP_TILE
    tiles = padded.reshape(n_cols, n_tiles, SKIP_TILE)
    mins = tiles.min(axis=2)
    maxs = tiles.max(axis=2)
    bl = bloom_bitmap(padded, xp=xp) if bloom else None
    return mins, maxs, bl


def bloom_bitmap(columns, *, xp):
    """Bloom bitmap bool[C, T, BLOOM_BITS] of an already-padded batch.

    Key = round(x) mod BLOOM_BITS. Padding lanes (NaN here, zeros in the
    pallas glue) fold to key 0, which only ADDS a bit — weakening fail
    proofs, never strengthening them (conservative).
    """
    padded = pad_to_tiles(columns, xp=xp)
    n_cols = padded.shape[0]
    n_tiles = padded.shape[1] // SKIP_TILE
    tiles = padded.reshape(n_cols, n_tiles, SKIP_TILE)
    vals = xp.where(xp.isnan(tiles), xp.zeros_like(tiles), xp.round(tiles))
    keys = xp.mod(vals, float(BLOOM_BITS)).astype(np.int32)
    return (keys[..., None] ==
            xp.arange(BLOOM_BITS, dtype=np.int32)).any(axis=2)


# ------------------------------------------------------------ resolution
def resolve_tiles(mins, maxs, bloom, specs, *, xp) -> tuple:
    """Tri-state tile resolution against the chain's CNF structure.

    Returns (pass_tiles bool[T], fail_tiles bool[T]). A group provably
    passes a tile iff ANY member provably passes every row; it provably
    fails iff EVERY member provably fails every row. The tile passes the
    chain iff every group passes, fails iff any group fails. Evaluation
    order is irrelevant (proofs are order-free), so resolution needs no
    ``perm`` — the adopted permutation only steers the ambiguous tiles'
    row-level work.
    """
    rows = host_pred_rows(specs)
    n_tiles = mins.shape[1]
    all_pass, all_fail = [], []
    for col, op, t1, t2 in rows:
        mn, mx = mins[col], maxs[col]
        if op == pred_lib.OP_GT:
            ap, af = mn > t1, mx <= t1
        elif op == pred_lib.OP_LT:
            ap, af = mx < t1, mn >= t1
        elif op == pred_lib.OP_BETWEEN:
            ap = (mn > t1) & (mx < t2)
            af = (mx <= t1) | (mn >= t2)
        elif op == pred_lib.OP_EQ:
            r1 = eq_round(t1)
            rmn, rmx = xp.round(mn), xp.round(mx)
            ap = (rmn == r1) & (rmx == r1)
            af = (rmn > r1) | (rmx < r1)
            if bloom is not None:
                af = af | ~bloom[col, :, bloom_key(t1)]
        else:                                   # OP_HASHMIX: never provable
            ap = xp.zeros((n_tiles,), bool)
            af = xp.zeros((n_tiles,), bool)
        all_pass.append(ap)
        all_fail.append(af)

    groups = specs.groups
    pass_t = xp.ones((n_tiles,), bool)
    fail_t = xp.zeros((n_tiles,), bool)
    for members in specs.group_members:
        gp = all_pass[members[0]]
        gf = all_fail[members[0]]
        for m in members[1:]:
            gp = gp | all_pass[m]
            gf = gf & all_fail[m]
        pass_t = pass_t & gp
        fail_t = fail_t | gf
    return pass_t & ~fail_t, fail_t


def triage(columns, specs, *, bloom: bool, xp) -> SkipInfo:
    """Summaries + resolution in one call (the engine ``triage`` body)."""
    mins, maxs, bl = tile_summaries(columns, bloom=bloom, xp=xp)
    pass_t, fail_t = resolve_tiles(mins, maxs, bl, specs, xp=xp)
    n_amb = (~(pass_t | fail_t)).sum().astype(np.int32) if xp is np \
        else (~(pass_t | fail_t)).sum(dtype=np.int32)
    return SkipInfo(pass_tiles=pass_t, fail_tiles=fail_t, n_ambiguous=n_amb)


def quantize_amb_cap(n_ambiguous: int, n_tiles: int) -> int:
    """Static gather width (in tiles) for the jnp skip path.

    Rounded up to ``AMBIG_QUANTUM_TILES`` so the jit cache sees a bounded
    set of widths, capped at the batch's tile count (shuffled layouts peg
    at the full width — the tier then degenerates to the plain chain plus
    the summary pass, which is exactly what ``auto`` detects and disables).
    """
    q = AMBIG_QUANTUM_TILES
    want = max(int(n_ambiguous), 1)
    return min(int(-(-want // q)) * q, max(int(n_tiles), 1))


def tile_counters(skip: SkipInfo, xp):
    """(n_pass, n_fail, n_ambiguous) i32 scalars from a SkipInfo."""
    n_pass = skip.pass_tiles.sum(dtype=np.int32)
    n_fail = skip.fail_tiles.sum(dtype=np.int32)
    n_tiles = skip.pass_tiles.shape[0]
    return n_pass, n_fail, np.int32(n_tiles) - n_pass - n_fail


# --------------------------------------------------------- jnp skip chain
def run_chain_skip_jnp(columns, specs, perm, monitor, skip: SkipInfo,
                       *, amb_cap: int) -> ChainResult:
    """The jnp engine's skip-tier chain: gather → row-level → scatter.

    Only the ambiguous tiles' rows reach the row-level CNF evaluation: they
    are gathered into a static [C, amb_cap·SKIP_TILE] buffer (``amb_cap``
    from ``quantize_amb_cap`` — the caller syncs the ambiguous count once
    per step), evaluated there, and their mask scattered back; provably-
    pass tiles are bulk-set, provably-fail tiles stay cut. Unlike the
    masked off-path — which evaluates every predicate full-width — the
    expensive predicates here genuinely run at the ambiguous width, which
    is where the measured clustered-layout win comes from. The monitor
    lane runs on the FULL columns exactly as the off path does, so the
    ordering statistics are bit-identical with the tier on or off. Work
    counters charge only the (valid) ambiguous rows — the row-level work a
    short-circuiting engine behind this tier would actually do.
    """
    import jax.numpy as jnp

    from repro.core import filter_exec

    n_cols, n_rows = columns.shape
    n_tiles = skip.pass_tiles.shape[0]
    amb = ~(skip.pass_tiles | skip.fail_tiles)

    # gather map: the k-th ambiguous tile's index lands in slot k; tiles
    # beyond amb_cap (caller guarantees none) and non-ambiguous tiles dump
    pos = jnp.cumsum(amb.astype(jnp.int32)) - 1
    dest = jnp.where(amb & (pos < amb_cap), pos, amb_cap)
    tile_idx = jnp.full((amb_cap + 1,), n_tiles, jnp.int32) \
        .at[dest].set(jnp.arange(n_tiles, dtype=jnp.int32), mode="drop") \
        [:amb_cap]

    padded = pad_to_tiles(columns, xp=jnp)
    tiles = padded.reshape(n_cols, n_tiles, SKIP_TILE)
    g = jnp.take(tiles, tile_idx, axis=1, mode="fill",
                 fill_value=float("nan"))
    gcols = g.reshape(n_cols, amb_cap * SKIP_TILE)
    gid = tile_idx[:, None] * SKIP_TILE + jnp.arange(SKIP_TILE)[None, :]
    valid = (gid < n_rows).reshape(-1)           # unused slots + ragged tail

    amb_mask, work, active, _ = filter_exec.run_chain_masks(
        gcols, specs, perm, valid=valid, count_tiles=False)

    mask_tiles = jnp.broadcast_to(skip.pass_tiles[:, None],
                                  (n_tiles, SKIP_TILE))
    mask_tiles = mask_tiles.at[tile_idx].set(
        amb_mask.reshape(amb_cap, SKIP_TILE), mode="drop")
    mask = mask_tiles.reshape(-1)[:n_rows]

    cut, gcut, n_mon, mon_cost, _ = filter_exec.run_monitor(
        columns, specs, monitor.collect_rate, monitor.sample_phase)

    n_pass_t, n_fail_t, n_amb_t = tile_counters(skip, jnp)
    return ChainResult(
        mask=mask, work_units=work, active_before=active,
        cut_counts=cut, n_monitored=n_mon, monitor_cost=mon_cost,
        group_cut_counts=gcut,
        n_tiles_pass=n_pass_t, n_tiles_fail=n_fail_t,
        n_tiles_ambiguous=n_amb_t)


# ------------------------------------------------------------- auto tuner
class SkipTierTuner:
    """Online decision for ``skip_tier="auto"`` (one per session).

    Deterministic schedule, two arms ("off" vs the zone-map tier): the
    first ``2·warmup`` steps alternate arms to seed both EMAs (each arm's
    first sample is discarded — it pays compilation); afterwards the
    faster EMA wins, re-probing the losing arm every ``probe_period``
    steps so drifting layouts can flip the decision. One structural rule
    overrides the clocks: when the observed ambiguous-tile fraction says
    nearly every tile reaches the row-level kernel anyway
    (``>= ambig_off_frac``), the tier provably cannot pay — choose "off"
    without waiting for wall-clock evidence. That is the graceful
    degradation on shuffled layouts, and it is what the conformance test
    pins (timing EMAs alone would be CI-noise-flaky).
    """

    def __init__(self, on_mode: str, *, warmup: int = 3,
                 probe_period: int = 64, ambig_off_frac: float = 0.9,
                 ema: float = 0.3):
        if on_mode not in ("zonemap", "zonemap+bloom"):
            raise ValueError(on_mode)
        self.on_mode = on_mode
        self.warmup = warmup
        self.probe_period = probe_period
        self.ambig_off_frac = ambig_off_frac
        self.ema = ema
        self.step_idx = 0
        self.us_ema = {"off": None, on_mode: None}
        self.samples = {"off": 0, on_mode: 0}
        self.ambig_frac: float | None = None

    @property
    def active_mode(self) -> str:
        """The arm a non-probe step would run right now."""
        if self.ambig_frac is not None \
                and self.ambig_frac >= self.ambig_off_frac:
            return "off"
        on, off = self.us_ema[self.on_mode], self.us_ema["off"]
        if on is None:
            return self.on_mode
        if off is None:
            return "off"
        return self.on_mode if on <= off else "off"

    def choose(self) -> str:
        """Arm for the CURRENT step (advance with ``observe`` afterwards)."""
        if self.step_idx < 2 * self.warmup:
            return self.on_mode if self.step_idx % 2 == 0 else "off"
        active = self.active_mode
        if self.probe_period and self.step_idx % self.probe_period == 0:
            other = "off" if active != "off" else self.on_mode
            # never probe the tier back on when the layout structurally
            # rules it out — that is the adversarial case auto defends
            if not (other != "off" and self.ambig_frac is not None
                    and self.ambig_frac >= self.ambig_off_frac):
                return other
        return active

    def observe(self, mode: str, us_per_row: float,
                ambig_frac: float | None = None) -> None:
        self.step_idx += 1
        if ambig_frac is not None:
            self.ambig_frac = float(ambig_frac)
        self.samples[mode] += 1
        if self.samples[mode] <= 1:
            return                     # first sample per arm pays compile
        prev = self.us_ema[mode]
        self.us_ema[mode] = us_per_row if prev is None \
            else (1 - self.ema) * prev + self.ema * us_per_row
