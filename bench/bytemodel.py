"""The least HBM bytes the filter's chain kernel needs, copied from the
arithmetic of ``benchmarks/roofline.py::filter_ingest_model`` so that the
program cannot change the yardstick.

Per grid tile of ``tile`` rows and ``n_cols`` f32 columns:

  chain only   every column of the tile read once, plus the i8 mask;
  fused        chain only, plus the survivors' packed bytes (the pass rate
               rounded up to the 128-lane copy granule, times the packed
               output's padded sublane rows);
  chain skip   the tier's summary pass (2·C f32 min/max per 128-row
               sub-tile, written and read, + C 128-bit Bloom maps), the
               columns of the sub-tiles not provably failed (a failed
               sub-tile needs no read) and the mask;
  fused skip   chain skip, plus the survivors and the provably passing
               sub-tiles' bulk copy.
"""

from __future__ import annotations

import math

LANES = 128
SUB_TILE = 128


def dma_rows(n_cols: int) -> int:
    """Sublane rows of the packed output (Mosaic's 1/2/4/8-row tiling)."""
    for rows in (1, 2, 4):
        if n_cols <= rows:
            return rows
    return -(-n_cols // 8) * 8


def tile_bytes(*, n_cols: int, tile: int, pass_rate: float,
               dtype_bytes: int = 4, skip_fraction: float = 0.0,
               skip_pass_fraction: float = 0.0, bloom: bool = False) -> dict:
    col_bytes = n_cols * tile * dtype_bytes
    mask_bytes = tile
    chain_only = col_bytes + mask_bytes
    p_quant = math.ceil(pass_rate * tile / LANES) * LANES / tile
    out_bytes = dma_rows(n_cols) * tile * dtype_bytes
    fused = chain_only + p_quant * out_bytes
    sub_tiles = tile // SUB_TILE
    summary = 2 * n_cols * 4 * sub_tiles
    if bloom:
        summary += n_cols * 16 * sub_tiles
    summary *= 2
    fail_frac = skip_fraction * (1.0 - skip_pass_fraction)
    pass_frac = skip_fraction * skip_pass_fraction
    chain_skip = summary + (1.0 - fail_frac) * col_bytes + mask_bytes
    fused_skip = chain_skip + min(p_quant + pass_frac, 1.0) * out_bytes
    return {"bytes_chain_only": chain_only, "bytes_fused": fused,
            "bytes_summary": summary, "bytes_chain_skip": chain_skip,
            "bytes_fused_skip": fused_skip}


def step_bytes(*, n_cols: int, rows: int, tile: int, compact: bool,
               skip: bool, n_kept: int = 0, tiles_pass: int = 0,
               tiles_fail: int = 0, bloom: bool = False) -> float:
    """Bytes one ``session.step`` needs: its grid tiles under the model
    above, with the pass rate and the decided shares taken from the
    step's own counters (``tiles_*`` count 128-row sub-tiles)."""
    n_tiles = -(-rows // tile)
    sub_total = n_tiles * (tile // SUB_TILE)
    decided = tiles_pass + tiles_fail
    per_tile = tile_bytes(
        n_cols=n_cols, tile=tile, pass_rate=n_kept / max(rows, 1),
        skip_fraction=decided / max(sub_total, 1),
        skip_pass_fraction=tiles_pass / max(decided, 1), bloom=bloom)
    key = ("bytes_fused_skip" if skip else "bytes_fused") if compact \
        else ("bytes_chain_skip" if skip else "bytes_chain_only")
    return n_tiles * per_tile[key]
