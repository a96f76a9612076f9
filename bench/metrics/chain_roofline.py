"""The chain kernel's share of its HBM roofline: the least bytes the
window's steps need (``bench/bytemodel.py``, with each step's own pass
count and decided sub-tiles), per chip, over the kernel's device time per
chip times the chip's HBM peak (``bench/peaks.json``). The HASHMIX
predicate's vector work has no published peak, so this is the share of
the HBM bound only."""

from bench import bytemodel

TILE = 2048


def read(run):
    t = run.trace
    k = None if t is None else t.kernel_s("adaptive_filter_chain")
    if k is None or not k:
        return None
    plan = run.config["plan"]
    compact = bool(plan.get("compact"))
    skip = plan.get("skip_tier", "off") != "off"
    n_cols = len(run.config["columns"])
    tiles = run.counts.get("tile_counts") or [(0, 0, 0)] * run.window.steps
    kept = run.counts.get("n_kept") or [0] * run.window.steps
    need = sum(bytemodel.step_bytes(
        n_cols=n_cols, rows=rows, tile=TILE, compact=compact, skip=skip,
        n_kept=n, tiles_pass=tp, tiles_fail=tf,
        bloom=plan.get("skip_tier") == "zonemap+bloom")
        for rows, n, (tp, tf, _) in zip(run.counts["rows"], kept, tiles))
    chips = run.cell["chips"]
    return need / chips / (k * run.peak["hbm_bytes_per_s"]) * 100.0
