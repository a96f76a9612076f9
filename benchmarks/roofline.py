"""Roofline harness: renders EXPERIMENTS §Roofline from the dry-run
artifacts (artifacts/dryrun/*.json). One row per (arch × shape × mesh):
three terms in seconds, dominant bottleneck, MODEL_FLOPS/HLO_FLOPs ratio,
and a one-line what-would-move-it-down note.

Also renders the INGESTION grid-step byte model (``filter_ingest_model``):
per-tile HBM traffic of the fused filter kernel with in-kernel compaction
versus the legacy kernel + argsort ``compact_fixed`` path, as a function of
the stream pass-rate — the analytic companion to ``benchmarks/ingest.py``'s
measured sweep."""

from __future__ import annotations

import json
import pathlib

ART = pathlib.Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"


def filter_ingest_model(*, n_cols: int = 4, tile: int = 2048,
                        pass_rate: float = 0.25, dtype_bytes: int = 4,
                        batch_rows: int = 65536,
                        skip_fraction: float = 0.0,
                        skip_pass_fraction: float = 0.0,
                        bloom: bool = False) -> dict:
    """Grid-step HBM byte model for the filter→compact ingestion pass.

    chain-only        : C·T·B read + T mask write (the pre-compaction
                        kernel — the chain is fused, one pass over HBM).
    unfused (argsort) : chain-only PLUS the legacy ``compact_fixed``: an
                        O(R log R) stable boolean argsort (≈ log2(R)
                        key+index passes over the tile's 4-byte lanes),
                        then a second FULL-WIDTH gather read of the
                        columns and the cap-width packed write.
    fused (in-kernel) : ONE launch — the chain pass packs each tile's
                        survivors while the tile is resident in VMEM and
                        DMAs them to the HBM output in TILE-row blocks,
                        so on top of chain-only it writes only SURVIVOR
                        data (rounded up to the 128-lane quantum; the DMA
                        moves the output's padded sublane rows). No sort
                        runs anywhere, and the full-width columns are
                        never read again.
    skip tier         : with ``skip_fraction`` of 128-row sub-tiles
                        provably decided by zone maps (``skip_pass_fraction``
                        of THOSE provably passing), the fused launch is
                        additionally charged the summary pass — per
                        128-row sub-tile: write+read of 2·C f32 min/max
                        (+ C Bloom 128-bit bitmaps when ``bloom``) — while
                        a Mosaic lowering's DMA gating never streams
                        provably-FAILED sub-tiles into VMEM at all, so the
                        chain read shrinks to the undecided + pass
                        fraction (pass sub-tiles are still read once for
                        the bulk copy). ``bytes_fused_skip`` therefore
                        drops toward the summary floor as layouts cluster
                        (skip_fraction → 1) and degrades to fused + the
                        summary overhead when nothing is provable
                        (skip_fraction = 0) — the graceful-shuffle case.
    """
    import importlib
    import math

    # the packed output's padded sublane rows (what each survivor DMA
    # moves); the package re-exports the jitted ``filter_chain`` under the
    # kernel module's name, so import the module itself
    dma_rows = importlib.import_module(
        "repro.kernels.filter_chain.filter_chain").dma_rows
    col_bytes = n_cols * tile * dtype_bytes
    mask_bytes = tile                                   # i8 mask lane
    chain_only = col_bytes + mask_bytes
    sort_passes = math.ceil(math.log2(max(batch_rows, 2)))
    sort_bytes = 2 * tile * 4 * sort_passes             # key + index lanes
    unfused = chain_only + sort_bytes + col_bytes + col_bytes
    # survivor prefix, quantized to the 128-lane copy granule
    p_quant = math.ceil(pass_rate * tile / 128) * 128 / tile
    out_bytes = dma_rows(n_cols) * tile * dtype_bytes    # one packed block
    surv = p_quant * out_bytes
    # the repro.analysis.kernel_audit contract: the captured BlockSpec
    # geometry of every chain launch (fused or not) must stream exactly
    # chain_only; the survivor DMA is the fused launch's only addition
    fused = chain_only + surv

    # ---- skip tier: tile-summary traffic + decided-sub-tile read savings
    sub_tiles = tile // 128                             # 128-row sub-tiles
    summary_bytes = 2 * n_cols * 4 * sub_tiles          # f32 min/max lanes
    if bloom:
        summary_bytes += n_cols * 16 * sub_tiles        # 128-bit bitmaps
    summary_bytes *= 2                                  # written, then read
    fail_frac = skip_fraction * (1.0 - skip_pass_fraction)
    pass_frac = skip_fraction * skip_pass_fraction
    # chain launch reads only undecided + pass sub-tiles (fail sub-tiles
    # are DMA-gated out); pass sub-tiles skip predicate math but are still
    # copied through VMEM to the packed output
    read_frac = 1.0 - fail_frac
    surv_skip = min(p_quant + pass_frac, 1.0) * out_bytes
    fused_skip = summary_bytes + read_frac * col_bytes + mask_bytes \
        + surv_skip
    return {
        "n_cols": n_cols, "tile": tile, "pass_rate": pass_rate,
        "bytes_chain_only": chain_only,
        "bytes_unfused_argsort": unfused,
        "bytes_fused": fused,
        "fused_traffic_ratio": fused / unfused,
        "skip_fraction": skip_fraction,
        "bytes_summary": summary_bytes,
        "bytes_fused_skip": fused_skip,
        "skip_traffic_ratio": fused_skip / fused,
        "note": "fused removes the sort and the second pass entirely and "
                "writes survivor bytes only; at low pass-rates it costs "
                "about what the chain alone costs; the skip tier trades a "
                "~1% summary pass for not reading decided tiles at all",
    }

_NOTES = {
    ("memory", "train"): "cut activation traffic: fused flash kernel, "
                         "bf16 residuals, selective remat policy",
    ("memory", "prefill"): "fuse attention inner loop (flash kernel) to "
                           "keep accumulators in VMEM",
    ("memory", "decode"): "cache-read bound (intrinsic); quantize KV or "
                          "widen SP to spread cache reads",
    ("collective", "train"): "reshard to cut gathers: EP all_to_all "
                             "dispatch, overlap grad all-reduce with bwd",
    ("collective", "prefill"): "keep activations sequence-sharded; avoid "
                               "vocab-axis gathers (pad vocab)",
    ("collective", "decode"): "merge softmax partials (SP) instead of "
                              "gathering cache",
    ("compute", "train"): "near MXU roof: raise per-chip batch or quantize",
    ("compute", "prefill"): "near MXU roof: chunked attention already MXU-"
                            "dominated",
    ("compute", "decode"): "compute-bound decode is unusual; check batching",
}


def load(tag: str = "") -> list[dict]:
    rows = []
    for p in sorted(ART.glob("*.json")):
        r = json.loads(p.read_text())
        if r.get("tag", "") != tag:
            continue
        rows.append(r)
    return rows


def render(rows: list[dict], *, csv: bool = True) -> list[str]:
    out = []
    for r in rows:
        mesh = r["mesh"]
        if r["status"] == "skip":
            out.append(f"roofline/{r['arch']}/{r['shape']}/{mesh},0.0000,"
                       f"SKIP({r['why'][:40]})")
            continue
        if r["status"] != "ok":
            out.append(f"roofline/{r['arch']}/{r['shape']}/{mesh},0.0000,"
                       f"ERROR")
            continue
        ro = r["roofline"]
        note = _NOTES.get((ro["dominant"], r["kind"]), "")
        out.append(
            f"roofline/{r['arch']}/{r['shape']}/{mesh},"
            f"{ro['bound_s']*1e6:.1f},"
            f"dom={ro['dominant']};tc={ro['t_compute_s']:.3e};"
            f"tm={ro['t_memory_s']:.3e};tx={ro['t_collective_s']:.3e};"
            f"useful={r['useful_flops_ratio']:.3f};"
            f"mfu_ub={r['mfu_upper_bound']:.4f}")
        if not csv:
            out.append(f"#   → {note}")
    return out


def render_ingest_model() -> list[str]:
    out = ["# --- ingest grid-step byte model (fused vs kernel+argsort) ---"]
    for p in (0.05, 0.25, 0.5, 1.0):
        m = filter_ingest_model(pass_rate=p)
        out.append(
            f"ingest-model/p{p:g},{m['fused_traffic_ratio']:.4f},"
            f"chain={m['bytes_chain_only']};"
            f"unfused={m['bytes_unfused_argsort']:.0f};"
            f"fused={m['bytes_fused']:.0f}")
    out.append("# --- skip-tier read-savings model (zone maps, pass_rate="
               "0.05) ---")
    for sf in (0.0, 0.25, 0.5, 0.75, 0.9):
        m = filter_ingest_model(pass_rate=0.05, skip_fraction=sf,
                                skip_pass_fraction=0.05)
        out.append(
            f"ingest-model/skip{sf:g},{m['skip_traffic_ratio']:.4f},"
            f"summary={m['bytes_summary']};"
            f"fused={m['bytes_fused']:.0f};"
            f"fused_skip={m['bytes_fused_skip']:.0f}")
    return out


def main() -> None:
    for line in render_ingest_model():
        print(line)
    for tag in ("", "opt"):
        rows = load(tag)
        if not rows:
            if tag == "":
                print("# no dry-run artifacts found — run "
                      "PYTHONPATH=src python -m repro.launch.dryrun --all")
            continue
        print(f"# --- roofline[{tag or 'baseline'}] ---")
        for line in render(rows):
            print(line if tag == "" else line.replace("roofline/",
                                                      "roofline-opt/"))
        ok = [r for r in rows if r["status"] == "ok"]
        by_dom = {}
        for r in ok:
            by_dom.setdefault(r["roofline"]["dominant"], []).append(r)
        print(f"# {len(ok)} cells ok; dominant terms: "
              + ", ".join(f"{k}={len(v)}" for k, v in sorted(by_dom.items())))


if __name__ == "__main__":
    main()
