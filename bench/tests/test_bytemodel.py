"""The benchmark's copy of the byte model gives the numbers of the
program's ``benchmarks/roofline.py::filter_ingest_model`` at the same
inputs, and counts provably failed tiles as unread."""

import importlib.util

import pytest
from conftest import ROOT

from bench import bytemodel


def program_model():
    spec = importlib.util.spec_from_file_location(
        "roofline", ROOT / "benchmarks" / "roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.filter_ingest_model


@pytest.mark.parametrize("n_cols", [3, 4, 9])
@pytest.mark.parametrize("pass_rate", [0.0, 0.019, 0.25, 1.0])
@pytest.mark.parametrize("skip", [(0.0, 0.0), (0.85, 0.0), (0.6, 0.3)])
@pytest.mark.parametrize("bloom", [False, True])
def test_matches_filter_ingest_model(n_cols, pass_rate, skip, bloom):
    want = program_model()(n_cols=n_cols, tile=2048, pass_rate=pass_rate,
                           skip_fraction=skip[0],
                           skip_pass_fraction=skip[1], bloom=bloom)
    got = bytemodel.tile_bytes(n_cols=n_cols, tile=2048, pass_rate=pass_rate,
                               skip_fraction=skip[0],
                               skip_pass_fraction=skip[1], bloom=bloom)
    for key in ("bytes_chain_only", "bytes_fused", "bytes_summary",
                "bytes_fused_skip"):
        assert got[key] == pytest.approx(want[key], rel=1e-12)


def test_failed_tiles_are_unread():
    rows, tile = 1 << 22, 2048
    subs = rows // 128
    none = bytemodel.step_bytes(n_cols=4, rows=rows, tile=tile, compact=True,
                                skip=True, n_kept=0)
    most = bytemodel.step_bytes(n_cols=4, rows=rows, tile=tile, compact=True,
                                skip=True, n_kept=0,
                                tiles_fail=int(subs * 0.85))
    col = 4 * 4 * rows
    assert none - most == pytest.approx(0.85 * col, rel=1e-3)
    plain = bytemodel.step_bytes(n_cols=3, rows=rows, tile=tile,
                                 compact=False, skip=False)
    assert plain == 3 * 4 * rows + rows


@pytest.mark.parametrize("chips", [1, 4])
def test_roofline_reader_is_per_chip(chips):
    """Bytes of a step split over the chips, against the kernel's mean
    device time per chip: the same work on 4 chips in the same time per
    chip reads the same share."""
    import types

    from bench.metrics import chain_roofline

    rows = (1 << 22) * chips
    need = bytemodel.step_bytes(n_cols=3, rows=rows, tile=2048,
                                compact=False, skip=False)
    per_chip_s = need / chips / 819e9 / 0.5          # a 50% share
    run = types.SimpleNamespace(
        trace=types.SimpleNamespace(kernel_s=lambda _: per_chip_s),
        config={"plan": {}, "columns": [0, 1, 2]}, cell={"chips": chips},
        counts={"rows": [rows]}, window=types.SimpleNamespace(steps=1),
        peak={"hbm_bytes_per_s": 819e9})
    assert chain_roofline.read(run) == pytest.approx(50.0)
