"""The benchmark's generators: the paper stream is the program's
``gen_batch`` to the bit, and the lineitem columns follow dbgen."""

import json

import numpy as np
import pytest
from conftest import ROOT

from bench.data import paper_log, tpch_lineitem

DRIFT = json.loads((ROOT / "bench/traffic/drift.json").read_text())["drift"]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 40 + 3])
def test_paper_block_is_gen_batch(seed):
    from repro.data.stream import DriftConfig, gen_batch

    for b in (0, 3):
        got = paper_log.block(seed, b, 4096, DRIFT)
        want = gen_batch(seed, b, b * 4096, 4096,
                         drift=DriftConfig(**DRIFT))
        assert np.array_equal(got, want)


def test_paper_ring_covers_both_regimes():
    cfg = json.loads((ROOT / "bench/configs/paper-log-stream.json")
                     .read_text())
    signs = {paper_log.drift_shift(DRIFT, b * cfg["batch_rows"]
                                   + cfg["batch_rows"] / 2)[0] > 0
             for b in range(cfg["ring_blocks"])}
    assert signs == {True, False}


def _table(order, seed=5):
    cfg = {"partition_rows": 8192, "partitions": 4, "scale_factor": 50}
    return [np.asarray(p) for p in
            tpch_lineitem.device_table(seed, cfg, {"order": order})]


@pytest.mark.parametrize("order", ["shipdate", "orderkey"])
def test_lineitem_columns_follow_dbgen(order):
    cols = np.concatenate(_table(order), axis=1)
    ship, disc, qty, price = cols
    assert np.all(cols == np.round(cols)) and np.all(cols < 2 ** 24)
    assert ship.min() >= 8036 and ship.max() <= 10561
    assert set(np.unique(disc)) == set(range(11))
    assert set(np.unique(qty)) == set(range(1, 51))
    cents = price / qty
    assert np.all(cents == np.round(cents))
    assert cents.min() >= 90000 and cents.max() <= 209900
    if order == "shipdate":
        assert np.all(np.diff(ship) >= 0)
    else:
        # dbgen's order: ship dates scatter within every 128-row tile
        tiles = ship.reshape(-1, 128)
        assert np.median(tiles.max(1) - tiles.min(1)) > 1000


def test_lineitem_is_made_from_the_seed():
    a, b, c = _table("orderkey", 1), _table("orderkey", 1), \
        _table("orderkey", 2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


def test_ship_day_probabilities_sum_to_one():
    first, p = tpch_lineitem.ship_day_probs()
    assert first == 8036 and abs(p.sum() - 1.0) < 1e-12
    assert len(p) == (10440 - 8035 + 1) + 121 - 1
