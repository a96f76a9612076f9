"""CPU tests of the Q19 cell: dbgen's marginals, the tree reference
against the SQL text, and the comparison that decides ``correct``."""

import argparse
import json

import numpy as np
import pytest
from conftest import ROOT

from bench import reference_tree as ref
from bench.control_tree import ControlTreeSession
from bench.data import tpch_lineitem_part as gen
from bench.drivers import scan_tree

CELL = "tpch-q19.orderkey-order"
CONFIG = json.loads(
    (ROOT / "bench" / "configs" / "tpch-q19-lineitem-part.json").read_text())
ROWS = 1 << 14
SMALL = {"partition_rows": ROWS, "partitions": 4, "scale_factor": 1,
         "plan": {"engine": "pallas", "compact": True, "capacity": ROWS,
                  "skip_tier": "off"},
         "ordering": {"collect_rate": 64, "calculate_rate": ROWS,
                      "momentum": 0.3}}


def q19_parts(seed, max_partkey):
    """Part tables drawn from Q19's own brands, containers and sizes, so
    that every term keeps rows at CPU sizes: a planted fault then shows in
    the survivors (dbgen's own marginals are tested on the generator)."""
    rng = np.random.default_rng(seed & 0xFFFFFFFF)
    shape = max_partkey + 1
    return (rng.choice([1, 7, 13, 2], shape),
            rng.choice([0, 1, 4, 5, 17, 18, 20, 21, 8, 9, 12, 13, 30],
                       shape),
            rng.integers(1, 21, shape))


@pytest.fixture
def q19_data(monkeypatch):
    monkeypatch.setattr(gen, "part_tables", q19_parts)


def run_small(session_factory=None, seed=2 ** 33 + 19, seconds=0.5,
              override=None):
    from bench import run

    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0)
    return run.run_cell(args, require_chip=False,
                        config_override={**SMALL, **(override or {})},
                        session_factory=session_factory)


# ------------------------------------------------------------ generator
def _within_binomial(values, domain, inflate=1.0):
    """Each value of ``domain`` within 6 sigma of its uniform count."""
    n, k = len(values), len(domain)
    counts = np.array([np.count_nonzero(values == v) for v in domain])
    assert counts.sum() == n, "values outside the domain"
    sigma = np.sqrt(n * (1 / k) * (1 - 1 / k) * inflate)
    assert np.all(np.abs(counts - n / k) < 6 * sigma), counts


def test_generator_matches_dbgen_marginals():
    config = {**CONFIG, "partition_rows": 1 << 17, "scale_factor": 1}
    parts = list(gen.partitions(2 ** 33 + 5, config, range(2)))
    cols = np.concatenate([np.asarray(c) for c, _ in parts], axis=1)
    pk = np.concatenate([np.asarray(k) for _, k in parts])
    n_parts = 200_000
    qty, price, disc, inst, mode, brand, cont, size = cols
    _within_binomial(qty, range(1, 51))
    _within_binomial(disc, range(11))
    _within_binomial(inst, range(4))
    _within_binomial(mode, range(7))
    _within_binomial((pk - 1) // 10_000, range(20))   # U{1..SF·200,000}
    # a part's attributes repeat on each of its lines: the variance of a
    # line count grows by the lines per part
    inflate = 1.0 + len(pk) / n_parts
    _within_binomial(brand, range(25), inflate)
    _within_binomial(cont, range(40), inflate)
    _within_binomial(size, range(1, 51), inflate)
    cents = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    np.testing.assert_array_equal(price, qty * cents)
    assert cols.dtype == np.float32 and np.all(cols < 2 ** 24)


def test_generator_codes_span_the_configurations_dictionaries():
    dic = CONFIG["dictionaries"]
    assert gen.N_INSTRUCTS == len(dic["l_shipinstruct"])
    assert gen.N_MODES == len(dic["l_shipmode"])
    assert gen.N_BRANDS == 5 * 5                   # Brand#MN, M, N in 1..5
    assert gen.N_CONTAINERS == len(dic["p_container_size"]) \
        * len(dic["p_container_kind"])


def test_one_partkey_is_one_part():
    config = {**CONFIG, "partition_rows": 1 << 15, "scale_factor": 1}
    seen: dict = {}
    for cols, pk in gen.partitions(2 ** 35 + 3, config, range(3)):
        cols, pk = np.asarray(cols), np.asarray(pk)
        attrs = cols[5:8].T
        for key, a in zip(pk.tolist(), map(tuple, attrs.tolist())):
            assert seen.setdefault(key, a) == a, key
    assert len(seen) < 3 << 15                 # parts do repeat


# ------------------------------------------------------------ reference
def _sql_where(row) -> bool:
    """Q19's WHERE clause, row at a time, on the decoded strings."""
    dic = CONFIG["dictionaries"]
    qty, _, _, inst, mode, brand, cont, size = (int(v) for v in row)
    instruct = dic["l_shipinstruct"][inst]
    shipmode = dic["l_shipmode"][mode]
    brand_s = f"Brand#{brand // 5 + 1}{brand % 5 + 1}"
    container = (f"{dic['p_container_size'][cont // 8]} "
                 f"{dic['p_container_kind'][cont % 8]}")
    common = shipmode in ("AIR", "AIR REG") \
        and instruct == "DELIVER IN PERSON"
    return common and (
        (brand_s == "Brand#12" and container in
         ("SM CASE", "SM BOX", "SM PACK", "SM PKG")
         and 1 <= qty <= 1 + 10 and 1 <= size <= 5)
        or (brand_s == "Brand#23" and container in
            ("MED BAG", "MED BOX", "MED PKG", "MED PACK")
            and 10 <= qty <= 10 + 10 and 1 <= size <= 10)
        or (brand_s == "Brand#34" and container in
            ("LG CASE", "LG BOX", "LG PACK", "LG PKG")
            and 20 <= qty <= 20 + 10 and 1 <= size <= 15))


def test_reference_matches_the_sql_text():
    rng = np.random.default_rng(19)
    n = 10_000
    brand, cont, size = (t[rng.integers(1, 500, n)]
                         for t in q19_parts(7, 500))
    # every code of the common conjuncts' columns, quantities and sizes
    # past both ends of each term's ranges
    cols = np.stack([rng.integers(0, 32, n), rng.integers(1, 1 << 20, n),
                     rng.integers(0, 11, n), rng.integers(0, 4, n),
                     rng.choice([0, 1, 1, 1, 2, 3, 4, 5, 6], n), brand,
                     cont, rng.integers(0, 17, n)]).astype(np.float32)
    want = np.array([_sql_where(r) for r in cols.T])
    got = ref.tree_mask(cols, CONFIG["predicates"])
    assert want.sum() > 20 and (~want).sum() > 20
    np.testing.assert_array_equal(got, want)


def test_reference_structure_is_q19s():
    groups, terms = ref.structure(CONFIG["predicates"])
    assert len(CONFIG["predicates"]) == 15
    assert (max(groups) + 1, max(terms) + 1) == (4, 6)


# --------------------------------------------------- correct or not
def test_sound_run_is_correct(q19_data):
    out = run_small()
    assert out["correct"], out["checks"]
    assert all(v["value"] == 0 for v in out["checks"].values())


def _with(preds_fn):
    def factory(config):
        return scan_tree.tree_session(
            {**config, "predicates": preds_fn(
                [dict(p) for p in config["predicates"]])})
    return factory


def term_left_out(preds):
    return [p for p in preds if p.get("term") != "t2"]


def in_code_dropped(preds):
    for p in preds:
        if p["name"] == "container_2":
            p["codes"] = p["codes"][1:]
    return preds


def size_off_by_one(preds):
    for p in preds:
        if p["name"] == "size_le_10":
            p["t1"] += 1.0
    return preds


def terms_anded(preds):
    for p in preds:
        if p.get("term") is not None:
            p["term"] = "all"
    return preds


@pytest.mark.parametrize("fault", [term_left_out, in_code_dropped,
                                   size_off_by_one, terms_anded],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(q19_data, fault):
    out = run_small(session_factory=_with(fault))
    assert not out["correct"], out["checks"]


def test_bfloat16_control_is_not_correct(q19_data):
    out = run_small(session_factory=ControlTreeSession)
    assert not out["correct"], out["checks"]
    # the predicate columns are small whole numbers bfloat16 holds; the
    # price is not, so the checked partitions' checksums catch it
    assert out["checks"]["survivors_wrong"]["value"] > 0


def test_atom_tiles_pct_reads_the_counter():
    from bench.metrics import atom_tiles_pct
    from bench.run import Run

    run = Run(config=CONFIG, counts={"rows": [4096, 4096],
                                     "atom_tiles": [15, 30],
                                     "atom_pairs": [30, 30]})
    assert atom_tiles_pct.read(run) == pytest.approx(45 / 60 * 100)
    assert atom_tiles_pct.read(Run(config=CONFIG,
                                   counts={"rows": [4096]})) is None


def test_atom_pairs_are_the_kernel_grid(q19_data):
    """A chain of one atom that passes every row evaluates it on every
    grid tile: the program's counter then equals the driver's pairs."""
    from bench.drivers.common import Window

    config = {**CONFIG, **SMALL, "partition_rows": 5 * 2048 + 7,
              "plan": {**SMALL["plan"], "capacity": 5 * 2048 + 7},
              "predicates": [p for p in CONFIG["predicates"]
                             if p["name"] == "size_ge_1"]}
    drv = scan_tree.Driver(config, {"order": "orderkey", "in_flight": 1},
                           2 ** 33 + 23)
    drv.warm()
    counts = drv.layer_counts(Window(first_step=0, steps=4))
    assert counts["atom_pairs"] == [6] * 4
    assert counts["atom_tiles"] == counts["atom_pairs"]
