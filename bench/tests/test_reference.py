"""The plain reference agrees with the program's engines at small sizes
(the numpy engine, the jnp engine and the interpreted Pallas kernel)."""

import json

import numpy as np
import pytest
from conftest import ROOT

from bench import program
from bench import reference as ref
from bench.data import paper_log, tpch_lineitem


def config(name, **plan):
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    cfg["plan"] = {**cfg["plan"], **plan}
    return cfg


def paper_batch(seed=3, rows=8192):
    drift = {"kind": "regime", "period_rows": 750000, "amplitude": 1.5}
    return paper_log.block(seed, 2, rows, drift)


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_paper_chain_mask(engine):
    cfg = config("paper-log-stream", engine=engine)
    batch = paper_batch()
    session = program.session(cfg)
    _, res = session.step(session.init_state(), batch)
    assert np.array_equal(res.mask_np, ref.chain_mask(batch,
                                                      cfg["predicates"]))


@pytest.mark.parametrize("order", ["shipdate", "orderkey"])
@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_q6_survivors(order, engine):
    cfg = config("tpch-q6-lineitem", engine=engine, capacity=8192)
    table = tpch_lineitem.device_table(
        9, {"partition_rows": 8192, "partitions": 12, "scale_factor": 50},
        {"order": order})
    session = program.session(cfg)
    state = session.init_state()
    kept = 0
    for part in table:
        state, res = session.step(state, part)
        cols = np.asarray(part)
        mask = ref.chain_mask(cols, cfg["predicates"])
        want = cols[:, mask]
        assert res.n_dropped == 0
        assert np.array_equal(res.survivors(), want)
        assert ref.survivor_checksum(res.survivors()) == \
            ref.survivor_checksum(want)
        kept += want.shape[1]
    assert kept > 0


def test_device_checksum_is_the_reference_checksum():
    import jax.numpy as jnp

    from bench.drivers.scan import _consumer

    rng = np.random.default_rng(0)
    packed = rng.integers(0, 2 ** 20, (4, 1024)).astype(np.float32)
    zero = jnp.int32(0)
    rev, row = _consumer(3, 1, True)(jnp.zeros((), jnp.float32),
                               jnp.asarray(packed), jnp.int32(700),
                               (zero,) * 4, jnp.arange(3))
    assert int(row[0]) == 700
    assert int(row[1]) & 0xFFFFFFFF == ref.survivor_checksum(packed[:, :700])
    assert float(rev) == pytest.approx(
        float(np.sum(packed[3, :700].astype(np.float64)
                     * packed[1, :700])), rel=1e-5)


def test_checksum_sees_value_order_and_count():
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    base = ref.survivor_checksum(a)
    assert ref.survivor_checksum(a[:, ::-1]) != base
    assert ref.survivor_checksum(a[:, :3]) != base
    b = a.copy()
    b[1, 2] += 1
    assert ref.survivor_checksum(b) != base


def test_ordering_follows_the_program():
    """The reference's ranks order every perm the program adopts, step by
    step, over drifting batches that each close an epoch."""
    cfg = config("paper-log-stream", engine="jnp")
    cfg["ordering"] = {"collect_rate": 64, "calculate_rate": 4096,
                       "momentum": 0.3}
    session = program.session(cfg)
    state = session.init_state()
    order = ref.Ordering(cfg["predicates"], cfg["ordering"])
    changed = set()
    for k in range(12):
        batch = paper_log.block(11, k, 4096,
                                {"kind": "regime", "period_rows": 4096,
                                 "amplitude": 1.5})
        state, res = session.step(state, batch)
        perm = np.asarray(res.metrics.perm)
        assert not ref.misordered(perm, order.adj)
        changed.add(tuple(perm))
        cuts, n = ref.cut_counts(batch, cfg["predicates"],
                                 order.sample_start(), order.collect)
        order.fold(cuts, n, 4096)
        assert int(res.metrics.epoch) == order.epoch
    assert len(changed) > 1


def test_misordered():
    adj = np.array([0.5, 0.1, 0.3])
    assert not ref.misordered(np.array([1, 2, 0]), adj)
    assert ref.misordered(np.array([0, 1, 2]), adj)
    assert ref.misordered(np.array([1, 1, 0]), adj)
    assert not ref.misordered(np.array([0, 1, 2]), None)
    assert ref.misordered(np.array([[1, 2, 0], [0, 1, 2]]), adj)
