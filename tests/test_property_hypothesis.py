"""Property-based tests (hypothesis) for the system's invariants.

1. OPTIMALITY: rank-ascending order minimizes expected chain cost over all
   permutations (the theorem the paper's §2.1 relies on) — checked by
   exhaustive enumeration on random (cost, selectivity) draws.
2. ORDER-INVARIANCE: the filter's boolean outcome is identical under every
   permutation (conjunction commutes) across all three backends.
3. MONITOR UNBIASEDNESS: stride sampling counts match dense counts on the
   sampled index set exactly, for any phase.
4. MOMENTUM CONTRACTION: the adj-rank recurrence is a contraction toward
   the stationary rank (|adj - r*| shrinks by factor m per epoch).
"""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import np_exec, predicates as P, stats as S
from repro.core.filter_exec import run_chain
from repro.core.predicates import Predicate

settings.register_profile("ci", deadline=None, max_examples=30)
settings.load_profile("ci")


@given(
    costs=st.lists(st.floats(0.05, 10.0), min_size=2, max_size=5),
    sel=st.lists(st.floats(0.01, 0.99), min_size=2, max_size=5),
)
def test_rank_order_minimizes_expected_cost(costs, sel):
    n = min(len(costs), len(sel))
    costs = jnp.asarray(costs[:n], jnp.float32)
    sel = jnp.asarray(sel[:n], jnp.float32)
    nc = costs / jnp.max(costs)
    rank_perm = np.asarray(S.order_from_ranks(nc / (1 - sel)))
    best = min(
        float(S.expected_chain_cost(costs, sel, jnp.asarray(p)))
        for p in itertools.permutations(range(n)))
    got = float(S.expected_chain_cost(costs, sel, jnp.asarray(rank_perm)))
    assert got <= best * (1 + 1e-5)


def _random_chain(seed):
    r = np.random.default_rng(seed)
    preds = [
        Predicate("a", 0, P.OP_GT, float(r.normal(0, 1)), static_cost=1.0),
        Predicate("b", 1, P.OP_LT, float(r.normal(0, 1)), static_cost=2.0),
        Predicate("c", 0, P.OP_BETWEEN, -0.5, t2=1.5, static_cost=1.5),
        Predicate("d", 2, P.OP_HASHMIX, 0.4 * P.MIX_MOD, rounds=4,
                  static_cost=5.0),
    ]
    cols = np.stack([r.normal(0, 1, 400), r.normal(0, 1, 400),
                     r.uniform(0, P.MIX_MOD, 400)]).astype(np.float32)
    return preds, cols


@given(seed=st.integers(0, 10_000),
       perm=st.permutations(list(range(4))))
def test_outcome_order_invariant_all_backends(seed, perm):
    preds, cols = _random_chain(seed)
    specs = P.pack(preds)
    jperm = jnp.asarray(perm, jnp.int32)
    base = run_chain(jnp.asarray(cols), specs, jnp.arange(4, dtype=jnp.int32),
                     collect_rate=97, sample_phase=0)
    permuted = run_chain(jnp.asarray(cols), specs, jperm,
                         collect_rate=97, sample_phase=0)
    np_mask = np_exec.run_chain_np(cols, preds, perm)[0]
    assert np.array_equal(np.asarray(base.mask), np.asarray(permuted.mask))
    assert np.array_equal(np.asarray(base.mask), np_mask)


@given(phase=st.integers(0, 96), n_rows=st.integers(1, 400))
def test_monitor_stride_sampling_exact(phase, n_rows):
    preds, cols = _random_chain(7)
    cols = cols[:, :n_rows]
    n_rows = cols.shape[1]
    specs = P.pack(preds)
    res = run_chain(jnp.asarray(cols), specs, jnp.arange(4, dtype=jnp.int32),
                    collect_rate=97, sample_phase=phase)
    # dense reference: indices where (i + phase) % 97 == 0
    idx = np.asarray([i for i in range(n_rows) if (i + phase) % 97 == 0])
    assert float(res.n_monitored) == len(idx)
    if len(idx):
        dense = np.asarray(P.eval_all(specs, jnp.asarray(cols)))
        np.testing.assert_allclose(
            np.asarray(res.cut_counts), (~dense[:, idx]).sum(axis=1))


@given(m=st.floats(0.0, 0.9), r_star=st.floats(0.1, 10.0),
       adj0=st.floats(0.0, 20.0))
def test_momentum_contraction(m, r_star, adj0):
    adj = jnp.asarray([adj0])
    target = jnp.asarray([r_star])
    prev_err = abs(adj0 - r_star)
    for _ in range(5):
        adj = S.momentum_update(adj, target, m, first_epoch=jnp.asarray(False))
        err = float(abs(adj[0] - r_star))
        assert err <= prev_err * max(m, 1e-9) + 1e-6 or err < 1e-6
        prev_err = err


@given(frac_cut=st.lists(st.floats(0.0, 1.0), min_size=3, max_size=3))
def test_work_units_match_survivor_counts(frac_cut):
    """Row-level work == Σ cost[perm[k]] · rows alive before position k."""
    r = np.random.default_rng(3)
    n = 300
    cols = np.stack([r.uniform(0, 1, n) for _ in range(3)]).astype(np.float32)
    preds = [Predicate(f"p{i}", i, P.OP_GT, float(frac_cut[i]),
                       static_cost=float(i + 1)) for i in range(3)]
    specs = P.pack(preds)
    perm = jnp.asarray([2, 0, 1], jnp.int32)
    res = run_chain(jnp.asarray(cols), specs, perm, collect_rate=1000,
                    sample_phase=0)
    outcomes = np.asarray(P.eval_all(specs, jnp.asarray(cols)))
    alive = np.ones(n, bool)
    expect = 0.0
    for k in [2, 0, 1]:
        expect += alive.sum() * (k + 1)
        alive &= outcomes[k]
    assert float(res.work_units) == expect
