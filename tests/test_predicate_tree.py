"""Trees (AND of ORs of AND-terms) and the OP_IN atom.

Every engine (pallas in interpret mode, jnp, numpy) against the plain
references — the dense numpy evaluation ``np_exec.eval_tree_np``, the
kernel oracle ``filter_chain_ref`` and a row-at-a-time python evaluation —
on seeded random trees and on TPC-H Q19's tree; the three-level order
against a brute force over every contiguous permutation of the row-level
cost model; flat and CNF chains against values fixed before trees
existed; and the plan, fingerprint and lint rules for trees.
"""

import dataclasses
import hashlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import q19_filters

from repro.analysis import chain_lint
from repro.core import FilterPlan, build_session
from repro.core import predicates as P
from repro.core import stats as stats_lib
from repro.core.engine import MonitorSpec, get_engine
from repro.core.np_exec import eval_tree_np
from repro.core.ordering import OrderingConfig, epoch_update, init_order_state
from repro.kernels.filter_chain.ref import filter_chain_ref

ENGINES = ("pallas", "jnp", "numpy")
EDGE_CODES = (0, 23, 24, 47)      # the ends of both words of an IN mask
EXACT = ("mask", "cut_counts", "n_monitored", "group_cut_counts",
         "term_cut_counts", "active_before", "n_atom_tiles")


# ------------------------------------------------------------- generators
def tree_columns(seed: int, n_rows: int) -> np.ndarray:
    """col 0: codes in [0, 48) with the word edges over-drawn, plus a few
    out-of-domain values; col 1: normal; col 2: small whole numbers."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 48, n_rows).astype(np.float32)
    edge = rng.random(n_rows) < 0.3
    codes[edge] = rng.choice(EDGE_CODES, edge.sum())
    odd = rng.random(n_rows) < 0.01
    codes[odd] = rng.choice([-1.0, 48.0, 60.0, 23.5, 24.49], odd.sum())
    return np.stack([codes, rng.normal(0.0, 1.0, n_rows),
                     rng.integers(0, 10, n_rows)]).astype(np.float32)


def random_atom(rng, name: str, **labels) -> P.Predicate:
    kind = rng.integers(0, 6)
    if kind == 0:
        codes = set(rng.choice(48, rng.integers(1, 20), replace=False))
        codes |= set(rng.choice(EDGE_CODES, rng.integers(1, 4)))
        return P.Predicate(name, 0, P.OP_IN, *P.in_set(sorted(codes)),
                           **labels)
    if kind == 1:
        return P.Predicate(name, 1, P.OP_GT, float(rng.normal(-0.5, 0.5)),
                           **labels)
    if kind == 2:
        return P.Predicate(name, 1, P.OP_LT, float(rng.normal(0.5, 0.5)),
                           **labels)
    if kind == 3:
        lo = float(rng.integers(-1, 6))
        return P.Predicate(name, 2, P.OP_BETWEEN, lo - 0.5,
                           lo + float(rng.integers(2, 6)), **labels)
    if kind == 4:
        return P.Predicate(name, 0, P.OP_EQ, float(rng.choice(EDGE_CODES)),
                           static_cost=0.5, **labels)
    return P.Predicate(name, 2, P.OP_HASHMIX, 524288.0, rounds=2,
                       static_cost=3.0, **labels)


def random_tree(seed: int, max_groups=3, max_terms=3, max_atoms=3):
    """Up to max_groups × max_terms × max_atoms atoms. A one-atom group
    is a singleton; a one-atom term inside a group is sometimes left
    unlabelled (a one-atom term by default)."""
    rng = np.random.default_rng(seed)
    chain = []
    for g in range(rng.integers(1, max_groups + 1)):
        n_terms = rng.integers(1, max_terms + 1)
        sizes = rng.integers(1, max_atoms + 1, n_terms)
        if n_terms == 1 and sizes[0] == 1:
            chain.append(random_atom(rng, f"g{g}"))
            continue
        for t, size in enumerate(sizes):
            term = None if size == 1 and rng.random() < 0.5 else f"t{t}"
            for a in range(size):
                chain.append(random_atom(rng, f"g{g}t{t}a{a}",
                                         group=f"g{g}", term=term))
    return chain


def contiguous_perms(specs: P.PredicateSpecs):
    """Every permutation keeping groups and terms contiguous."""
    terms = specs.term or tuple(range(specs.n))
    blocks = []                        # per group: its terms' member lists
    for mem in specs.group_members:
        by_term: dict = {}
        for i in mem:
            by_term.setdefault(terms[i], []).append(i)
        blocks.append(list(by_term.values()))

    def group_orders(tlist):
        for t_order in itertools.permutations(tlist):
            for atom_orders in itertools.product(
                    *(itertools.permutations(t) for t in t_order)):
                yield [i for a in atom_orders for i in a]

    for g_order in itertools.permutations(blocks):
        for parts in itertools.product(*(list(group_orders(b))
                                         for b in g_order)):
            yield [i for part in parts for i in part]


def some_contiguous_perms(specs, seed: int, k: int = 3):
    perms = list(itertools.islice(contiguous_perms(specs), 2000))
    rng = np.random.default_rng(seed)
    picks = rng.choice(len(perms), min(k, len(perms)), replace=False)
    return [perms[0]] + [perms[i] for i in picks]


def python_tree(cols: np.ndarray, preds) -> np.ndarray:
    """Row-at-a-time evaluation straight from the definitions."""
    groups = P.normalize_groups(preds)
    terms = P.normalize_terms(preds) or tuple(range(len(preds)))

    def atom(p, x):
        x = float(x)
        if p.op == P.OP_GT:
            return x > np.float32(p.t1)
        if p.op == P.OP_LT:
            return x < np.float32(p.t1)
        if p.op == P.OP_BETWEEN:
            return np.float32(p.t1) < x < np.float32(p.t2)
        if p.op == P.OP_EQ:
            return np.round(x) == np.round(np.float32(p.t1))
        if p.op == P.OP_IN:
            return np.round(x) in P.in_codes(p.t1, p.t2)
        y = np.float32(x)
        for _ in range(p.rounds):
            y = y * np.float32(P.MIX_MUL) + np.float32(P.MIX_ADD)
            y = y - np.floor(y / np.float32(P.MIX_MOD)) * np.float32(P.MIX_MOD)
        return y > np.float32(p.t1)

    out = []
    for r in range(cols.shape[1]):
        ok = True
        for g in sorted(set(groups)):
            members = [i for i in range(len(preds)) if groups[i] == g]
            any_term = False
            for t in sorted({terms[i] for i in members}):
                any_term |= all(atom(preds[i], cols[preds[i].column, r])
                                for i in members if terms[i] == t)
            ok &= any_term
        out.append(ok)
    return np.asarray(out)


def assert_engines_match(cols, specs, perm, collect_rate, phase, msg=""):
    ref = filter_chain_ref(jnp.asarray(cols), specs, jnp.asarray(perm),
                           collect_rate=collect_rate, sample_phase=phase)
    mon = MonitorSpec(collect_rate=collect_rate, sample_phase=phase)
    for name in ENGINES:
        eng = get_engine(name)
        data = jnp.asarray(cols) if eng.traceable else cols
        got = eng.run_chain(data, specs, jnp.asarray(perm, jnp.int32), mon)
        for field in got._fields:
            a, b = np.asarray(getattr(got, field)), \
                np.asarray(getattr(ref, field))
            if field in EXACT:
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{name} {field} {msg} perm={perm}")
            else:
                np.testing.assert_allclose(
                    a, b, rtol=1e-6, err_msg=f"{name} {field} {msg}")
    return ref


# ------------------------------------------------------------- semantics
@pytest.mark.parametrize("seed", range(8))
def test_engines_agree_with_reference_on_random_trees(seed):
    preds = random_tree(seed)
    specs = P.pack(preds)
    cols = tree_columns(seed, 2 * 2048 + 301)
    plain = eval_tree_np(cols, preds)
    np.testing.assert_array_equal(plain[:600],
                                  python_tree(cols[:, :600], preds))
    for perm in some_contiguous_perms(specs, seed):
        ref = assert_engines_match(cols, specs, perm, 37, seed % 37,
                                   msg=f"seed={seed}")
        np.testing.assert_array_equal(np.asarray(ref.mask), plain)


def test_in_mask_word_edges():
    """Codes 0, 23, 24 and 47 sit at the ends of the two 24-bit words;
    out-of-domain, fractional and NaN values pass nothing they should not."""
    x = np.array([0, 23, 24, 47, 1, 22, 25, 46, -1, 48, 60, 23.4, 23.6,
                  24.5, 47.5, np.nan], np.float32)
    pred = P.Predicate("edges", 0, P.OP_IN, *P.in_set(EDGE_CODES))
    # 23.6 and 24.5 round (half to even) to 24, 47.5 to 48
    want = np.array([1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 1, 1, 1, 0, 0], bool)
    specs = P.pack([pred])
    for name in ENGINES:
        eng = get_engine(name)
        data = jnp.asarray(x[None]) if eng.traceable else x[None]
        got = eng.run_chain(data, specs, jnp.zeros((1,), jnp.int32),
                            MonitorSpec(collect_rate=1, sample_phase=0))
        np.testing.assert_array_equal(np.asarray(got.mask), want, name)
    assert P.in_codes(*P.in_set(EDGE_CODES)) == EDGE_CODES
    with pytest.raises(ValueError, match="outside"):
        P.in_set([48])
    with pytest.raises(ValueError, match="whole numbers"):
        P.Predicate("bad", 0, P.OP_IN, 0.5, 0.0)


def q19_columns(seed: int, n_rows: int) -> np.ndarray:
    """Q19's 8 columns with dbgen's domains, independent per row."""
    rng = np.random.default_rng(seed)
    return np.stack([
        rng.integers(1, 51, n_rows), rng.integers(90000, 10500000, n_rows),
        rng.integers(0, 11, n_rows), rng.integers(0, 4, n_rows),
        rng.integers(0, 7, n_rows),
        # brand and container concentrated on Q19's values, so the
        # 2^14 rows hold survivors of every term
        rng.choice([1, 7, 13, 2], n_rows), rng.choice(
            [0, 1, 4, 5, 17, 18, 20, 21, 8, 9, 12, 13, 30], n_rows),
        rng.integers(1, 51, n_rows)]).astype(np.float32)


def test_q19_tree_engines_agree_at_2_14_rows():
    preds = q19_filters()
    specs = P.pack(preds)
    assert (specs.n, specs.n_groups, specs.n_terms) == (15, 4, 6)
    cols = q19_columns(19, 1 << 14)
    plain = eval_tree_np(cols, preds)
    assert plain.sum() > 0
    np.testing.assert_array_equal(plain[:2000],
                                  python_tree(cols[:, :2000], preds))
    for perm in some_contiguous_perms(specs, 19):
        ref = assert_engines_match(cols, specs, perm, 100, 7, "q19")
        np.testing.assert_array_equal(np.asarray(ref.mask), plain)


def test_q19_session_learns_to_put_the_tautology_last():
    """``p_size >= 1`` passes every row: after an epoch it runs last, the
    OR group runs after the selective singletons, and every step's
    survivors equal the reference's."""
    preds = q19_filters()
    plan = FilterPlan(preds, OrderingConfig(collect_rate=16,
                                            calculate_rate=8192),
                      engine="pallas", compact=True)
    session = build_session(plan)
    state = session.init_state()
    for k in range(3):
        cols = q19_columns(100 + k, 8192)
        state, res = session.step(state, cols)
        np.testing.assert_array_equal(
            res.survivors(), cols[:, eval_tree_np(cols, preds)])
    perm = np.asarray(state.perm).tolist()
    assert perm[-1] == 2                               # size_ge_1
    assert set(perm[:2]) == {0, 1}                     # the singletons
    assert int(res.metrics.n_atom_tiles) > 0


# -------------------------------------------------------------- ordering
def _stats_for(specs, s, cost, n=1 << 20):
    """Epoch statistics whose selectivities are ``s`` with independent
    atoms: exact term and group pass rates follow from the products."""
    s = np.asarray(s, np.float64)
    t_pass = np.array([np.prod(s[list(m)]) for m in specs.term_members])
    g_fail = np.array([np.prod(1.0 - t_pass[list(t)])
                       for t in specs.group_terms])
    f32 = np.float32
    return stats_lib.FilterStats(
        num_cut=((1.0 - s) * n).astype(f32),
        cost_acc=(np.asarray(cost) * n).astype(f32),
        n_monitored=f32(n), group_cut=(g_fail * n).astype(f32),
        term_cut=((1.0 - t_pass) * n).astype(f32))


@pytest.mark.parametrize("seed", range(12))
def test_three_level_order_minimises_row_cost_model(seed):
    rng = np.random.default_rng(seed)
    while True:
        preds = random_tree(seed * 101 + rng.integers(1000),
                            max_groups=3, max_terms=3, max_atoms=2)
        if 2 <= len(preds) <= 6 and any(p.term for p in preds):
            break
    cost = rng.uniform(0.2, 3.0, len(preds))
    preds = [dataclasses.replace(p, static_cost=float(c))
             for p, c in zip(preds, cost)]
    specs = P.pack(preds)
    s = rng.uniform(0.05, 0.95, len(preds))
    state = init_order_state(specs.n, specs.n_groups, xp=np,
                             n_terms=specs.n_terms)
    state = state._replace(stats=_stats_for(specs, s, cost))
    new = epoch_update(state, OrderingConfig(), groups=specs.groups, xp=np,
                       terms=specs.term)
    perm = np.asarray(new.perm).tolist()
    got = P.tree_cost_row_model(specs, s, perm)
    best = min(P.tree_cost_row_model(specs, s, p)
               for p in contiguous_perms(specs))
    assert got <= best * (1.0 + 1e-5), (perm, got, best)


def test_one_atom_terms_keep_member_rank_order():
    """A group of unlabelled atoms inside a tree orders them by
    ``member_ranks`` exactly, as a CNF chain does."""
    rng = np.random.default_rng(3)
    preds = [P.Predicate(f"m{i}", 1, P.OP_GT, 0.0, group="or",
                         static_cost=float(c))
             for i, c in enumerate(rng.uniform(0.5, 2.0, 4))]
    preds += [P.Predicate("x", 2, P.OP_LT, 3.0, group="t", term="a"),
              P.Predicate("y", 2, P.OP_GT, 1.0, group="t", term="a")]
    specs = P.pack(preds)
    s = np.array([0.3, 0.6, 0.1, 0.8, 0.4, 0.5])
    stats = _stats_for(specs, s, np.asarray(specs.static_cost))
    term_rank = stats_lib.tree_levels(stats, specs.term_members,
                                      specs.group_terms, xp=np)[1]
    member = stats_lib.member_ranks(stats, xp=np)
    np.testing.assert_array_equal(term_rank[:4], member[:4])


def test_tree_cost_model_matches_measured_work():
    """The row-level model is what the engines' work counters measure on
    independent columns."""
    preds = [P.Predicate("a", 1, P.OP_GT, 0.0),
             P.Predicate("b", 2, P.OP_LT, 5.0, group="g", term="t"),
             P.Predicate("c", 1, P.OP_LT, 1.0, group="g", term="t"),
             P.Predicate("d", 0, P.OP_IN, *P.in_set(range(12)), group="g",
                         static_cost=2.0)]
    specs = P.pack(preds)
    rng = np.random.default_rng(5)
    n = 1 << 16
    cols = np.stack([rng.integers(0, 48, n), rng.normal(0, 1, n),
                     rng.integers(0, 10, n)]).astype(np.float32)
    perm = [0, 3, 1, 2]
    res = get_engine("numpy").run_chain(cols, specs, np.asarray(perm),
                                        MonitorSpec(collect_rate=1,
                                                    sample_phase=0))
    s = 1.0 - np.asarray(res.cut_counts) / n
    model = P.tree_cost_row_model(specs, s, perm)
    assert abs(float(res.work_units) / n - model) < 0.01 * model


# ------------------------------------------------ flat and CNF unchanged
def _paper_cols(seed, n):
    rng = np.random.default_rng(seed)
    return np.stack([rng.normal(500, 100, n), rng.normal(50, 15, n),
                     rng.uniform(0, 1 << 20, n)]).astype(np.float32)


def _paper_chains():
    hi, lo, date, mix = P.paper_filters_4("fig1")
    return {"flat": [hi, lo, date, mix],
            "cnf": [hi, lo, dataclasses.replace(mix, group="g"),
                    dataclasses.replace(date, group="g")]}


#: recorded from the code before trees existed (same seeds and calls)
GOLDEN = {
    "flat": dict(
        n_pass=232, mask_sha="3bc3842b8bcc1bb7",
        active=[5000.0, 1885.0, 950.0, 583.0], cut=[50.0, 52.0, 60.0, 78.0],
        gcut=[50.0, 52.0, 60.0, 78.0], work=33795.0,
        perms=[[0, 1, 2, 3], [0, 1, 2, 3], [2, 0, 1, 3], [2, 0, 1, 3],
               [2, 1, 0, 3], [2, 1, 0, 3], [2, 1, 0, 3], [2, 0, 1, 3]],
        adj=[0.4227922558784485, 0.4556996822357178, 0.4117107093334198,
             1.6671764850616455]),
    "cnf": dict(
        n_pass=829, mask_sha="78c18f3fdc6d5c0f",
        active=[5000.0, 3053.0, 1183.0, 562.0], cut=[50.0, 52.0, 78.0, 60.0],
        gcut=[50.0, 52.0, 34.0], work=12844.599609375,
        perms=[[0, 1, 2, 3], [0, 1, 2, 3], [0, 1, 3, 2], [0, 1, 3, 2],
               [1, 0, 3, 2], [1, 0, 3, 2], [1, 0, 3, 2], [0, 1, 3, 2]],
        adj=[0.352326899766922, 0.37974971532821655, 3.399463176727295]),
}


@pytest.mark.parametrize("shape", ["flat", "cnf"])
@pytest.mark.parametrize("engine", ENGINES)
def test_flat_and_cnf_chains_unchanged(shape, engine):
    want = GOLDEN[shape]
    chain = _paper_chains()[shape]
    specs = P.pack(chain)
    assert not specs.is_tree
    cols = _paper_cols(11, 5000)
    perm = [3, 2, 0, 1] if shape == "flat" else [1, 0, 3, 2]
    eng = get_engine(engine)
    r = eng.run_chain(jnp.asarray(cols) if eng.traceable else cols, specs,
                      jnp.asarray(perm, jnp.int32),
                      MonitorSpec(collect_rate=37, sample_phase=5))
    m = np.asarray(r.mask)
    assert int(m.sum()) == want["n_pass"]
    assert hashlib.sha256(np.packbits(m).tobytes()).hexdigest()[:16] \
        == want["mask_sha"]
    assert np.asarray(r.active_before).tolist() == want["active"]
    assert np.asarray(r.cut_counts).tolist() == want["cut"]
    assert np.asarray(r.group_cut_counts).tolist() == want["gcut"]
    assert float(r.work_units) == want["work"]
    assert np.asarray(r.term_cut_counts).shape == (0,)
    if not eng.traceable:
        return
    session = build_session(FilterPlan(
        chain, OrderingConfig(collect_rate=100, calculate_rate=20000),
        engine=engine))
    state, perms = session.init_state(), []
    assert state.stats.term_cut is None
    for k in range(8):
        state, res = session.step(state, _paper_cols(100 + k, 12000))
        perms.append(np.asarray(res.metrics.perm).tolist())
    assert perms == want["perms"]
    np.testing.assert_array_equal(np.asarray(state.adj_rank),
                                  np.asarray(want["adj"], np.float32))


# ------------------------------------------------------ plan and lint
def _tree(**kw):
    return [P.Predicate("a", 0, P.OP_GT, 1.0),
            P.Predicate("b", 1, P.OP_LT, 2.0, group="g", term="t"),
            P.Predicate("c", 0, P.OP_IN, *P.in_set([1, 2]), group="g",
                        term="t"),
            P.Predicate("d", 1, P.OP_GT, 5.0, group="g", **kw)]


@pytest.mark.parametrize("tier", ["zonemap", "zonemap+bloom", "auto"])
def test_plan_refuses_skip_tier_for_terms_and_in(tier):
    with pytest.raises(ValueError, match="AND-terms or OP_IN"):
        FilterPlan(_tree(), engine="jnp", skip_tier=tier)
    flat_in = [P.Predicate("c", 0, P.OP_IN, *P.in_set([1, 2]))]
    with pytest.raises(ValueError, match="AND-terms or OP_IN"):
        FilterPlan(flat_in, engine="jnp", skip_tier=tier)
    FilterPlan(_tree(), engine="jnp", skip_tier="off")


def test_plan_refuses_shards_for_terms():
    with pytest.raises(ValueError, match="one shard"):
        FilterPlan(_tree(), engine="jnp", shards=4)
    # OP_IN alone has no terms: sharding stays a matter of devices
    FilterPlan([P.Predicate("c", 0, P.OP_IN, *P.in_set([1]))],
               engine="jnp", shards=4)


def test_fingerprint_covers_terms_and_in_sets():
    base = FilterPlan(_tree()).fingerprint()
    moved = _tree()
    moved[3] = dataclasses.replace(moved[3], term="t")   # d joins term t
    assert FilterPlan(moved).fingerprint() != base
    other_in = _tree()
    other_in[2] = dataclasses.replace(other_in[2],
                                      **dict(zip(("t1", "t2"),
                                                 P.in_set([1, 3]))))
    assert FilterPlan(other_in).fingerprint() != base
    # chains without terms keep the fingerprints recorded before trees
    # existed, so their checkpoints still restore
    assert FilterPlan(P.paper_filters_4("fig1")).fingerprint() \
        == "7b6cd964e5a7fec9"
    assert FilterPlan(P.paper_filters_cnf("fig1")).fingerprint() \
        == "eb1ebe010b500708"


def test_term_labels_need_groups_and_contiguity():
    with pytest.raises(ValueError, match="needs a group"):
        P.Predicate("x", 0, P.OP_GT, 0.0, term="t")
    split = [P.Predicate("a", 0, P.OP_GT, 0.0, group="g", term="t"),
             P.Predicate("b", 0, P.OP_GT, 1.0, group="g", term="u"),
             P.Predicate("c", 0, P.OP_GT, 2.0, group="g", term="t")]
    with pytest.raises(ValueError, match="not contiguous"):
        P.pack(split)
    # the same label in two groups names two terms
    specs = P.pack([P.Predicate("a", 0, P.OP_GT, 0.0, group="g", term="t"),
                    P.Predicate("b", 0, P.OP_GT, 0.0, group="h", term="t")])
    assert specs.term == (0, 1)


def test_lint_accepts_q19_and_in_atoms():
    q19 = q19_filters()
    assert chain_lint.lint_chain(q19) == []
    canon = chain_lint.canonicalize_chain(q19)
    assert not canon.changed
    # a one-code IN lints like EQ; more codes by their hull
    one = P.Predicate("one", 0, P.OP_IN, *P.in_set([3]))
    eq = P.Predicate("eq", 0, P.OP_EQ, 3.0)
    assert chain_lint.sat_over(one) == chain_lint.sat_over(eq)
    assert chain_lint.sat_under(one) == chain_lint.sat_under(eq)
    gt = P.Predicate("gt", 0, P.OP_GT, 10.0)
    codes = {d.code for d in chain_lint.lint_chain([one, gt])}
    assert "chain-unsat-conjunction" in codes
    two = P.Predicate("two", 0, P.OP_IN, *P.in_set([3, 7]))
    assert "chain-unsat-conjunction" in {
        d.code for d in chain_lint.lint_chain([two, gt])}
    wide = P.Predicate("wide", 0, P.OP_IN, *P.in_set([3, 40]))
    assert chain_lint.lint_chain([wide, gt]) == []
    empty = P.Predicate("none", 0, P.OP_IN, 0.0, 0.0)
    assert "chain-unsat-predicate" in {
        d.code for d in chain_lint.lint_chain([empty])}


def test_lint_judges_terms_not_atoms():
    """x > 5 inside an AND-term is not an OR-member the wider x > 1
    contains; a term whose atoms contradict is dead, and a group whose
    terms are all dead is unsatisfiable."""
    tree = [P.Predicate("x5", 0, P.OP_GT, 5.0, group="g", term="t"),
            P.Predicate("y3", 1, P.OP_LT, 3.0, group="g", term="t"),
            P.Predicate("x1", 0, P.OP_GT, 1.0, group="g")]
    assert chain_lint.lint_chain(tree) == []
    assert not chain_lint.canonicalize_chain(tree).changed
    dead = [P.Predicate("a", 0, P.OP_GT, 5.0, group="g", term="t"),
            P.Predicate("b", 0, P.OP_LT, 3.0, group="g", term="t"),
            P.Predicate("c", 0, P.OP_LT, 1.0, group="g"),
            P.Predicate("d", 0, P.OP_GT, 2.0)]
    codes = {d.code for d in chain_lint.lint_chain(dead)}
    assert "chain-unsat-conjunction" in codes
    # a tautological term (every atom always true) makes its group one
    taut = [P.Predicate("a", 0, P.OP_GT, -1.0, group="g", term="t"),
            P.Predicate("b", 0, P.OP_LT, 99.0, group="g", term="t"),
            P.Predicate("c", 1, P.OP_LT, 1.0, group="g")]
    codes = {d.code for d in chain_lint.lint_chain(
        taut, domains={0: (0.0, 10.0)})}
    assert "chain-group-always-true" in codes


def test_tree_state_checkpoints_round_trip():
    """A tree session's state, term counters included, survives
    save/restore bit for bit and steps on as if never stopped."""
    preds = q19_filters()
    plan = FilterPlan(preds, OrderingConfig(collect_rate=16,
                                            calculate_rate=3 * 4096))
    session = build_session(plan)
    state = session.init_state()
    for k in range(2):
        state, _ = session.step(state, q19_columns(200 + k, 4096))
    blob = session.save_state(state)
    assert blob["arrays"]["stats.term_cut"].shape == (6,)
    again = build_session(plan)
    restored = again.restore_state(blob)
    assert again.validate_state(restored)
    cols = q19_columns(300, 4096)
    a, ra = session.step(state, cols)
    b, rb = again.step(restored, cols)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(ra.metrics.perm),
                                  np.asarray(rb.metrics.perm))
