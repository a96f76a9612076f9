"""Predicate algebra for the adaptive filter operator.

A predicate is a vectorizable boolean test over one column of a columnar
``RecordBatch``.  The paper's predicates (comparisons on date / int / string
attributes of a structured log stream) map onto six op codes:

  OP_GT       x > t1
  OP_LT       x < t1
  OP_BETWEEN  t1 < x < t2
  OP_EQ       round(x) == round(t1)     (hashed-categorical equality)
  OP_HASHMIX  iterated arithmetic mix of x, ``rounds`` times, then > t1.
              This is the *expensive* predicate class (stands in for
              regex / string matching in the paper): its per-row cost is
              tunable and genuinely higher, so cost-aware ordering matters.
  OP_IN       round(x) in a set of dictionary codes from [0, 48): bit c of
              the set's 48-bit mask, bits 0-23 in t1 and 24-47 in t2
              (each a whole number below 2^24, exact in f32; ``in_set``
              builds them).

All columns are carried as float32.  String attributes are pre-hashed into
[0, 2^24) (exactly representable in f32) by the data layer.  The same op
semantics are implemented three times and cross-checked by tests:
pure-jnp (here), the Pallas kernel, and the row-level oracle in
``kernels/filter_chain/ref.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

OP_GT = 0
OP_LT = 1
OP_BETWEEN = 2
OP_EQ = 3
OP_HASHMIX = 4
OP_IN = 5

_OP_NAMES = {OP_GT: "gt", OP_LT: "lt", OP_BETWEEN: "between", OP_EQ: "eq",
             OP_HASHMIX: "hashmix", OP_IN: "in"}

#: OP_IN codes live in [0, IN_CODES); each half of the mask holds IN_WORD
IN_CODES = 48
IN_WORD = 24

# Arithmetic-mix constants for OP_HASHMIX (shared with kernel + oracle).
MIX_MUL = 1.0000019073486328  # exactly representable in f32
MIX_ADD = 0.31830987334251404
MIX_MOD = 1048576.0  # 2**20


@dataclasses.dataclass(frozen=True)
class Predicate:
    """One filter condition over ``column`` of the record batch.

    ``group`` extends the algebra from a flat conjunction to CNF: predicates
    sharing a group id are OR'ed together; distinct groups are AND'ed.
    ``group=None`` (default) puts the predicate in its own singleton group,
    so a chain of ungrouped predicates is exactly the paper's conjunction.
    Group labels are arbitrary hashables; ``pack`` normalizes them to dense
    ids in first-appearance order.

    ``term`` adds a third level inside a labelled group: predicates sharing
    a term label within one group are AND'ed into a term, the group's
    terms are OR'ed, and groups are AND'ed (an AND of ORs of ANDs). An
    unlabelled predicate in a group is a one-atom term, so a group without
    term labels is today's OR of single predicates.
    """

    name: str
    column: int
    op: int
    t1: float
    t2: float = 0.0
    rounds: int = 0          # extra mix rounds (OP_HASHMIX only)
    static_cost: float = 1.0  # calibrated per-row work units (STATIC cost mode)
    group: object = None     # CNF OR-group label; None → singleton group
    term: object = None      # AND-term label inside ``group``; None → alone

    def __post_init__(self) -> None:
        if self.op not in _OP_NAMES:
            raise ValueError(f"unknown op code {self.op}")
        if self.op == OP_HASHMIX and self.rounds < 1:
            raise ValueError("OP_HASHMIX requires rounds >= 1")
        if self.op == OP_IN:
            for t in (self.t1, self.t2):
                if t != int(t) or not 0 <= t < (1 << IN_WORD):
                    raise ValueError(
                        f"OP_IN masks must be whole numbers in [0, 2^24), "
                        f"got t1={self.t1} t2={self.t2} (use in_set)")
        if self.static_cost <= 0:
            raise ValueError("static_cost must be positive")
        if self.term is not None and self.group is None:
            raise ValueError(f"predicate {self.name!r}: a term label needs "
                             "a group label (terms are AND's inside an "
                             "OR-group)")

    def describe(self) -> str:
        grp = "" if self.group is None else f" group={self.group}"
        if self.term is not None:
            grp += f" term={self.term}"
        if self.op == OP_IN:
            args = f"codes={list(in_codes(self.t1, self.t2))}"
        else:
            args = f"t1={self.t1} t2={self.t2} rounds={self.rounds}"
        return f"{self.name}: col[{self.column}] {_OP_NAMES[self.op]} " \
               f"{args} c={self.static_cost}{grp}"


def in_set(codes) -> tuple[float, float]:
    """(t1, t2) of an OP_IN predicate testing membership in ``codes``."""
    lo = hi = 0
    for c in codes:
        c = int(c)
        if not 0 <= c < IN_CODES:
            raise ValueError(f"OP_IN code {c} outside [0, {IN_CODES})")
        if c < IN_WORD:
            lo |= 1 << c
        else:
            hi |= 1 << (c - IN_WORD)
    return float(lo), float(hi)


def in_codes(t1: float, t2: float) -> tuple[int, ...]:
    """The codes of an OP_IN mask pair (inverse of ``in_set``)."""
    lo, hi = int(t1), int(t2)
    return tuple(c for c in range(IN_CODES)
                 if (lo if c < IN_WORD else hi) >> (c % IN_WORD) & 1)


@dataclasses.dataclass(frozen=True)
class PredicateSpecs:
    """Structure-of-arrays packing of a predicate chain (kernel ABI).

    ``group`` is the CNF structure: a *static* tuple of dense group ids, one
    per predicate (it rides in the pytree aux data, not as an array, so jit
    traces can unroll group-shaped control flow and kernels can specialize on
    the grouping). ``()`` means all-singleton groups (flat conjunction).
    ``term`` is the same for the AND-terms inside groups: a static tuple of
    dense term ids, or ``()`` when the chain has no term labels — then every
    engine traces exactly the two-level (CNF) code it always did.
    """

    column: jnp.ndarray      # i32[P]
    op: jnp.ndarray          # i32[P]
    t1: jnp.ndarray          # f32[P]
    t2: jnp.ndarray          # f32[P]
    rounds: jnp.ndarray      # i32[P]
    static_cost: jnp.ndarray  # f32[P]
    group: tuple = ()        # static dense group id per predicate; () → flat
    term: tuple = ()         # static dense term id per predicate; () → none

    @property
    def n(self) -> int:
        return int(self.column.shape[0])

    @property
    def groups(self) -> tuple:
        """Dense group id per predicate (singletons when unset)."""
        return self.group if self.group else tuple(range(self.n))

    @property
    def n_groups(self) -> int:
        return max(self.groups) + 1

    @property
    def is_flat(self) -> bool:
        """True when every group is a singleton (plain conjunction)."""
        g = self.groups
        return len(set(g)) == len(g)

    @property
    def is_tree(self) -> bool:
        """True when the chain has AND-terms inside its OR-groups."""
        return bool(self.term)

    @property
    def n_terms(self) -> int:
        return max(self.term) + 1 if self.term else 0

    @property
    def group_members(self) -> tuple:
        """tuple[G] of tuple[int] — predicate indices per group (static)."""
        members: list[list[int]] = [[] for _ in range(self.n_groups)]
        for i, g in enumerate(self.groups):
            members[g].append(i)
        return tuple(tuple(m) for m in members)

    @property
    def term_members(self) -> tuple:
        """tuple[T] of tuple[int] — predicate indices per term (static)."""
        return tree_structure(self.groups, self.term)[0]

    @property
    def group_terms(self) -> tuple:
        """tuple[G] of tuple[int] — term ids per group, in term-id order."""
        return tree_structure(self.groups, self.term)[1]

    def tree_flatten(self):
        return ((self.column, self.op, self.t1, self.t2, self.rounds,
                 self.static_cost), (self.group, self.term))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, group=aux[0], term=aux[1])


jax.tree_util.register_pytree_node(
    PredicateSpecs, PredicateSpecs.tree_flatten, PredicateSpecs.tree_unflatten)


def tree_structure(groups: tuple, terms: tuple) -> tuple[tuple, tuple]:
    """(predicate indices per term, term ids per group) from the static
    dense id tuples of a tree; ((), ()) without terms."""
    if not terms:
        return (), ()
    term_members: list[list[int]] = [[] for _ in range(max(terms) + 1)]
    group_terms: list[list[int]] = [[] for _ in range(max(groups) + 1)]
    for i, (g, t) in enumerate(zip(groups, terms)):
        term_members[t].append(i)
        if t not in group_terms[g]:
            group_terms[g].append(t)
    return (tuple(tuple(m) for m in term_members),
            tuple(tuple(m) for m in group_terms))


def _dense_ids(keys: Sequence, what: str, names: Sequence[str]) -> tuple:
    """Dense ids of ``keys`` in first-appearance order; equal keys must be
    ADJACENT (every engine closes one accumulator at a time)."""
    ids: dict = {}
    out = []
    for i, key in enumerate(keys):
        gid = ids.setdefault(key, len(ids))
        if out and gid < len(ids) - 1 and out[-1] != gid:
            raise ValueError(
                f"predicates of {what} {key[-1]!r} are not contiguous in "
                f"statement order (predicate {i}: {names[i]!r}); "
                f"{what} members must be adjacent")
        out.append(gid)
    return tuple(out)


def normalize_groups(predicates: Sequence[Predicate]) -> tuple:
    """Dense group ids in first-appearance order; None → fresh singleton.

    Predicates sharing a group label must be ADJACENT in statement order:
    the statement order is the initial evaluation permutation, and every
    engine closes one OR accumulator at a time — the jit-traced engines
    (jnp, pallas) cannot detect an interleaved layout at runtime, so it is
    rejected here, at the one eager choke point.
    """
    keys = [("__singleton__", i) if p.group is None else ("user", p.group)
            for i, p in enumerate(predicates)]
    return _dense_ids(keys, "group", [p.name for p in predicates])


def normalize_terms(predicates: Sequence[Predicate]) -> tuple:
    """Dense term ids in first-appearance order, or () without term labels.

    A term label is scoped to its group; an unlabelled predicate is a
    one-atom term. Members of one term must be adjacent, like a group's.
    """
    if all(p.term is None for p in predicates):
        return ()
    gids = normalize_groups(predicates)
    keys = [(g, "__alone__", i) if p.term is None else (g, "user", p.term)
            for i, (g, p) in enumerate(zip(gids, predicates))]
    return _dense_ids(keys, "term", [p.name for p in predicates])


def pack(predicates: Sequence[Predicate]) -> PredicateSpecs:
    """Pack a python predicate chain into the SoA kernel ABI."""
    if not predicates:
        raise ValueError("empty predicate chain")
    return PredicateSpecs(
        column=jnp.asarray([p.column for p in predicates], jnp.int32),
        op=jnp.asarray([p.op for p in predicates], jnp.int32),
        t1=jnp.asarray([p.t1 for p in predicates], jnp.float32),
        t2=jnp.asarray([p.t2 for p in predicates], jnp.float32),
        rounds=jnp.asarray([p.rounds for p in predicates], jnp.int32),
        static_cost=jnp.asarray([p.static_cost for p in predicates], jnp.float32),
        group=normalize_groups(predicates),
        term=normalize_terms(predicates),
    )


def hashmix(x: jnp.ndarray, rounds) -> jnp.ndarray:
    """Iterated arithmetic mix — the tunably-expensive predicate body.

    Deterministic, branch-free, identical in jnp / Pallas / numpy oracle.
    """
    def body(_, y):
        y = y * MIX_MUL + MIX_ADD
        return y - jnp.floor(y / MIX_MOD) * MIX_MOD

    return jax.lax.fori_loop(0, rounds, body, x.astype(jnp.float32))


def in_mask(x, t1, t2):
    """OP_IN body: bit round(x) of the (t1, t2) mask pair, as bool.

    Shared by the jnp engine and the Pallas kernel: integer shifts of the
    mask word the code falls in; codes outside [0, 48) (and NaN) pass
    nothing.
    """
    c = jnp.round(jnp.clip(x, -1.0, float(IN_CODES)))
    c = jnp.where(jnp.isnan(x), -1.0, c).astype(jnp.int32)
    low = c < IN_WORD
    word = jnp.where(low, t1, t2).astype(jnp.int32)   # vector f32 → i32
    shift = jnp.clip(jnp.where(low, c, c - IN_WORD), 0, IN_WORD - 1)
    bit = jax.lax.shift_right_logical(word, shift) & 1
    return jnp.logical_and(jnp.logical_and(c >= 0, c < IN_CODES), bit == 1)


def eval_one(specs: PredicateSpecs, i, x: jnp.ndarray) -> jnp.ndarray:
    """Evaluate predicate ``i`` (dynamic index) of ``specs`` on values ``x``.

    ``x`` is the *already-selected* column values, f32[R]. Returns bool[R].
    """
    op = specs.op[i]
    t1 = specs.t1[i]
    t2 = specs.t2[i]
    rounds = specs.rounds[i]

    # Branches are lazy: the expensive mix only runs when op == OP_HASHMIX,
    # preserving the cost heterogeneity the ordering exploits.
    return jax.lax.switch(op, [
        lambda: x > t1,
        lambda: x < t1,
        lambda: jnp.logical_and(x > t1, x < t2),
        lambda: jnp.round(x) == jnp.round(t1),
        lambda: hashmix(x, jnp.maximum(rounds, 1)) > t1,
        lambda: in_mask(x, t1, t2),
    ])


def eval_all(specs: PredicateSpecs, columns: jnp.ndarray) -> jnp.ndarray:
    """Evaluate every predicate on every row: bool[P, R].

    ``columns`` is f32[C, R]. Used by the monitor lane (the paper evaluates
    *all* predicates on sampled rows to avoid correlation bias) and by tests.
    """
    def one(i):
        x = columns[specs.column[i]]
        return eval_one(specs, i, x)

    return jax.vmap(one)(jnp.arange(specs.n))


def chain_cost_row_model(specs: PredicateSpecs, pass_probs: jnp.ndarray,
                         perm: jnp.ndarray) -> jnp.ndarray:
    """Expected per-row cost of evaluating the chain in ``perm`` order.

    Implements the textbook objective the paper's rank ordering minimizes:
      E[cost] = sum_i c_{perm[i]} * prod_{j<i} s_{perm[j]}
    with s = per-predicate pass probability (selectivity). Used by property
    tests to verify rank-ascending order is optimal.
    """
    c = specs.static_cost[perm]
    s = pass_probs[perm]
    surv = jnp.concatenate([jnp.ones((1,), s.dtype), jnp.cumprod(s)[:-1]])
    return jnp.sum(c * surv)


def tree_cost_row_model(specs: PredicateSpecs, pass_probs,
                        perm) -> float:
    """Expected per-row cost of a tree (AND of ORs of ANDs) in ``perm``
    order, for independent atoms with pass probabilities ``pass_probs``.

    The three-level objective the tree ordering minimizes, each level a
    short-circuit of the one below (``perm`` keeps groups and terms
    contiguous):

      term   t = a_1 AND ... AND a_k:  C_t = Σ_i c_{a_i} Π_{j<i} s_{a_j},
             P_t = Π_i s_{a_i}          (stops at the first atom that fails)
      group  g = t_1 OR ... OR t_m:    C_g = Σ_i C_{t_i} Π_{j<i} (1 - P_{t_j}),
             P_g = 1 - Π_i (1 - P_{t_i})  (stops at the first term that passes)
      chain  g_1 AND ... AND g_n:      Σ_i C_{g_i} Π_{j<i} P_{g_j}

    which is exactly what a row-at-a-time evaluator with both short-
    circuits pays. Each level is a sequence of independent units whose
    costs do not depend on the order of the others, so the exchange
    argument orders them by a rank: atoms by c/(1-s), terms by C_t/P_t,
    groups by C_g/(1-P_g), all ascending (``stats.tree_order``). A chain
    without terms is the special case of one-atom terms, where this equals
    ``chain_cost_row_model`` on flat chains. Host (numpy) evaluation; used
    by the property tests.
    """
    cost = np.asarray(specs.static_cost, np.float64)
    s = np.asarray(pass_probs, np.float64)
    groups = specs.groups
    terms = specs.term if specs.term else tuple(range(specs.n))
    total, reach = 0.0, 1.0               # reach: P(all earlier groups pass)
    k, perm = 0, [int(i) for i in perm]
    while k < len(perm):
        g = groups[perm[k]]
        g_cost, miss = 0.0, 1.0           # miss: P(all earlier terms fail)
        while k < len(perm) and groups[perm[k]] == g:
            t = terms[perm[k]]
            t_cost, alive = 0.0, 1.0
            while k < len(perm) and terms[perm[k]] == t:
                t_cost += cost[perm[k]] * alive
                alive *= s[perm[k]]
                k += 1
            g_cost += t_cost * miss
            miss *= 1.0 - alive
        total += g_cost * reach
        reach *= 1.0 - miss
    return total


def paper_filters_4(selectivity_target: str = "fig1") -> list[Predicate]:
    """The paper's experimental chain: 2 int predicates, 1 date, 1 string.

    Columns: 0=date (days, normal), 1=int (normal), 2=string-hash.
    Thresholds are chosen by the data layer's generator statistics so that
    overall selectivity ~= 4.51% ("fig1") or ~= 16.14% ("sens").
    """
    from repro.data.stream import threshold_for_quantile  # cycle-free at runtime

    # The two int predicates form a range (as in the paper's hour>7 && hour<16
    # example), so they are CORRELATED: joint int pass = a + b - 1. Overall
    # selectivity = (a+b-1) * d * s.
    if selectivity_target == "fig1":
        # (.62+.62-1) * .5 * .376 = 0.0451
        a, b, d, s = 0.62, 0.62, 0.50, 0.376
    elif selectivity_target == "sens":
        # (.75+.75-1) * .62 * .5208 = 0.1614
        a, b, d, s = 0.75, 0.75, 0.62, 0.5208
    else:
        raise ValueError(selectivity_target)

    return [
        Predicate("int_hi", column=1, op=OP_GT,
                  t1=threshold_for_quantile("int", 1.0 - a), static_cost=1.0),
        Predicate("int_lo", column=1, op=OP_LT,
                  t1=threshold_for_quantile("int", b), static_cost=1.0),
        Predicate("date_gt", column=0, op=OP_GT,
                  t1=threshold_for_quantile("date", 1.0 - d), static_cost=1.2),
        Predicate("str_match", column=2, op=OP_HASHMIX,
                  t1=(1.0 - s) * MIX_MOD, rounds=24, static_cost=6.0),
    ]


def paper_filters_cnf(selectivity_target: str = "fig1") -> list[Predicate]:
    """CNF (AND-of-OR) variant of the paper chain.

    Same columns and thresholds; the date and string predicates collapse
    into one OR-group ("recent OR matching") while the two int range
    predicates stay singleton groups:

        int_hi AND int_lo AND (date_gt OR str_match)

    This is the first filter shape the flat conjunction could not express.
    The OR-group pairs a cheap selective member with an expensive one, so
    both levels of the ordering matter: the group's rank against the int
    predicates, and evaluating ``date_gt`` before ``str_match`` inside the
    group (an OR short-circuits on the first PASS, so the cheap member
    spares most rows the hashmix).
    """
    int_hi, int_lo, date_gt, str_match = paper_filters_4(selectivity_target)
    return [
        int_hi, int_lo,
        dataclasses.replace(date_gt, group="recent_or_match"),
        dataclasses.replace(str_match, group="recent_or_match"),
    ]

