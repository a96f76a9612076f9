"""The plain reference for chains with OR-groups, AND-terms and IN lists:
numpy evaluation of the tree and of its three-level epoch ordering. It
imports nothing of the program.

Predicates come from the configuration file as dicts ``{"name", "column",
"op", "t1", "t2", "codes", "group", "term", "static_cost"}`` with ``op``
one of ``gt`` (x > t1), ``lt`` (x < t1), ``between`` (t1 < x < t2),
``eq`` (round(x) == round(t1)) and ``in`` (round(x) is one of ``codes``;
rounding half to even). Predicates sharing a ``group`` label are OR'ed;
inside a group, predicates sharing a ``term`` label are AND'ed into one
term (an unlabelled one is a term alone); the groups are AND'ed. All
arithmetic on the columns is float32, as the configurations state.
"""

from __future__ import annotations

import numpy as np

from bench.reference import EPS, as_bfloat16, survivor_checksum  # noqa: F401


def predicate(x: np.ndarray, p: dict) -> np.ndarray:
    """bool[R]: rows of column values ``x`` (f32) that pass ``p``."""
    op = p["op"]
    if op == "in":
        return np.isin(np.round(x), np.asarray(p["codes"], np.float32))
    t1 = np.float32(p.get("t1", 0.0))
    if op == "gt":
        return x > t1
    if op == "lt":
        return x < t1
    if op == "between":
        return (x > t1) & (x < np.float32(p["t2"]))
    if op == "eq":
        return np.round(x) == np.round(t1)
    raise ValueError(f"unknown predicate op {op!r}")


def structure(predicates: list[dict]) -> tuple[list[int], list[int]]:
    """(group id, term id) per predicate, dense in order of first
    appearance; an unlabelled predicate is a group or term alone."""
    g_ids: dict = {}
    t_ids: dict = {}
    groups, terms = [], []
    for i, p in enumerate(predicates):
        g_key = ("label", p["group"]) if p.get("group") is not None \
            else ("alone", i)
        g = g_ids.setdefault(g_key, len(g_ids))
        t_key = (g, "label", p["term"]) if p.get("term") is not None \
            else (g, "alone", i)
        groups.append(g)
        terms.append(t_ids.setdefault(t_key, len(t_ids)))
    return groups, terms


def outcomes(cols: np.ndarray, predicates: list[dict]):
    """(atom pass bool[P, R], term pass bool[T, R], group pass
    bool[G, R])."""
    groups, terms = structure(predicates)
    atoms = np.stack([predicate(cols[p["column"]], p) for p in predicates])
    t_pass = np.ones((max(terms) + 1, cols.shape[1]), bool)
    g_pass = np.zeros((max(groups) + 1, cols.shape[1]), bool)
    for i, t in enumerate(terms):
        t_pass[t] &= atoms[i]
    for t, g in {(t, g) for t, g in zip(terms, groups)}:
        g_pass[g] |= t_pass[t]
    return atoms, t_pass, g_pass


def tree_mask(cols: np.ndarray, predicates: list[dict]) -> np.ndarray:
    """bool[R]: rows passing every group."""
    return outcomes(cols, predicates)[2].all(axis=0)


def cut_counts(cols: np.ndarray, predicates: list[dict], start: int,
               stride: int):
    """Rows at ``start, start+stride, ...`` (the paper's monitor lane)
    that fail each atom, term and group: (atom f64[P], term f64[T],
    group f64[G], number of rows)."""
    rows = cols[:, start::stride]
    atoms, t_pass, g_pass = outcomes(rows, predicates)
    return ((~atoms).sum(axis=1).astype(np.float64),
            (~t_pass).sum(axis=1).astype(np.float64),
            (~g_pass).sum(axis=1).astype(np.float64), rows.shape[1])


# ------------------------------------------------------------ the ordering
def _sequence_cost(cost, survive, order) -> float:
    """Expected cost of a short-circuit sequence: Σ c_k Π_{j<k} survive_j."""
    total, alive = 0.0, 1.0
    for k in order:
        total += cost[k] * alive
        alive *= survive[k]
    return total


class Ordering:
    """The three-level epoch ordering of a tree (Table 1's rates).

    Every ``collect_rate``-th row is monitored and every atom evaluated on
    it; after ``calculate_rate`` rows the epoch closes. From its counts:
    atom selectivity s = 1 - cut/monitored, cost normalised by the largest,
    atom rank = cost / (1 - s); a term's pass rate P from its own cut
    count, its cost C_t the expected cost of its atoms in rank order
    (Σ c_k Π_{j<k} s_j), its rank C_t / P; a group's cost C_g the expected
    cost of its terms in rank order (Σ C_t Π (1 - P)), normalised by the
    largest, its rank C_g / (1 - S_g) with S_g from its cut count,
    smoothed by ``momentum`` (the first epoch takes the rank as it is).
    The chain runs groups in ascending smoothed rank, each group's terms
    and each term's atoms in ascending rank of the epoch just closed.
    """

    def __init__(self, predicates: list[dict], ordering: dict):
        self.groups, self.terms = structure(predicates)
        self.collect = int(ordering["collect_rate"])
        self.calculate = int(ordering["calculate_rate"])
        self.momentum = float(ordering["momentum"])
        cost = np.array([p.get("static_cost", 1.0) for p in predicates])
        self.norm_cost = cost / max(cost.max(), EPS)
        n, n_t, n_g = len(predicates), max(self.terms) + 1, \
            max(self.groups) + 1
        self.phase = self.rows = self.epoch = 0
        self.adj = self.term_rank = self.atom_rank = None
        self.cut, self.t_cut, self.g_cut = (np.zeros(n), np.zeros(n_t),
                                            np.zeros(n_g))
        self.n_mon = 0

    def sample_start(self) -> int:
        return (-self.phase) % self.collect

    def fold(self, cuts, n_mon: int, local_rows: int) -> bool:
        """Fold one step's (atom, term, group) monitor counts; returns True
        when the step closes an epoch."""
        self.cut += cuts[0]
        self.t_cut += cuts[1]
        self.g_cut += cuts[2]
        self.n_mon += n_mon
        self.phase = (self.phase + local_rows) % self.collect
        self.rows += local_rows
        if self.rows < self.calculate:
            return False
        self.rows %= self.calculate
        if self.n_mon > 0:
            self._rerank()
            self.epoch += 1
        self.cut, self.t_cut, self.g_cut = (np.zeros_like(self.cut),
                                            np.zeros_like(self.t_cut),
                                            np.zeros_like(self.g_cut))
        self.n_mon = 0
        return True

    def _rerank(self) -> None:
        n = self.n_mon
        s = np.clip(1.0 - self.cut / n, 0.0, 1.0)
        p_term = np.clip(1.0 - self.t_cut / n, 0.0, 1.0)
        s_group = np.clip(1.0 - self.g_cut / n, 0.0, 1.0)
        c = self.norm_cost
        atom_rank = c / np.maximum(1.0 - s, EPS)
        t_cost = np.zeros(len(p_term))
        for t in range(len(p_term)):
            mem = [i for i, x in enumerate(self.terms) if x == t]
            order = sorted(mem, key=lambda i: (atom_rank[i], i))
            t_cost[t] = _sequence_cost(c, s, order)
        term_rank = t_cost / np.maximum(p_term, EPS)
        g_cost = np.zeros(len(s_group))
        for g in range(len(s_group)):
            mem = sorted({t for t, x in zip(self.terms, self.groups)
                          if x == g})
            order = sorted(mem, key=lambda t: (term_rank[t], t))
            g_cost[g] = _sequence_cost(t_cost, 1.0 - p_term, order)
        g_cost = g_cost / max(g_cost.max(), EPS)
        rank = g_cost / np.maximum(1.0 - s_group, EPS)
        self.adj = rank if self.adj is None else \
            (1.0 - self.momentum) * rank + self.momentum * self.adj
        self.term_rank, self.atom_rank = term_rank, atom_rank

    def order(self) -> list[int]:
        """The order the reference adopts now (statement order before the
        first epoch)."""
        n = len(self.terms)
        if self.adj is None:
            return list(range(n))
        return sorted(range(n), key=lambda i: (
            self.adj[self.groups[i]], self.groups[i],
            self.term_rank[self.terms[i]], self.terms[i],
            self.atom_rank[i], i))

    def misordered(self, perm, rel: float = 1e-4) -> bool:
        """True when ``perm`` is not a permutation, splits a group or a
        term, or does not run groups, terms within a group and atoms within
        a term in ascending reference rank (ranks within ``rel`` of each
        other may run in either order: the program keeps them in f32)."""
        perm = [int(i) for i in np.asarray(perm).reshape(-1)]
        n = len(self.terms)
        if sorted(perm) != list(range(n)):
            return True
        if self.adj is None:
            return perm != list(range(n))
        for ids in (self.groups, self.terms):
            runs = [ids[i] for k, i in enumerate(perm)
                    if k == 0 or ids[perm[k - 1]] != ids[i]]
            if len(runs) != len(set(runs)):
                return True

        def ascending(keys) -> bool:
            return all(a <= b * (1.0 + rel) + 1e-12
                       for a, b in zip(keys, keys[1:]))

        g_seq = [self.groups[i] for k, i in enumerate(perm)
                 if k == 0 or self.groups[perm[k - 1]] != self.groups[i]]
        if not ascending([self.adj[g] for g in g_seq]):
            return True
        for g in g_seq:
            t_seq = [self.terms[i] for k, i in enumerate(perm)
                     if self.groups[i] == g
                     and (k == 0 or self.terms[perm[k - 1]] != self.terms[i])]
            if not ascending([self.term_rank[t] for t in t_seq]):
                return True
            for t in t_seq:
                if not ascending([self.atom_rank[i] for i in perm
                                  if self.terms[i] == t]):
                    return True
        return False
