"""Per-predicate statistics: the paper's numCut / cost arrays and rank math.

Faithful to §2.1 of the paper:

  * ``num_cut[i]``   — monitored rows that did NOT satisfy predicate i
  * ``cost_acc[i]``  — accumulated evaluation cost attributed to predicate i
  * selectivity      s_i  = 1 - num_cut_i / n_monitored        (pass fraction)
  * normalized cost  nc_i = avg_cost_i / max_j avg_cost_j  ∈ [0, 1]
  * rank             rank_i = nc_i / (1 - s_i)
  * momentum         adj_rank_i(t) = (1-m)·rank_i(t) + m·adj_rank_i(t-1)

Ordering predicates by adj_rank ascending minimizes the expected per-row
chain cost  Σ_i c_i Π_{j<i} s_j  (see tests/test_property_hypothesis.py for
the machine-checked proof-by-enumeration).

CNF extension (AND of OR-groups): the same machinery lifts to *groups* —

  * ``group_cut[g]`` — monitored rows cut by group g (no member passed)
  * group selectivity  S_g = 1 - group_cut_g / n_monitored  (exact, not the
    independence product — the monitor lane sees the full outcome matrix)
  * group cost         Σ_{i∈g} avg_cost_i, normalized by the max group
  * group rank         gnc_g / (1 - S_g); groups evaluated rank-ascending
  * within a group, members are ordered by miss-rate: an OR short-circuits
    on the first PASS, so cheap high-pass-rate members go first
    (member rank = nc_i / s_i — the conjunction formula with s ↔ 1-s).

Tree extension (AND of ORs of AND-terms; ``tree_order``): the monitor lane
also counts ``term_cut[t]``, the sampled rows term t cuts (some atom
fails), so P(term passes) is exact too. Atoms inside a term are ordered by
their conjunction rank, terms inside a group by expected term cost over
P(term passes), and groups by rank as above — with a group's cost its
expected cost under that term order (Σ member costs would overstate an OR
that stops at its first passing term). Chains without terms never reach
this code, so their ordering is unchanged bit for bit.

Every function here is **backend-agnostic**: it takes an array-namespace
argument ``xp`` (``jax.numpy`` or ``numpy``) and runs the identical code
path on either, so there is exactly one implementation of the rank math for
the jitted device pipeline and the host (numpy) streaming path. A parity
test pins the two bit-close.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax.numpy as jnp
import numpy as np

_EPS = 1e-6

#: Saturation guard for the f32 epoch accumulators. f32 stops absorbing
#: +1-sized increments at 2^24 (ulp = 2), which silently freezes the
#: selectivity/cost estimates — and with them the adaptive ordering — on
#: long epochs (collect_rate=1 with a 10^8-row calculate_rate is a real
#: long-stream config). ``accumulate`` therefore decays every accumulator
#: by ``SAT_DECAY`` whenever ``n_monitored`` crosses ``SAT_THRESHOLD``:
#: multiplying an f32 by 0.5 only decrements the exponent (exact, never
#: rounds), so selectivities and average costs — the RATIOS the rank math
#: consumes — are preserved bit-for-bit, rank order is untouched, and the
#: accumulators stay in a range where integer increments remain exact.
#: Within a super-long epoch the evidence becomes exponentially weighted
#: toward recent batches, which is the behavior an *adaptive* filter
#: wants anyway. Epochs shorter than SAT_THRESHOLD monitored rows (every
#: paper configuration) never trigger it: the scale factor is exactly 1.0
#: and ``x * 1.0`` is a bit-exact no-op.
SAT_THRESHOLD = float(1 << 22)
SAT_DECAY = 0.5


def argsort_stable(a, xp=jnp):
    """Stable ascending argsort; the only API seam between numpy and jnp."""
    if xp is jnp:
        return jnp.argsort(a, stable=True)
    return np.argsort(a, kind="stable")


class FilterStats(NamedTuple):
    """Accumulators collected since the start of the current epoch.

    ``group_cut`` is None for consumers that predate CNF (flat chains treat
    every predicate as its own group, where group_cut ≡ num_cut).
    ``term_cut`` is None unless the chain has AND-terms.
    """

    num_cut: Any       # f32[P]
    cost_acc: Any      # f32[P]
    n_monitored: Any   # f32[]
    group_cut: Any = None  # f32[G] | None
    term_cut: Any = None   # f32[T] | None


def init_stats(n_predicates: int, n_groups: int | None = None,
               xp=jnp, n_terms: int = 0) -> FilterStats:
    if n_groups is None:
        n_groups = n_predicates
    return FilterStats(
        num_cut=xp.zeros((n_predicates,), xp.float32),
        cost_acc=xp.zeros((n_predicates,), xp.float32),
        n_monitored=xp.zeros((), xp.float32),
        group_cut=xp.zeros((n_groups,), xp.float32),
        term_cut=xp.zeros((n_terms,), xp.float32) if n_terms else None,
    )


def merge_stats(a: FilterStats, b: FilterStats) -> FilterStats:
    """Associative merge (used by the centralized scope's psum and by tests)."""
    gc = tc = None
    if a.group_cut is not None and b.group_cut is not None:
        gc = a.group_cut + b.group_cut
    if a.term_cut is not None and b.term_cut is not None:
        tc = a.term_cut + b.term_cut
    return FilterStats(a.num_cut + b.num_cut, a.cost_acc + b.cost_acc,
                       a.n_monitored + b.n_monitored, gc, tc)


def accumulate(stats: FilterStats, cut_counts, costs, n_monitored,
               group_cut=None, xp=jnp, term_cut=None) -> FilterStats:
    """Fold one batch's monitor-lane results into the epoch accumulators.

    Saturation guard (see ``SAT_THRESHOLD``): once the epoch has monitored
    2^22 rows, every accumulator is decayed by the exact-in-f32 factor 0.5
    BEFORE the batch folds in, so increments keep landing in a range where
    f32 absorbs them and the adaptive ordering never freezes. The decay is
    branchless (``xp.where`` on a scalar) and a bit-exact no-op (×1.0)
    below the threshold; because ``n_monitored`` advances deterministically
    (static batch widths), sharded replicas trigger it in lockstep.
    """
    scale = xp.where(stats.n_monitored >= SAT_THRESHOLD,
                     xp.float32(SAT_DECAY), xp.float32(1.0))
    if stats.group_cut is None:
        new_gc = None
    else:
        inc = cut_counts if group_cut is None else group_cut
        new_gc = stats.group_cut * scale + inc.astype(xp.float32)
    new_tc = None
    if stats.term_cut is not None:
        new_tc = stats.term_cut * scale + term_cut.astype(xp.float32)
    return FilterStats(
        num_cut=stats.num_cut * scale + cut_counts.astype(xp.float32),
        cost_acc=stats.cost_acc * scale + costs.astype(xp.float32),
        n_monitored=stats.n_monitored * scale
        + xp.asarray(n_monitored, xp.float32),
        group_cut=new_gc,
        term_cut=new_tc,
    )


def selectivities(stats: FilterStats, xp=jnp):
    """Pass fraction per predicate, from monitored rows only (paper §2.1)."""
    n = xp.maximum(stats.n_monitored, 1.0)
    s = 1.0 - stats.num_cut / n
    return xp.clip(s, 0.0, 1.0)


def normalized_costs(stats: FilterStats, xp=jnp):
    """Average per-row cost, min-max-free normalization to [0,1] by the max."""
    n = xp.maximum(stats.n_monitored, 1.0)
    avg = stats.cost_acc / n
    return avg / xp.maximum(xp.max(avg), _EPS)


def ranks(stats: FilterStats, xp=jnp):
    """rank_i = nc_i / (1 - s_i); selective-and-cheap predicates rank lowest.

    The 1-s denominator is floored so an all-pass predicate gets a large but
    finite rank (it should run last — it cuts nothing).
    """
    s = selectivities(stats, xp=xp)
    nc = normalized_costs(stats, xp=xp)
    return nc / xp.maximum(1.0 - s, _EPS)


def member_ranks(stats: FilterStats, xp=jnp):
    """Within-OR-group order key: nc_i / s_i ascending.

    An OR group short-circuits when a member PASSES, so the optimal member
    order puts cheap, *high*-pass-rate (low miss-rate) members first — the
    mirror image of the conjunction rank (s ↔ 1-s).
    """
    s = selectivities(stats, xp=xp)
    nc = normalized_costs(stats, xp=xp)
    return nc / xp.maximum(s, _EPS)


def _group_matrix(groups, xp=jnp):
    """f32[G, P] membership one-hot built from the static group tuple."""
    g = np.asarray(groups, np.int64)
    m = np.zeros((int(g.max()) + 1, len(groups)), np.float32)
    m[g, np.arange(len(groups))] = 1.0
    return xp.asarray(m)


def group_selectivities(stats: FilterStats, xp=jnp):
    """Exact P(group passes) from the monitor lane's group-cut counters."""
    gcut = stats.group_cut if stats.group_cut is not None else stats.num_cut
    n = xp.maximum(stats.n_monitored, 1.0)
    return xp.clip(1.0 - gcut / n, 0.0, 1.0)


def group_normalized_costs(stats: FilterStats, groups, xp=jnp):
    """Group cost = Σ member avg costs, normalized to [0,1] by the max group.

    For all-singleton groups this reduces exactly to ``normalized_costs``
    (same max normalizer), keeping flat chains bit-identical to the paper
    math.
    """
    n = xp.maximum(stats.n_monitored, 1.0)
    avg = stats.cost_acc / n
    gavg = _group_matrix(groups, xp=xp) @ avg
    return gavg / xp.maximum(xp.max(gavg), _EPS)


def group_ranks(stats: FilterStats, groups, xp=jnp):
    """Group-level rank = gnc_g / (1 - S_g); ≡ ``ranks`` on flat chains."""
    s = group_selectivities(stats, xp=xp)
    nc = group_normalized_costs(stats, groups, xp=xp)
    return nc / xp.maximum(1.0 - s, _EPS)


def momentum_update(adj_prev, rank_now, momentum, first_epoch, xp=jnp):
    """First-order difference equation from the paper, with cold-start.

    On the very first epoch there is no history: adj_rank(0) = rank(0)
    (equivalently momentum is ignored once).
    """
    m = xp.asarray(momentum, xp.float32)
    blended = (1.0 - m) * rank_now + m * adj_prev
    return xp.where(first_epoch, rank_now, blended)


def order_from_ranks(adj_rank, xp=jnp):
    """Ascending stable sort → evaluation permutation (ties by user order)."""
    return argsort_stable(adj_rank, xp=xp).astype(xp.int32)


def cnf_order(group_adj_rank, member_rank, groups, xp=jnp):
    """Full CNF evaluation order from group + member ranks.

    Returns (perm i32[P], group_perm i32[G]): groups concatenated in
    group-rank-ascending order (ties by group id), members within each group
    in member-rank-ascending order (ties by user order). Group members are
    always CONTIGUOUS in ``perm`` — the execution engines rely on that to
    close one OR accumulator at a time.

    Built from two composed stable sorts so it is traceable under jit with
    dynamic ranks: the primary key is each group's *position* in the sorted
    group order (a distinct integer per group, so equal group ranks can
    never interleave members of different groups).
    """
    garr = xp.asarray(np.asarray(groups, np.int32))
    group_perm = argsort_stable(group_adj_rank, xp=xp).astype(xp.int32)
    group_pos = argsort_stable(group_perm, xp=xp)   # inverse permutation
    primary = group_pos[garr]                        # i32[P]
    by_member = argsort_stable(member_rank, xp=xp)
    perm = by_member[argsort_stable(primary[by_member], xp=xp)]
    return perm.astype(xp.int32), group_perm


# ------------------------------------------------------------------ trees
def term_selectivities(stats: FilterStats, xp=jnp):
    """Exact P(term passes) from the monitor lane's term-cut counters,
    computed as ``selectivities`` is, so a one-atom term's equals its
    atom's bit for bit."""
    n = xp.maximum(stats.n_monitored, 1.0)
    return xp.clip(1.0 - stats.term_cut / n, 0.0, 1.0)


def tree_levels(stats: FilterStats, term_members, group_terms, xp=jnp):
    """The three levels of a tree's ordering (``predicates.
    tree_cost_row_model``).

    Returns (group rank f32[G], term rank f32[T], atom rank f32[P],
    group cost f32[G]): atoms rank by the conjunction rank nc/(1-s) (an
    AND stops at the first failing atom); terms by C_t / P_t, C_t the
    term's expected cost in that atom order (an OR stops at the first
    passing term — for a one-atom term this is ``member_ranks`` bit for
    bit); groups as ``group_ranks`` does, C_g / (1 - S_g), with C_g the
    group's expected cost in that term order normalized by the largest
    group's. ``term_members``/``group_terms`` are the static structure of
    ``PredicateSpecs``.
    """
    s = selectivities(stats, xp=xp)
    nc = normalized_costs(stats, xp=xp)
    atom_rank = nc / xp.maximum(1.0 - s, _EPS)
    p_term = term_selectivities(stats, xp=xp)
    t_cost = []
    for mem in term_members:                     # static unroll
        idx = xp.asarray(list(mem), xp.int32)
        order = idx[argsort_stable(atom_rank[idx], xp=xp)]
        t_cost.append(expected_chain_cost(nc, s, order, xp=xp))
    t_cost = xp.stack(t_cost)
    term_rank = t_cost / xp.maximum(p_term, _EPS)
    g_cost = []
    for terms in group_terms:
        idx = xp.asarray(list(terms), xp.int32)
        order = idx[argsort_stable(term_rank[idx], xp=xp)]
        g_cost.append(expected_chain_cost(t_cost, 1.0 - p_term, order,
                                          xp=xp))
    g_cost = xp.stack(g_cost)
    gnc = g_cost / xp.maximum(xp.max(g_cost), _EPS)
    group_rank = gnc / xp.maximum(1.0 - group_selectivities(stats, xp=xp),
                                  _EPS)
    return group_rank, term_rank, atom_rank, gnc


def tree_order(group_adj_rank, term_rank, atom_rank, groups, terms,
               xp=jnp):
    """Full tree evaluation order: groups by (smoothed) rank, terms inside
    each group by term rank, atoms inside each term by atom rank, ties by
    user order. Returns (perm i32[P], group_perm i32[G]); groups and terms
    stay contiguous in ``perm``.

    Three composed stable sorts, least significant key first (as
    ``cnf_order``): atom rank, then each atom's term position in the
    global term-rank order, then its group's position in the group order
    (terms never span groups, so the term key only breaks ties inside a
    group).
    """
    garr = xp.asarray(list(groups), xp.int32)
    tarr = xp.asarray(list(terms), xp.int32)
    group_perm = argsort_stable(group_adj_rank, xp=xp).astype(xp.int32)
    group_pos = argsort_stable(group_perm, xp=xp)
    term_pos = argsort_stable(argsort_stable(term_rank, xp=xp), xp=xp)
    perm = argsort_stable(atom_rank, xp=xp)
    perm = perm[argsort_stable(term_pos[tarr][perm], xp=xp)]
    perm = perm[argsort_stable(group_pos[garr][perm], xp=xp)]
    return perm.astype(xp.int32), group_perm


def expected_chain_cost(costs, pass_probs, perm, xp=jnp):
    """Σ_i c_{perm[i]} Π_{j<i} s_{perm[j]} — the quantity rank order minimizes
    (with s the probability that evaluation goes on past a unit: its pass
    rate in an AND, its miss rate in an OR)."""
    c = costs[perm]
    s = pass_probs[perm]
    surv = xp.concatenate([xp.ones((1,), s.dtype), xp.cumprod(s)[:-1]])
    return xp.sum(c * surv)
