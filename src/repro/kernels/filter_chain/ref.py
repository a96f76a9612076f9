"""Pure-jnp oracle for the fused filter-chain kernel.

Deliberately computed a *different* way from both the kernel and
``core.filter_exec.run_chain``: the dense [P, R] outcome matrix is built
up-front (no laziness, no tiling, no masked short-circuit) and the CNF
chain — mask, pending counts, group cuts — is derived from that matrix with
plain boolean algebra, so a bug in the lazy/tiled paths cannot hide in the
oracle. Row-level work accounting (the Spark model with OR- and AND-level
short-circuit) falls out of the per-position pending masks. Trees (AND-terms
inside the OR-groups) are one more level of the same algebra.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import predicates as pred_lib
from repro.core.engine.base import CHAIN_TILE, ChainResult
from repro.core.predicates import PredicateSpecs


def filter_chain_ref(columns: jnp.ndarray, specs: PredicateSpecs,
                     perm: jnp.ndarray, *, collect_rate: int,
                     sample_phase) -> ChainResult:
    n_rows = columns.shape[1]
    outcomes = pred_lib.eval_all(specs, columns)          # bool[P, R]
    groups = np.asarray(specs.groups)
    # one-atom terms when the chain has none: the same algebra
    terms = np.asarray(specs.term if specs.is_tree else range(specs.n))
    term_members = specs.term_members if specs.is_tree \
        else tuple((i,) for i in range(specs.n))
    perm_host = [int(i) for i in np.asarray(perm)]        # oracle runs eager

    # pass matrices (order-invariant): a row passes term t iff EVERY atom
    # of t passes, group g iff ANY of its terms passes; the chain mask is
    # the AND over groups.
    tpass = jnp.stack([jnp.all(outcomes[jnp.asarray(m)], axis=0)
                       for m in term_members])            # bool[T, R]
    group_terms = [sorted({int(terms[i]) for i in m})
                   for m in specs.group_members]
    gpass = jnp.stack([jnp.any(tpass[jnp.asarray(t)], axis=0)
                       for t in group_terms])             # bool[G, R]
    mask = jnp.all(gpass, axis=0)

    # work model: walk perm positions; a row is pending at position k iff it
    # passed every group already CLOSED, no closed term of the OPEN group,
    # and every earlier atom of the OPEN term. (Groups and terms are
    # contiguous in perm by construction.)
    closed_pass = jnp.ones((n_rows,), bool)
    seen_or = jnp.zeros((n_rows,), bool)
    term_and = jnp.ones((n_rows,), bool)
    active_before = []
    atom_tiles = jnp.zeros((), jnp.int32)
    work = jnp.zeros((), jnp.float32)
    for k, i in enumerate(perm_host):
        if k > 0 and terms[perm_host[k - 1]] != terms[i]:
            seen_or = jnp.logical_or(seen_or,
                                     tpass[int(terms[perm_host[k - 1]])])
            term_and = jnp.ones((n_rows,), bool)
        if k > 0 and groups[perm_host[k - 1]] != groups[i]:
            closed_pass = jnp.logical_and(closed_pass,
                                          gpass[int(groups[perm_host[k - 1]])])
            seen_or = jnp.zeros((n_rows,), bool)
        pending = closed_pass & ~seen_or & term_and
        alive = jnp.sum(pending).astype(jnp.float32)
        active_before.append(alive)
        pend = np.asarray(pending)
        atom_tiles = atom_tiles + sum(
            bool(pend[j:j + CHAIN_TILE].any())
            for j in range(0, n_rows, CHAIN_TILE))
        work = work + alive * specs.static_cost[i]
        term_and = jnp.logical_and(term_and, outcomes[i])

    # monitor lane: stride-sampled rows, ALL predicates (user order)
    gidx = jnp.arange(n_rows, dtype=jnp.int32)
    sampled = ((gidx + sample_phase) % collect_rate) == 0
    cut = jnp.sum(jnp.logical_and(~outcomes, sampled[None, :]), axis=1)
    group_cut = jnp.sum(jnp.logical_and(~gpass, sampled[None, :]), axis=1)
    term_cut = jnp.sum(jnp.logical_and(~tpass, sampled[None, :]),
                       axis=1).astype(jnp.float32) if specs.is_tree \
        else jnp.zeros((0,), jnp.float32)
    n_monitored = jnp.sum(sampled).astype(jnp.float32)

    return ChainResult(
        mask=mask,
        work_units=work,
        active_before=jnp.stack(active_before),
        cut_counts=cut.astype(jnp.float32),
        n_monitored=n_monitored,
        monitor_cost=specs.static_cost * n_monitored,
        group_cut_counts=group_cut.astype(jnp.float32),
        term_cut_counts=term_cut,
        n_atom_tiles=atom_tiles,
    )


def compact_fixed_ref(columns, mask, capacity: int, fill: float = 0.0):
    """Host-oracle for fixed-capacity compaction: plain boolean index + pad.

    Deliberately the dumbest possible formulation (numpy boolean indexing,
    eager) so a bug in the cumsum-scatter or the two-launch kernel path
    cannot hide in the oracle. Returns (packed f32[C, capacity], n_kept).
    """
    cols = np.asarray(columns)
    m = np.asarray(mask).astype(bool)
    survivors = cols[:, m][:, :capacity]
    out = np.full((cols.shape[0], capacity), fill, cols.dtype)
    out[:, :survivors.shape[1]] = survivors
    return out, survivors.shape[1]
