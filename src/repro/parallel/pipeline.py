"""Pipeline parallelism: GPipe-style microbatch schedule over a ``stage``
mesh axis using ``shard_map`` + ``collective_permute``.

The production configs use DP×TP×EP(×SP) — the TPU-idiomatic choice — but
the framework supports PP as a first-class module for topologies where a
stage axis is preferable (e.g. spanning slow inter-pod links). The schedule
is the classic fill-drain: with S stages and M microbatches, bubble fraction
= (S-1)/(M+S-1); each tick every stage runs its block on its current
microbatch, then activations shift stage i → i+1 with one
``collective_permute`` (point-to-point, overlappable).

``pipeline_apply`` is deliberately model-agnostic: it takes a per-stage
``block_fn(stage_params, x) -> x`` and handles scheduling/communication, so
any of the 10 archs' layer stacks can be cut into stages. Correctness is
asserted against the unpipelined reference in tests/test_pipeline_pp.py (4
CPU devices, 2 stages).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_apply(block_fn, stage_params, x_microbatches, *, mesh,
                   stage_axis: str = "stage"):
    """Run a stage-sharded stack over microbatches.

    stage_params: pytree whose leaves have a leading ``n_stages`` axis,
      sharded over ``stage_axis``.
    x_microbatches: [M, mb, ...] activations (replicated over stages).
    Returns [M, mb, ...] outputs of the final stage (replicated).
    """
    n_stages = mesh.shape[stage_axis]

    def per_stage(params, xs):
        # params: this stage's block params (leading axis stripped by shard_map)
        params = jax.tree.map(lambda a: a[0], params)
        m = xs.shape[0]
        ticks = m + n_stages - 1
        stage_id = jax.lax.axis_index(stage_axis)

        def tick(carry, t):
            buf, outputs = carry
            # stage s works on microbatch (t - s) when 0 <= t-s < m
            mb_idx = t - stage_id
            active = jnp.logical_and(mb_idx >= 0, mb_idx < m)
            # stage 0 ingests from xs; others use the shifted-in buffer
            x_in = jnp.where(stage_id == 0,
                             xs[jnp.clip(mb_idx, 0, m - 1)], buf)
            y = block_fn(params, x_in)
            y = jnp.where(active, y, buf)
            # shift activations one stage forward (ring permute)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            shifted = jax.lax.ppermute(y, stage_axis, perm)
            # last stage commits its finished microbatch
            out_idx = t - (n_stages - 1)
            commit = jnp.logical_and(stage_id == n_stages - 1,
                                     jnp.logical_and(out_idx >= 0,
                                                     out_idx < m))
            outputs = jnp.where(
                commit,
                outputs.at[jnp.clip(out_idx, 0, m - 1)].set(y),
                outputs)
            return (shifted, outputs), None

        # initial carries must be marked stage-varying (they become so after
        # one tick: stage_id enters the dataflow)
        buf0 = jax.lax.pcast(jnp.zeros_like(xs[0]), ("stage",), to="varying")
        out0 = jax.lax.pcast(jnp.zeros_like(xs), ("stage",), to="varying")
        (_, outputs), _ = jax.lax.scan(tick, (buf0, out0),
                                       jnp.arange(ticks))
        # replicate final-stage outputs to every stage
        outputs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outputs, 0.0), stage_axis)
        return outputs

    spec_params = jax.tree.map(lambda _: P(stage_axis), stage_params)
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_params, P()), out_specs=P(),
    )(stage_params, x_microbatches)
