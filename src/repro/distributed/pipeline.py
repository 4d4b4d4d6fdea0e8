"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

Optional third parallelism dimension beyond DP×TP: the scanned superblock
stack maps naturally onto pipeline stages (stage s owns superblocks
[s·L/P, (s+1)·L/P)).  Implemented with ``shard_map`` over the ``pipe``
axis + ``ppermute`` ring shifts, using the canonical collective-matmul
style schedule:

    for t in 0 .. M + P - 2:          # M microbatches, P stages
        h = stage_fn(h) if active     # every stage computes each tick
        h = ppermute(h, s -> s+1)     # hand activations downstream

Bubble fraction = (P-1)/(M+P-1); the launcher picks M ≥ 4P by default.

This module is deliberately self-contained (it pipelines any per-stage
``fn``), with a numerical-equivalence test against the unpipelined stack in
tests/test_pipeline.py.  The production meshes in launch/mesh.py default to
(pod, data, model) with PP off; ``make_pp_mesh`` builds (pipe, data, model)
variants — on real multi-pod hardware the pipe axis maps onto the
pod/DCN dimension, which is exactly where pipelining (point-to-point,
latency-tolerant) beats data-parallel all-reduces (bandwidth-hungry).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_params,
    x_mb: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "pipe",
) -> jax.Array:
    """Run M microbatches through P pipeline stages.

    Args:
      stage_fn: (params_slice, h) -> h, the per-stage computation.  Called
        under shard_map: inside, tensors are the per-stage local shards.
      stage_params: pytree whose leading axis is the stage count P
        (sharded over ``axis``).
      x_mb: [M, mb, ...] microbatched input, replicated over ``axis``.

    Returns [M, mb, ...] outputs (replicated over ``axis``).
    """
    p = mesh.shape[axis]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(params_local, xs):
        # params_local: [1, ...] stage slice; xs: [M, mb, ...]
        params_stage = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        m = xs.shape[0]
        n_ticks = m + p - 1

        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), axis=0, keepdims=False
            )
            h = jnp.where(stage == 0, jnp.where(t < m, mb_in, buf), buf)
            h = stage_fn(params_stage, h)
            # last stage emits microbatch t-(p-1)
            out_idx = t - (p - 1)
            emit = (stage == p - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h, jnp.clip(out_idx, 0, m - 1), axis=0
                ),
                lambda o: o,
                outs,
            )
            # ring-shift activations downstream (stage s -> s+1)
            perm = [(i, (i + 1) % p) for i in range(p)]
            buf = jax.lax.ppermute(h, axis, perm)
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # outs live on the last stage; broadcast to all stages for out_specs
        outs = jax.lax.psum(
            jnp.where(stage == p - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    return run(stage_params, x_mb)


def split_stages(stacked_params, n_stages: int):
    """[L, ...] scanned params → [P, L/P, ...] per-stage groups."""

    def split(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])

    return jax.tree_util.tree_map(split, stacked_params)
