"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The layer stack is split into S stages (leading dim of ``stage_params``);
microbatches stream through the ring with ``jax.lax.ppermute``. The schedule
is the classic GPipe fill-run-drain: M + S - 1 ticks, bubble fraction
(S - 1)/(M + S - 1). Differentiable end-to-end (ppermute transposes to the
reverse permute), so a full train step backprops through the pipeline.

This is feature-flagged (not part of the default dry-run mesh, DESIGN.md §5)
and validated on small meshes in tests/test_distributed.py against the
sequential stack — forward and gradients.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,           # (stage_params, x_mb) -> y_mb
    stage_params,                 # pytree, leading dim = num_stages
    x: jax.Array,                 # (global_batch, ...)
    *,
    mesh: Mesh,
    axis: str = "pipe",
    num_microbatches: int,
) -> jax.Array:
    S = mesh.shape[axis]
    M = num_microbatches
    gb = x.shape[0]
    assert gb % M == 0, (gb, M)
    mb = gb // M
    x_mb = x.reshape(M, mb, *x.shape[1:])

    def body(params_stage, xs):
        # params_stage leaves arrive as (1, ...) — shard_map keeps the sharded
        # axis with local size 1; drop it to get this stage's params.
        params_stage = jax.tree.map(lambda p: p[0], params_stage)
        # xs: (M, mb, ...) microbatches (replicated over the pipe axis)
        stage = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % S) for i in range(S)]
        zero = jnp.zeros_like(xs[0])

        def tick(carry, t):
            state, out = carry
            inj = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, M - 1), axis=0, keepdims=False)
            inp = jnp.where(stage == 0, inj, state)
            y = stage_fn(params_stage, inp)
            nxt = jax.lax.ppermute(y, axis, perm)
            is_out = (stage == S - 1) & (t >= S - 1)
            slot = jnp.maximum(t - (S - 1), 0)
            cur = jax.lax.dynamic_index_in_dim(out, slot, 0, keepdims=False)
            new = jnp.where(is_out, y, cur)
            out = jax.lax.dynamic_update_index_in_dim(out, new, slot, 0)
            return (nxt, out), None

        out0 = jnp.zeros_like(xs)
        (state, out), _ = jax.lax.scan(
            tick, (zero, out0), jnp.arange(M + S - 1))
        # broadcast the last stage's outputs to every stage
        mask = (stage == S - 1).astype(out.dtype)
        out = jax.lax.psum(out * mask, axis)
        return out

    stage_spec = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(stage_spec, P()), out_specs=P(),
                       check_vma=False)
    y_mb = fn(stage_params, x_mb)
    return y_mb.reshape(gb, *y_mb.shape[2:])


def split_stages(stacked_params, num_stages: int):
    """Reshape a (L, ...) layer-stacked param tree into (S, L/S, ...)."""
    def one(p):
        L = p.shape[0]
        assert L % num_stages == 0, (L, num_stages)
        return p.reshape(num_stages, L // num_stages, *p.shape[1:])
    return jax.tree.map(one, stacked_params)


def make_stage_fn(block_fn: Callable):
    """Wrap a per-layer block fn into a stage fn scanning its sub-stack."""
    def stage_fn(stage_params, x):
        def body(h, p_l):
            return block_fn(p_l, h), None
        y, _ = jax.lax.scan(body, x, stage_params)
        return y
    return stage_fn
