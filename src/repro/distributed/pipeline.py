"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The layer stack is split into ``S`` stages along a "pipe" mesh axis; a
microbatched schedule streams activations stage-to-stage with
``ppermute``.  Running ``M + S - 1`` ticks drains the pipe; bubble fraction
is (S-1)/(M+S-1).

This is the optional PP dimension of the framework (DESIGN.md §4): the
production mesh keeps DP x TP because scan-over-layers + FSDP covers the
assigned models, but long-skinny models (94-layer qwen3) can trade the
"data" axis for "pipe" with this module.  Correctness is tested on 8
virtual devices in tests/test_distributed_multidev.py.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_fn: Callable, num_microbatches: int,
                   axis_name: str = "pipe"):
    """Build a pipelined apply: y = stage_{S-1}(...stage_0(x)).

    stage_fn(stage_params, x_mb) -> y_mb applies ONE stage to ONE microbatch
    (same activation shape in/out).

    The returned callable takes
      stage_params: pytree with leading dim S (sharded over the pipe axis),
      x: (M, mb, ...) microbatched input (replicated),
    and returns y: (M, mb, ...) (replicated output of the last stage).
    """
    S = mesh.shape[axis_name]
    M = num_microbatches

    def per_stage(stage_params, x):
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        idx = jax.lax.axis_index(axis_name)
        mb_shape = x.shape[1:]
        state = jnp.zeros(mb_shape, x.dtype)
        outputs = jnp.zeros_like(x)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outputs = carry
            inject = x[jnp.minimum(t, M - 1)]
            state = jnp.where(idx == 0, inject, state)
            state = stage_fn(stage_params, state)
            slot = jnp.clip(t - (S - 1), 0, M - 1)
            take = (idx == S - 1) & (t >= S - 1)
            outputs = jnp.where(
                take,
                jax.lax.dynamic_update_index_in_dim(outputs, state, slot, 0),
                outputs)
            state = jax.lax.ppermute(state, axis_name, perm)
            return (state, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(M + S - 1))
        return outputs[None]  # (1, M, mb...) -> stacked over stages

    stacked = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(axis_name), check_vma=False)

    def apply(stage_params, x):
        out = stacked(stage_params, x)      # (S, M, mb...)
        return out[-1]                      # last stage holds the results

    return apply


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
