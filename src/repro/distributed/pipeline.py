"""Pipeline parallelism via shard_map + collective_permute.

GPipe-style microbatch pipelining over a ``stage`` mesh axis: the layer
stack is split into S stages (stage s holds layers [s·L/S, (s+1)·L/S));
microbatches stream through with activations moved stage→stage by
``lax.ppermute``.  The steady-state loop is a ``lax.scan`` over
(num_microbatches + S − 1) ticks — the classic pipelined schedule, bubble
fraction (S−1)/(M+S−1).

This is an opt-in alternative to the default DP×TP layout (DESIGN.md §5);
unit tests validate numerical equality with the unpipelined forward on a
small host mesh.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(block_fn: Callable, params_stacked: Any,
                     x_microbatches: jnp.ndarray, mesh: Mesh,
                     stage_axis: str = "stage") -> jnp.ndarray:
    """Run ``block_fn(params_layer, x) -> x`` over a stage-sharded stack.

    params_stacked: pytree with leading layer axis L (L % S == 0), sharded
      so each stage holds its L/S layers.
    x_microbatches: (M, mb, ...) microbatched input, replicated across
      stages (stage 0 consumes; results exit from the last stage).
    Returns (M, mb, ...) outputs.
    """
    S = mesh.shape[stage_axis]

    def stage_body(params_local, xs):
        """Runs on ONE stage. params_local: (L/S, ...); xs: (M, mb, ...)."""
        stage_id = lax.axis_index(stage_axis)
        M = xs.shape[0]

        def run_stage(x):
            def layer(h, p):
                return block_fn(p, h), None
            h, _ = lax.scan(layer, x, params_local)
            return h

        # schedule: tick t processes microbatch (t - stage_id) at this stage
        state = jnp.zeros_like(xs[0])
        outputs = jnp.zeros_like(xs)
        num_ticks = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outputs = carry
            mb_idx = t - stage_id
            # stage 0 ingests a fresh microbatch at ticks [0, M)
            fresh = xs[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(stage_id == 0, fresh, state)
            active = (mb_idx >= 0) & (mb_idx < M)
            out = run_stage(inp)
            out = jnp.where(active, out, state)
            # last stage commits finished microbatches
            outputs = lax.cond(
                (stage_id == S - 1) & active,
                lambda o: o.at[jnp.clip(mb_idx, 0, M - 1)].set(out),
                lambda o: o, outputs)
            # rotate activations to the next stage
            state = lax.ppermute(out, stage_axis, perm)
            return (state, outputs), None

        (_, outputs), _ = lax.scan(tick, (state, outputs),
                                   jnp.arange(num_ticks))
        # only the last stage holds real outputs; broadcast them
        outputs = lax.psum(
            jnp.where(stage_id == S - 1, outputs, jnp.zeros_like(outputs)),
            stage_axis)
        return outputs

    pspec = jax.tree_util.tree_map(
        lambda l: P(stage_axis, *([None] * (l.ndim - 1))), params_stacked)
    return jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False)(params_stacked, x_microbatches)
