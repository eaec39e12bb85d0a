"""GPipe-style pipeline parallelism over a mesh axis (library feature).

Stages live on consecutive devices of the `pipe` axis; microbatches flow
through a `lax.ppermute` ring.  Forward runs the classic GPipe schedule in
M + P - 1 ticks inside one shard_map; the backward schedule falls out of
reverse-mode AD through the same program (grad-of-ppermute is the opposite
permutation), so `jax.grad` of a pipelined loss is itself pipelined.

This is the PP building block (DESIGN.md Sec. 6); the assigned-arch
configs default to DP+TP+FSDP which covers every dry-run cell, so PP is
exercised by unit tests (tests/test_pipeline_parallel.py) rather than the
40-cell table.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, axis: str, stage_fn: Callable,
                   stage_params, microbatches):
    """Run `stage_fn` as a P-stage pipeline.

    stage_params: pytree with leading dim P (one slice per stage), sharded
                  over `axis`.
    microbatches: (M, mb, ...) array; every stage maps mb-sized activations
                  to same-shaped activations (homogeneous pipeline).
    Returns (M, mb, ...) outputs (as produced by the last stage).
    """
    n_stages = mesh.shape[axis]
    M = microbatches.shape[0]

    def shard_body(params_l, mb_l):
        # params_l: (1, ...) this stage's params; mb_l: (M, mb, ...) full
        # microbatch stream is replicated; only stage 0 consumes it.
        params_me = jax.tree.map(lambda a: a[0], params_l)
        stage = jax.lax.axis_index(axis)
        right = [(i, i + 1) for i in range(n_stages - 1)]

        mb_shape = mb_l.shape[1:]
        outputs = jnp.zeros((M,) + mb_shape, mb_l.dtype)
        carry = jnp.zeros(mb_shape, mb_l.dtype)

        def tick(t, state):
            outputs, carry = state
            # receive activations from the left neighbour
            recv = jax.lax.ppermute(carry, axis, right)
            x_in = jnp.where(stage == 0,
                             mb_l[jnp.clip(t, 0, M - 1)], recv)
            y = stage_fn(params_me, x_in)
            # my microbatch index at tick t is t - stage
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < M)
            carry = jnp.where(active, y, carry)
            is_last = stage == n_stages - 1
            outputs = jax.lax.cond(
                active & is_last,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (jnp.clip(mb_idx, 0, M - 1),)
                    + (0,) * len(mb_shape)),
                lambda o: o, outputs)
            return outputs, carry

        outputs, _ = jax.lax.fori_loop(0, M + n_stages - 1, tick,
                                       (outputs, carry))
        # every shard returns the same outputs tensor; only the last
        # stage's is non-zero -- sum-reduce to broadcast it.
        return jax.lax.psum(outputs, axis)[None]

    # Library entry point: callers jit pipeline_apply as a whole, so the
    # shard_map below traces inside the caller's cache entry.
    # repro-lint: disable=jit-cache-hygiene
    out = shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis), check_vma=False)(stage_params, microbatches)
    return out[0]


def stack_stages(layer_params_list):
    """[per-stage pytrees] -> stacked pytree with leading stage dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params_list)


__all__ = ["pipeline_apply", "stack_stages"]
