"""GPipe-style pipeline parallelism over a 'pipe' mesh axis.

The production dry-run mesh uses DP×TP (+pod) as specified in the brief;
pipelining is provided as an optional composable axis for deployments where
layer counts outgrow TP (e.g. 1000+-node fleets): stages are stacked layer
groups sharded over 'pipe', microbatches stream through a
``collective_permute`` ring with the classic (num_microbatches + num_stages
- 1)-tick schedule.  Bubble fraction = (S-1)/(M+S-1).

``pipeline_apply`` is jit-able, differentiable (the permutes are linear),
and mesh-agnostic; tests/test_pipeline.py checks exact equivalence with the
sequential composition on an 8-device host mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,          # (stage_params, x_mb) -> y_mb
    stage_params,                # pytree stacked on axis 0 = num_stages
    x: jax.Array,                # (num_microbatches, mb, ...)
    mesh: Mesh,
    *,
    axis: str = "pipe",
) -> jax.Array:
    """Returns stage_{S-1}(...stage_0(x)) with shapes preserved."""
    num_stages = mesh.shape[axis]
    num_mb = x.shape[0]
    ticks = num_mb + num_stages - 1

    def local_fn(params_local, x_all):
        # params_local: this rank's stage (leading axis 1) — squeeze it.
        params_local = jax.tree.map(lambda a: a[0], params_local)
        rank = jax.lax.axis_index(axis)
        # jax.lax.axis_size only exists on newer jax; psum(1) is equivalent.
        n = (jax.lax.axis_size(axis) if hasattr(jax.lax, "axis_size")
             else jax.lax.psum(1, axis))
        mb_shape = x_all.shape[1:]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (zeros once drained)
            mb_idx = jnp.clip(t, 0, num_mb - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_all, mb_idx, 0,
                                                 keepdims=False)
            inp = jnp.where(rank == 0, fresh, state)
            out = stage_fn(params_local, inp)
            # last stage banks its result for microbatch t - (n - 1)
            out_idx = jnp.clip(t - (n - 1), 0, num_mb - 1)
            take = (rank == n - 1) & (t >= n - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(take, out,
                          jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                                       keepdims=False)),
                out_idx, 0)
            # ring-shift activations to the next stage
            state = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n) for i in range(n)])
            return (state, outputs), None

        state0 = jnp.zeros(mb_shape, x_all.dtype)
        outputs0 = jnp.zeros((num_mb,) + mb_shape, x_all.dtype)
        (_, outputs), _ = jax.lax.scan(tick, (state0, outputs0),
                                       jnp.arange(ticks))
        # broadcast the last rank's outputs to everyone (replicated result);
        # ppermute is a strict permutation, so mask + psum instead
        outputs = jax.lax.psum(
            jnp.where(rank == n - 1, outputs, jnp.zeros_like(outputs)), axis)
        return outputs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def pipeline_apply_stateful(
    stage_fn: Callable,          # (params, state, x_mb, aux_mb, mb_idx)
                                 #   -> (y_mb, new_state)
    stage_params,                # pytree stacked on axis 0 = num_stages
    stage_state,                 # pytree stacked on axis 0 = num_stages
    x: jax.Array,                # (num_microbatches, mb, ...)
    mesh: Mesh,
    *,
    axis: str = "pipe",
    aux=None,                    # pytree, leaves (num_microbatches, ...)
):
    """:func:`pipeline_apply` for stage functions that carry *state* — the
    microbatched decode step, where each stage owns the KV caches of its
    layer group and must thread their updates out of the pipeline.

    Each stage applies each microbatch exactly once in the classic schedule
    (stage ``s`` sees microbatch ``m`` at tick ``m + s``); on warm-up/drain
    ticks where a stage holds no live microbatch the ``stage_fn`` still runs
    (SPMD — every rank executes every tick) but its state update is
    discarded with a validity mask, so bubble ticks cannot corrupt caches.

    ``aux`` carries per-microbatch side inputs every stage needs at its own
    schedule offset (e.g. decode positions): leaves are indexed with the
    stage's current microbatch id and handed to ``stage_fn`` as ``aux_mb``.

    Returns ``(y, new_stage_state)`` with ``y.shape == x.shape`` and
    ``new_stage_state`` matching ``stage_state``.
    """
    num_stages = mesh.shape[axis]
    num_mb = x.shape[0]
    ticks = num_mb + num_stages - 1
    aux = {} if aux is None else aux

    def local_fn(params_local, state_local, x_all, aux_all):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        st0 = jax.tree.map(lambda a: a[0], state_local)
        rank = jax.lax.axis_index(axis)
        n = (jax.lax.axis_size(axis) if hasattr(jax.lax, "axis_size")
             else jax.lax.psum(1, axis))
        mb_shape = x_all.shape[1:]

        def tick(carry, t):
            ring, st, outputs = carry
            # stage 0 ingests microbatch t; later stages take the ring
            fresh = jax.lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, num_mb - 1), 0, keepdims=False)
            inp = jnp.where(rank == 0, fresh, ring)
            # this stage's live microbatch at tick t (clamped on bubbles)
            my_mb = jnp.clip(t - rank, 0, num_mb - 1)
            valid = (t >= rank) & (t - rank < num_mb)
            aux_mb = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, my_mb, 0,
                                                       keepdims=False),
                aux_all)
            out, st_new = stage_fn(params_local, st, inp, aux_mb, my_mb)
            st = jax.tree.map(lambda new, old: jnp.where(valid, new, old),
                              st_new, st)
            # last stage banks its result for microbatch t - (n - 1)
            out_idx = jnp.clip(t - (n - 1), 0, num_mb - 1)
            take = (rank == n - 1) & (t >= n - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(take, out,
                          jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                                       keepdims=False)),
                out_idx, 0)
            ring = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n) for i in range(n)])
            return (ring, st, outputs), None

        ring0 = jnp.zeros(mb_shape, x_all.dtype)
        outputs0 = jnp.zeros((num_mb,) + mb_shape, x_all.dtype)
        (_, st, outputs), _ = jax.lax.scan(
            tick, (ring0, st0, outputs0), jnp.arange(ticks))
        outputs = jax.lax.psum(
            jnp.where(rank == n - 1, outputs, jnp.zeros_like(outputs)), axis)
        # restore the leading (local) stage axis for the P(axis) out_spec
        return outputs, jax.tree.map(lambda a: a[None], st)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    sspec = jax.tree.map(lambda _: P(axis), stage_state)
    aspec = jax.tree.map(lambda _: P(), aux)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(pspec, sspec, P(), aspec), out_specs=(P(), sspec),
        check_vma=False,
    )(stage_params, stage_state, x, aux)
