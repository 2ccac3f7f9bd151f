"""Pallas TPU kernel: one grouped packed matmul over a stack of experts.

``demm_experts_pallas(x, group_sizes, values, indices)`` computes, for the
rows of ``x`` sorted by expert (``group_sizes[e]`` consecutive rows for
expert ``e``, the sizes summing to the rows),

    y[r] = x[r] @ W_{e(r)}ᵀ

where every ``W_e`` is a relaxed N:M packed weight of the stack
``values``/``indices`` ``(E, G, Ne, O)`` (``core.sparsity`` lane-major
layout with a leading expert axis).  It is the mixture-of-experts twin of
``demm_xwT`` (:mod:`repro.kernels.demm_spmm`) and shares its Sᵀ expansion:
each grid step builds the scatter matrix of ``chunk`` groups one 128-lane
slice at a time (``_scatter_matrix``, ``slice_rows``) and accumulates
``x_tile @ Sᵀ`` on the MXU (``accumulate``).

Grid (the megablox pattern, ``jax.experimental.pallas.ops.tpu.megablox``):
the rows are cut into tiles of ``block_b``; a *visit* is one (expert, row
tile) pair that holds at least one of the expert's rows, and the visits
run in row order, so a tile shared by two experts is visited twice in a
row and stays resident.  The group offsets, each visit's expert and row
tile, and the number of visits are scalar-prefetched; each visit's
``index_map`` picks its expert's ``values``/``indices`` blocks, so an
expert with no rows is never visited and its weights are never read.  The
grid's visit axis is static, as long as the most visits any routing can
need (``row tiles + E - 1``).  Every step past the last visit keeps the
block indices of the last live step (its expert, row tile and contraction
block), so the pipeline fetches nothing for it and the kernel computes
nothing: such a step costs only its grid-step overhead.  (megablox bounds
the axis by a traced ``num_active_tiles`` instead, which drops those steps
but passes the bound as the call's first operand, a scalar.)

At the last contraction step a visit writes the rows of its tile that
belong to its expert; a tile's first visit zeroes the rest, which later
visits of the same tile then fill.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.core.sparsity import SparsityConfig
from repro.kernels.demm_spmm import (LANES, SUBLANES, _scatter_matrix,
                                     accumulate, fit_tile, group_chunk)

DEFAULT_BLOCK_B = 128   # sorted rows per tile


def experts_grid(rows: int, values_shape, m: int, block_b: int):
    """(block_b, lanes, chunk, visits) used for a problem: the row tile,
    the output lanes built per Sᵀ slice, groups per step and the length
    of the visit axis."""
    e, g, _n, o = values_shape
    tm = fit_tile(rows, block_b, SUBLANES)
    return (tm, fit_tile(o, LANES, LANES), group_chunk(g, m),
            rows // tm + e - 1)


def _experts_kernel(offsets_ref, experts_ref, tiles_ref, visits_ref, x_ref,
                    values_ref, indices_ref, out_ref, acc_ref, *, m, tm,
                    lanes, interpret):
    v, c = pl.program_id(0), pl.program_id(1)
    live = v < visits_ref[0]

    @pl.when(live & (c == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        x = x_ref[...]

        def lane_slice(j, carry):
            cols = pl.ds(pl.multiple_of(j * lanes, lanes), lanes)
            s = _scatter_matrix(values_ref.at[:, :, cols],
                                indices_ref.at[:, :, cols], m)
            accumulate(acc_ref.at[:, cols], x, s, interpret)
            return carry

        jax.lax.fori_loop(0, acc_ref.shape[1] // lanes, lane_slice, 0)

    @pl.when(live & (c == pl.num_programs(1) - 1))
    def _store():
        e, tile = experts_ref[v], tiles_ref[v]
        first = (v == 0) | (tiles_ref[jnp.maximum(v - 1, 0)] != tile)
        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                   acc_ref.shape, 0)
        mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        kept = jnp.where(first, 0.0, out_ref[...])
        out_ref[...] = jnp.where(mine, acc_ref[...], kept)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "block_b", "interpret"))
def demm_experts_pallas(
    x: jax.Array,             # (R, K) rows sorted by expert
    group_sizes: jax.Array,   # (E,) int32 rows of each expert, sum R
    values: jax.Array,        # (E, G, Ne, O) packed weights
    indices: jax.Array,       # (E, G, Ne, O) int32
    cfg: SparsityConfig,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
) -> jax.Array:
    r, k = x.shape
    e, g, n, o = values.shape
    m = cfg.m
    assert k == g * m, (k, g, m)
    assert n == cfg.n_effective, (n, cfg)
    tm, lanes, chunk, visits = experts_grid(r, values.shape, m, block_b)
    (offsets, experts, tiles), live = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=r, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=e,
        visit_empty_groups=False)
    # steps past the last visit repeat the last live step's blocks (its
    # expert, row tile and final contraction block): no copy, no compute
    last = jnp.maximum(live - 1, 0)
    idx = jnp.minimum(jnp.arange(visits), last)
    experts, tiles = experts[idx], tiles[idx]
    steps = g // chunk

    def block(v, c, nv):
        return jnp.where(v < nv[0], c, steps - 1)

    def rows(v, c, off, ex, ti, nv):
        return ti[v], block(v, c, nv)

    def weights(v, c, off, ex, ti, nv):
        return ex[v], block(v, c, nv), 0, 0

    def out(v, c, off, ex, ti, nv):
        return ti[v], 0

    return pl.pallas_call(
        functools.partial(_experts_kernel, m=m, tm=tm, lanes=lanes,
                          interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(visits, steps),
            in_specs=[
                pl.BlockSpec((tm, chunk * m), rows),
                pl.BlockSpec((None, chunk, n, o), weights),
                pl.BlockSpec((None, chunk, n, o), weights),
            ],
            out_specs=pl.BlockSpec((tm, o), out),
            scratch_shapes=[pltpu.VMEM((tm, o), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r, o), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="demm_experts",
    )(offsets, experts, tiles, jnp.reshape(live, (1,)).astype(jnp.int32),
      x, values, indices)
