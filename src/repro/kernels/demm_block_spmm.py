"""Pallas TPU kernel: scalar-prefetch block-gather DeMM spmm.

This is the *decoupled-memory* half of the DeMM adaptation (DESIGN.md §2,
row (b)): the column indices of the sparse matrix drive **which blocks of B
are fetched from HBM at all**.  The packed format is two-level:

  level 1 — per row-block, the list of *active* M-groups (groups where at
            least one row of the block has a non-zero).  Groups absent from
            the list are never DMA'd and never touch the MXU: the address
            stream gates the memory system exactly like DeMM's read ports
            gate its SRAM.
  level 2 — within each active group, the usual relaxed N:M packed
            {values, indices} (consumed by the same scatter→MXU body as
            ``demm_spmm``).

The active-group ids are passed through ``PrefetchScalarGridSpec`` so the
BlockSpec ``index_map`` of x reads them *before* the grid step runs — i.e.
the DMA engine is addressed by the sparse metadata, which is the paper's
decoupling, relocated to the HBM→VMEM boundary.  The kernel computes the
serving orientation y = x @ Wᵀ directly; x is viewed group-major
``(G, Bx, M)`` so one active group of x is one legal TPU block for every M,
and the lane-major packed slot ``(Ne, block_r)`` is consumed as stored.

Padded slots (row blocks with fewer than ``a_max`` active groups) point at
group 0 with all-zero values: they cost a redundant (but cheap, VMEM-hit)
step and contribute exactly 0.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import DEFAULT_BLOCK_R, SparsityConfig, pack_block
from repro.kernels.demm_spmm import (SUBLANES, _scatter_matrix,
                                     accumulate, fit_tile)

DEFAULT_BLOCK_C = 256


def pack_block_sparse(
    a: np.ndarray, cfg: SparsityConfig, block_r: int = DEFAULT_BLOCK_R,
    a_max: int | None = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side two-level packing — a numpy adapter over
    :func:`repro.core.sparsity.pack_block` (the single home for the
    active-group / level-2 selection semantics).

    Returns (active_groups (RB, A_max) int32,
             values (RB, A_max, Ne, block_r),
             indices (RB, A_max, Ne, block_r),
             a_max).
    """
    pw = pack_block(jnp.asarray(a), cfg, block_r=block_r, a_max=a_max)
    return (np.asarray(pw.active_groups), np.asarray(pw.values),
            np.asarray(pw.indices), pw.block_geom[1])


def _block_kernel(ag_ref, x_ref, values_ref, indices_ref, out_ref, *, m,
                  interpret):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = _scatter_matrix(values_ref, indices_ref, m)          # (M, block_r)
    accumulate(out_ref, x_ref[...], s, interpret)


def group_major(x: jax.Array, m: int) -> jax.Array:
    """(Bx, K) activations as (G, Bx, M): one M-group of x per leading
    index, so the active-group address picks a whole ``(Bx, M)`` block (a
    legal TPU block for any M).  Activations only — the packed weight is
    never reshaped per call."""
    bx, k = x.shape
    return jnp.swapaxes(x.reshape(bx, k // m, m), 0, 1)


def block_specs(values_shape, block_b: int, m: int):
    """BlockSpecs shared by the float and int8 block kernels: the x group
    addressed by the prefetched active-group id, then the packed slot."""
    _rb, _a_max, n, block_r = values_shape
    return [
        # The decoupled read port: x's DMA address comes from the
        # prefetched active-group id, not from the grid position.
        pl.BlockSpec((None, block_b, m), lambda b, i, j, ag: (ag[i, j], b, 0)),
        pl.BlockSpec((None, 1, n, block_r), lambda b, i, j, ag: (i, j, 0, 0)),
        pl.BlockSpec((None, 1, n, block_r), lambda b, i, j, ag: (i, j, 0, 0)),
    ]


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "cd_block", "interpret"),
)
def demm_block_xwT_pallas(
    x: jax.Array,              # (Bx, K) dense activations
    values: jax.Array,         # (RB, A_max, Ne, block_r)
    indices: jax.Array,        # (RB, A_max, Ne, block_r)
    active_groups: jax.Array,  # (RB, A_max) int32
    cfg: SparsityConfig,
    *,
    cd_block: int = DEFAULT_BLOCK_C,
    interpret: bool = False,
) -> jax.Array:
    """y = x @ W_blockᵀ, (Bx, R).  ``cd_block`` tiles the activation rows
    (the paper's B columns)."""
    bx, k = x.shape
    rb, a_max, n, block_r = values.shape
    m = cfg.m
    assert k % m == 0 and n == cfg.n_effective, (x.shape, values.shape, cfg)
    block_b = fit_tile(bx, cd_block, SUBLANES)
    return pl.pallas_call(
        functools.partial(_block_kernel, m=m, interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bx // block_b, rb, a_max),
            in_specs=block_specs(values.shape, block_b, m),
            out_specs=pl.BlockSpec((block_b, block_r),
                                   lambda b, i, j, ag: (b, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((bx, rb * block_r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="demm_block_spmm",
    )(active_groups, group_major(x, m), values, indices)


def demm_block_spmm_pallas(
    active_groups: jax.Array,  # (RB, A_max) int32
    values: jax.Array,         # (RB, A_max, Ne, block_r)
    indices: jax.Array,        # (RB, A_max, Ne, block_r)
    b: jax.Array,              # (K, Cd)
    cfg: SparsityConfig,
    *,
    r: int,
    cd_block: int = DEFAULT_BLOCK_C,
    interpret: bool = False,
) -> jax.Array:
    """C = A_block @ B (paper orientation) as ``(Bᵀ @ A_blockᵀ)ᵀ``."""
    assert values.shape[0] * values.shape[-1] == r, (values.shape, r)
    return demm_block_xwT_pallas(b.T, values, indices, active_groups, cfg,
                                 cd_block=cd_block, interpret=interpret).T
