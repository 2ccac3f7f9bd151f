"""Pallas TPU kernels: int8 quantized DeMM matmuls (w8a16).

Quantized twins of ``demm_spmm.demm_xwT_pallas`` and
``demm_block_spmm.demm_block_xwT_pallas`` for weights produced by
``repro.quant.quantize_packed``: the packed ``values`` stream is int8 (a
further 2–4× cut of the already-compressed weight HBM traffic on top of the
sparsity win) and dequantization happens **in-register**, after the DMA
stage — only quantized bytes ever leave HBM.

w8a16 scheme: weights int8, activations keep their serving dtype
(bf16/f32).  Inside the kernel the int8 values are cast to fp32 while
building the scatter matrix Sᵀ, and the symmetric scales fold
into each packed ``(1, block_o)`` row before the select, so the fused body
costs one extra VPU multiply per packed row:

  * xwT:   scales are per output row ``(O,)`` → every packed row of the
    tile scales by the same ``(1, block_o)`` lane row (passed as a
    ``(1, O)`` operand).  Per-group scales ``(G, O)`` (``repro.quant``
    granularity ``"per_group"``) cost the same: group ``g`` of the chunk
    reads row ``g`` of the scales strip instead.
  * block: scales are per (row-block, group, row) ``(RB, A_max, block_r)``
    → grid step ``j`` scales its slot by row ``j`` of the row block's
    scales, and the level-1 active-group prefetch (the decoupled address
    stream) is untouched.

Accumulation stays fp32, matching the float kernels' oracle tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import SparsityConfig
from repro.kernels.demm_block_spmm import (DEFAULT_BLOCK_C, block_specs,
                                           group_major)
from repro.kernels.demm_spmm import (
    SUBLANES,
    DEFAULT_BLOCK_B,
    DEFAULT_BLOCK_R,
    _scatter_matrix,
    accumulate,
    fit_tile,
    xwT_grid,
)


# ---------------------------------------------------------------------------
# y = x @ W_q8ᵀ (serving orientation)
# ---------------------------------------------------------------------------

def _xwT_q8_kernel(x_ref, values_ref, indices_ref, scales_ref, out_ref, *,
                   m, per_group, interpret):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # int8 → fp32 inside the scatter expansion (in-register dequant), each
    # packed row scaled by its group's (or its output row's) scale.
    chunk = values_ref.shape[0]
    if per_group:
        rows = [scales_ref[pl.ds(c * chunk + g, 1), :] for g in range(chunk)]
    else:
        rows = [scales_ref[...]] * chunk
    s = _scatter_matrix(values_ref, indices_ref, m, scales=rows)
    accumulate(out_ref, x_ref[...], s, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_b", "block_o", "interpret"),
)
def demm_xwT_q8_pallas(
    x: jax.Array,           # (Bx, K) dense activations
    values: jax.Array,      # (G, Ne, O) int8 packed weight
    indices: jax.Array,     # (G, Ne, O) int32
    scales: jax.Array,      # (O,) per-row or (G, O) per-group f32 scales
    cfg: SparsityConfig,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_o: int = DEFAULT_BLOCK_R,
    interpret: bool = False,
) -> jax.Array:
    bx, k = x.shape
    g, n, o = values.shape
    m = cfg.m
    assert k == g * m, (k, g, m)
    assert n == cfg.n_effective, (n, cfg)
    assert scales.shape in ((o,), (g, o)), (scales.shape, values.shape)
    per_group = scales.ndim == 2
    block_b, block_o, chunk = xwT_grid(x.shape, values.shape, m, block_b,
                                       block_o)
    # Per-row scales ride as one (1, O) row; per-group scales as the whole
    # (G, O) column strip of the output tile, indexed by the chunk's groups.
    if per_group:
        scales_spec = pl.BlockSpec((g, block_o), lambda i, j, c: (0, j))
    else:
        scales = scales.reshape(1, o)
        scales_spec = pl.BlockSpec((1, block_o), lambda i, j, c: (0, j))
    return pl.pallas_call(
        functools.partial(_xwT_q8_kernel, m=m, per_group=per_group,
                          interpret=interpret),
        grid=(bx // block_b, o // block_o, g // chunk),
        in_specs=[
            pl.BlockSpec((block_b, chunk * m), lambda i, j, c: (i, c)),
            pl.BlockSpec((chunk, n, block_o), lambda i, j, c: (c, 0, j)),
            pl.BlockSpec((chunk, n, block_o), lambda i, j, c: (c, 0, j)),
            scales_spec,
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bx, o), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="demm_xwT_q8",
    )(x, values, indices, scales)


# ---------------------------------------------------------------------------
# y = x @ W_q8_blockᵀ (two-level layout, scalar-prefetch address stream)
# ---------------------------------------------------------------------------

def _block_q8_kernel(ag_ref, x_ref, values_ref, indices_ref, scales_ref,
                     out_ref, *, m, interpret):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = _scatter_matrix(values_ref, indices_ref, m,
                        scales=[scales_ref[pl.ds(j, 1), :]])  # (M, block_r)
    accumulate(out_ref, x_ref[...], s, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "cd_block", "interpret"),
)
def demm_block_xwT_q8_pallas(
    x: jax.Array,              # (Bx, K) dense activations
    values: jax.Array,         # (RB, A_max, Ne, block_r) int8
    indices: jax.Array,        # (RB, A_max, Ne, block_r)
    active_groups: jax.Array,  # (RB, A_max) int32
    scales: jax.Array,         # (RB, A_max, block_r) float32
    cfg: SparsityConfig,
    *,
    cd_block: int = DEFAULT_BLOCK_C,
    interpret: bool = False,
) -> jax.Array:
    bx, k = x.shape
    rb, a_max, n, block_r = values.shape
    m = cfg.m
    assert k % m == 0 and n == cfg.n_effective, (x.shape, values.shape, cfg)
    assert scales.shape == (rb, a_max, block_r), (scales.shape, values.shape)
    block_b = fit_tile(bx, cd_block, SUBLANES)
    return pl.pallas_call(
        functools.partial(_block_q8_kernel, m=m, interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bx // block_b, rb, a_max),
            in_specs=block_specs(values.shape, block_b, m) + [
                # All of a row block's slot scales; step j reads row j.
                pl.BlockSpec((None, a_max, block_r),
                             lambda b, i, j, ag: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_b, block_r),
                                   lambda b, i, j, ag: (b, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((bx, rb * block_r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="demm_block_spmm_q8",
    )(active_groups, group_major(x, m), values, indices, scales)
