"""Pallas TPU kernel: fused decompress→MXU DeMM spmm.

TPU adaptation of the DeMM engine (DESIGN.md §2).  The packed sparse matrix
(values + column indices) is the only representation of W that leaves HBM.
Inside the kernel — i.e. *after* the DMA stage, in VMEM — the N
``{value, col_idx}`` pairs of each M-group are expanded into the transposed
scatter matrix Sᵀ (M rows per group, one lane per output row: the software
analogue of DeMM's N read ports selecting N rows of the pre-loaded x block),
and the MXU performs x_chunk @ Sᵀ, fusing the paper's multiplier array and
adder trees into the systolic matmul.

Two entry points:

* ``demm_xwT_pallas(x, values, indices)``    — y = x @ W_sparseᵀ
  (the serving hot path: dense activations × packed weightᵀ).
* ``demm_spmm_pallas(values, indices, b)``   — C = A_sparse @ B
  (the paper's orientation), evaluated as ``(Bᵀ @ Aᵀ)ᵀ`` by the same kernel.

Layout (``core.sparsity``): values/indices are lane-major ``(G, Ne, O)`` —
the output axis O is minor, so a ``(chunk, Ne, block_o)`` block is a legal
TPU tile for every pattern (Ne equals the full array dim, block_o is a
multiple of 128 or all of O) and the stream is DMA'd exactly as stored.
Each grid step consumes ``chunk`` consecutive groups, ``chunk * M`` columns
of x: the smallest chunk whose x block spans a multiple of 128 lanes, so
fine patterns (2:4, 8:16) feed the MXU a full-lane contraction.  The output
block is revisited across chunks and accumulated in fp32.

Sᵀ is built one sublane-aligned slice at a time (:func:`slice_rows`):
``lcm(M, 8)`` rows covering whole groups, each from only the groups it
covers, so a step of ``chunk`` groups runs N select passes per group, not
``chunk·N`` over all ``chunk·M`` rows.

VMEM budget (block_o = 128, bf16 x, f32 values): x block Bt×chunk·M×2,
values + indices chunk×Ne×128×(4+4), Sᵀ chunk·M×128×4 (64 KiB at 8:128,
chunk 1; 320 KiB at 5:80, chunk 8), out block Bt×128×4 — far inside the
scoped VMEM limit with double buffering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import SparsityConfig

# Direct-call defaults.  Production dispatch picks per-problem tiles via
# repro.tune (backend="auto").
DEFAULT_BLOCK_R = 128   # output rows (lanes) per tile
DEFAULT_BLOCK_C = 256   # dense output columns per tile (paper orientation)
DEFAULT_BLOCK_B = 128   # activation rows per tile (xwT orientation)

LANES = 128
SUBLANES = 8


def fit_tile(dim: int, want: int, align: int) -> int:
    """Largest tile <= ``want`` that divides ``dim`` and is a multiple of
    ``align``; the whole ``dim`` when none exists (a block equal to the
    array dim is always a legal TPU block)."""
    if dim <= want:
        return dim
    for t in range(want - want % align, 0, -align):
        if dim % t == 0:
            return t
    return dim


def group_chunk(groups: int, m: int) -> int:
    """Groups per grid step: the smallest divisor of ``groups`` whose
    ``chunk * m`` columns fill whole 128-lane tiles (all groups if none)."""
    for d in range(1, groups + 1):
        if groups % d == 0 and (d * m) % LANES == 0:
            return d
    return groups


def slice_rows(chunk_rows: int, m: int) -> int:
    """Rows of Sᵀ built at a time: ``lcm(M, 8)``, the fewest whole groups
    that fill whole f32 sublane tiles, or all ``chunk_rows`` where that
    does not divide them (a step shorter than one such slice)."""
    h = math.lcm(m, SUBLANES)
    return h if chunk_rows % h == 0 else chunk_rows


def count_scatter_slices(op: str, chunk: int, m: int):
    """Trace-time audit of the Sᵀ expansion of one packed ``pallas_call``
    of ``op`` whose grid steps hold ``chunk`` groups:
    ``kernel_scatter_slices_total{op, slice_rows, chunk_rows}`` on the
    default registry, beside ``kernel_dispatch_total``.  ``slice_rows <
    chunk_rows`` marks a matmul built in several slices."""
    from repro import obs

    rows = chunk * m
    obs.metrics().counter(
        "kernel_scatter_slices_total",
        help="packed kernel calls per (op, Sᵀ rows built at a time, Sᵀ "
             "rows per grid step)",
        op=op, slice_rows=slice_rows(rows, m), chunk_rows=rows).inc()


def _scatter_matrix(values_ref, indices_ref, m: int, scales=None):
    """Expand a packed ``(chunk, N, cols)`` block into the fp32 scatter
    matrix Sᵀ ``(chunk * M, cols)`` — the in-VMEM image of DeMM's N read
    ports:

        Sᵀ[g*M + j, c] = sum_n values[g, n, c] * [indices[g, n, c] == j]

    Sᵀ is built in slices of :func:`slice_rows` rows, each from only the
    groups it covers, and the slices are stacked along sublanes.  The
    chunk and N loops are static and small, so they unroll into
    select-accumulate passes over a slice.  Each packed row is a ``(1,
    cols)`` slice broadcast along sublanes, and the select runs in fp32 (the
    VPU's native width), so the same body lowers for f32, bf16 and int8
    values.  ``scales`` (optional) holds one ``(1, cols)`` fp32 row per
    group of the chunk, folded into the values before the select (the
    in-register w8a16 dequant).  Duplicate indices accumulate, matching the
    oracle's scatter-add.
    """
    chunk, n, cols = values_ref.shape
    h = slice_rows(chunk * m, m)
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 0)
    slices = []
    for g0 in range(0, chunk, h // m):
        s = jnp.zeros((h, cols), jnp.float32)
        for g in range(g0, g0 + h // m):
            for j in range(n):
                v = values_ref[g, j:j + 1, :].astype(jnp.float32)
                if scales is not None:
                    v = v * scales[g]
                target = indices_ref[g, j:j + 1, :] + (g - g0) * m
                s = s + jnp.where(rows == target, v, 0.0)
        slices.append(s)
    return slices[0] if len(slices) == 1 else jnp.concatenate(slices, 0)


def accumulate(out_ref, x, s, interpret: bool):
    """``out += x @ s`` on the MXU: s rounded to x's dtype, fp32
    accumulation.  Interpret mode runs on the CPU, whose dot has no
    bf16 × bf16 → f32 form, so there both operands are widened first —
    the same products, which are exact in fp32."""
    s = s.astype(x.dtype)
    if interpret:
        x, s = x.astype(jnp.float32), s.astype(jnp.float32)
    out_ref[...] += jnp.dot(x, s, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# y = x @ W_sparseᵀ (serving orientation: W packed (G, Ne, O), x (Bx, K))
# ---------------------------------------------------------------------------

def _xwT_kernel(x_ref, values_ref, indices_ref, out_ref, *, m, interpret):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = _scatter_matrix(values_ref, indices_ref, m)             # (Kc, Ot)
    accumulate(out_ref, x_ref[...], s, interpret)


def xwT_grid(x_shape, values_shape, m: int, block_b: int, block_o: int):
    """(block_b, block_o, chunk) actually used for a problem — legal TPU
    tiles derived from the requested ones (see :func:`fit_tile`)."""
    bx = x_shape[0]
    g, _n, o = values_shape
    return (fit_tile(bx, block_b, SUBLANES), fit_tile(o, block_o, LANES),
            group_chunk(g, m))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_b", "block_o", "interpret"),
)
def demm_xwT_pallas(
    x: jax.Array,           # (Bx, K) dense activations
    values: jax.Array,      # (G, Ne, O) packed weight
    indices: jax.Array,     # (G, Ne, O) int32
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
    block_b, block_o, chunk = xwT_grid(x.shape, values.shape, m, block_b,
                                       block_o)
    return pl.pallas_call(
        functools.partial(_xwT_kernel, m=m, interpret=interpret),
        grid=(bx // block_b, o // block_o, g // chunk),
        in_specs=[
            pl.BlockSpec((block_b, chunk * m), lambda i, j, c: (i, c)),
            pl.BlockSpec((chunk, n, block_o), lambda i, j, c: (c, 0, j)),
            pl.BlockSpec((chunk, n, block_o), lambda i, j, c: (c, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bx, o), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="demm_xwT",
    )(x, values, indices)


# ---------------------------------------------------------------------------
# C = A_sparse @ B (paper orientation)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_r", "block_c", "interpret"),
)
def demm_spmm_pallas(
    values: jax.Array,      # (G, N, R)
    indices: jax.Array,     # (G, N, R) int32
    b: jax.Array,           # (K, Cd), K = G * M
    cfg: SparsityConfig,
    *,
    block_r: int = DEFAULT_BLOCK_R,
    block_c: int = DEFAULT_BLOCK_C,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B as ``(Bᵀ @ Aᵀ)ᵀ``: the dense operand is transposed, the
    packed stream is consumed in place by the xwT kernel."""
    return demm_xwT_pallas(b.T, values, indices, cfg, block_b=block_c,
                           block_o=block_r, interpret=interpret).T
