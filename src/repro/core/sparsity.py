"""Relaxed N:M structured sparsity — formats, pruning, packing.

This module is the data-format half of the paper's contribution: a matrix A
follows *relaxed structured sparsity* N:M when every group of M contiguous
elements along the contraction dimension of each row holds at most N
non-zeros.  The packed representation stores, per (row, group), exactly N
``{value, col_idx}`` pairs (zero-padded when fewer non-zeros exist), which is
what the DeMM engine streams: values feed the multipliers, indices feed the
read ports.

Shapes
------
dense   A        : (R, K)            with K % M == 0, G = K // M groups
packed  values   : (G, N, R)         same dtype as A
packed  indices  : (G, N, R) int32   local column index within the group,
                                     in [0, M); padded slots point at 0 with
                                     value 0 (contributing nothing).

The row axis R is minor ("lane-major" layout): a TPU tiles the two minor
dims of every array by (8, 128), so a tiny minor N axis would be padded to
128 lanes in VMEM and force a relayout copy in front of every kernel call.
With R minor the {value, col_idx} stream is stored, DMA'd and consumed by
the Pallas kernels as is.

The k-reconfiguration of the paper (a DeMM(N, M, C, k) engine serving kN:M
patterns by time-sharing its N read ports over k cycles) is mirrored by
``reconfigure_k``: a packed (G, kN, R) tensor is viewed as k passes of
(G, N, R), preserving the engine-config semantics.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_static
class Static:
    """Hashable static metadata stored inside a params pytree (not traced).

    Lives here (not in ``models.layers``) so core/serialization code never
    has to import the model layer package; ``models.layers.Static`` re-exports
    this class for backward compatibility.
    """

    def __init__(self, value):
        self.value = value

    def __eq__(self, other):
        return isinstance(other, Static) and self.value == other.value

    def __hash__(self):
        return hash(("Static", self.value))

    def __repr__(self):
        return f"Static({self.value!r})"


@dataclasses.dataclass(frozen=True)
class SparsityConfig:
    """Relaxed structured sparsity pattern N:M with k-reconfiguration.

    The *native* engine pattern is ``n:m``.  ``k`` > 1 means the engine is
    reconfigured to serve the denser ``k*n : m`` pattern in ``k`` passes over
    the same pre-loaded B block (paper §II-B).  The *effective* number of
    non-zeros per group is ``n_effective = n * k``.
    """

    n: int = 8
    m: int = 128
    k: int = 1

    def __post_init__(self):
        if self.n < 1 or self.m < 1 or self.k < 1:
            raise ValueError(f"n, m, k must be >= 1, got {self}")
        if self.n * self.k > self.m:
            raise ValueError(
                f"effective non-zeros n*k={self.n * self.k} exceeds group size m={self.m}"
            )

    @property
    def n_effective(self) -> int:
        return self.n * self.k

    @property
    def density(self) -> float:
        return self.n_effective / self.m

    @property
    def sparsity(self) -> float:
        return 1.0 - self.density

    def pattern_name(self) -> str:
        if self.k == 1:
            return f"{self.n}:{self.m}"
        return f"{self.n_effective}:{self.m} (as {self.k}x{self.n}:{self.m})"

    def packed_bytes(self, rows: int, cols: int, value_bytes: int = 2,
                     index_bytes: int = 1) -> int:
        """HBM footprint of the packed representation."""
        groups = cols // self.m
        return rows * groups * self.n_effective * (value_bytes + index_bytes)

    def dense_bytes(self, rows: int, cols: int, value_bytes: int = 2) -> int:
        return rows * cols * value_bytes

    def compression_ratio(self, value_bytes: int = 2, index_bytes: int = 1) -> float:
        """Dense/packed byte ratio — the memory-roofline lever on TPU."""
        return (self.m * value_bytes) / (self.n_effective * (value_bytes + index_bytes))


# Common named patterns from the paper.
PATTERNS = {
    "8:128": SparsityConfig(8, 128, 1),
    "8:256": SparsityConfig(8, 256, 1),
    "4:64": SparsityConfig(4, 64, 1),
    "1:2": SparsityConfig(1, 2, 1),
    "1:4": SparsityConfig(1, 4, 1),
    "1:8": SparsityConfig(1, 8, 1),
    "2:4": SparsityConfig(2, 4, 1),
    # DeMM(8,128,·,8) reconfigured to fine-grained-equivalent densities:
    "64:128 (as 8x8:128)": SparsityConfig(8, 128, 8),
}


def _check_dims(shape, m: int):
    if len(shape) != 2:
        raise ValueError(f"expected 2-D matrix, got shape {shape}")
    if shape[1] % m == 0:
        return
    raise ValueError(f"contraction dim {shape[1]} not divisible by group size {m}")


# ---------------------------------------------------------------------------
# Pattern validation / mask utilities
# ---------------------------------------------------------------------------

def group_nonzero_counts(a: jax.Array, cfg: SparsityConfig) -> jax.Array:
    """Non-zero count per (row, group): shape (R, G)."""
    _check_dims(a.shape, cfg.m)
    r, kdim = a.shape
    g = kdim // cfg.m
    return jnp.sum((a.reshape(r, g, cfg.m) != 0).astype(jnp.int32), axis=-1)


def satisfies_pattern(a: jax.Array, cfg: SparsityConfig) -> jax.Array:
    """True iff every (row, group) has at most n_effective non-zeros."""
    return jnp.all(group_nonzero_counts(a, cfg) <= cfg.n_effective)


def prune_mask(a: jax.Array, cfg: SparsityConfig) -> jax.Array:
    """Magnitude top-``n_effective``-per-group boolean mask with A's shape.

    This is the pruning rule used to derive relaxed-structured-sparse models
    (keep the largest-|w| N elements of every M-block of every row).  Ties are
    broken deterministically by column order (first occurrence wins), matching
    ``jax.lax.top_k`` semantics.
    """
    _check_dims(a.shape, cfg.m)
    r, kdim = a.shape
    g = kdim // cfg.m
    ne = cfg.n_effective
    mag = jnp.abs(a.reshape(r, g, cfg.m))
    # Threshold = value of the ne-th largest magnitude in each group.
    top_vals, _ = jax.lax.top_k(mag, ne)
    thresh = top_vals[..., ne - 1 : ne]  # (R, G, 1)
    # Exact zeros are never kept — and must be excluded *before* the tie
    # resolution: an under-full group (fewer than ne non-zeros — the relaxed
    # "at most N" case) has threshold 0, and counting its zeros as tie
    # candidates used to crowd out the genuine non-zeros sitting later in
    # the group.
    keep = (mag >= thresh) & (mag > 0)
    # Resolve ties: if >ne elements meet the threshold, keep the first ones.
    over = jnp.cumsum(keep.astype(jnp.int32), axis=-1)
    keep = keep & (over <= ne)
    return keep.reshape(r, kdim)


def prune(a: jax.Array, cfg: SparsityConfig) -> jax.Array:
    """Magnitude-prune ``a`` to the N:M pattern (dense output, zeros inserted)."""
    return jnp.where(prune_mask(a, cfg), a, jnp.zeros((), a.dtype))


# ---------------------------------------------------------------------------
# Pack / unpack
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackedSparse:
    """Packed relaxed-structured-sparse matrix (the DeMM input stream)."""

    values: jax.Array   # (G, Ne, R)
    indices: jax.Array  # (G, Ne, R) int32, local in [0, M)
    cfg: SparsityConfig
    shape: tuple        # dense (R, K)

    @property
    def dense_shape(self):
        return self.shape

    def tree_flatten(self):
        return (self.values, self.indices), (self.cfg, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, indices = children
        cfg, shape = aux
        return cls(values=values, indices=indices, cfg=cfg, shape=shape)


jax.tree_util.register_pytree_node(
    PackedSparse, PackedSparse.tree_flatten, PackedSparse.tree_unflatten
)


@partial(jax.jit, static_argnames=("cfg",))
def pack(a: jax.Array, cfg: SparsityConfig) -> PackedSparse:
    """Pack a dense matrix that satisfies (or is pruned to) N:M into
    ``{values, indices}``.

    Elements beyond the ``n_effective`` magnitude-largest per group are
    dropped (i.e. ``pack(prune(a)) == pack(a)``); use :func:`satisfies_pattern`
    first if lossless packing must be asserted.
    """
    _check_dims(a.shape, cfg.m)
    r, kdim = a.shape
    g = kdim // cfg.m
    ne = cfg.n_effective
    # One row per (row, group): a 2-D gather compiles in about a second for
    # the TPU where the same gather over (R, G, M) takes minutes.
    grp = a.reshape(r * g, cfg.m)
    mag = jnp.abs(grp)
    # top_k by magnitude; indices are positions within the group.
    _, idx = jax.lax.top_k(mag, ne)                      # (R*G, Ne)
    idx = jnp.sort(idx, axis=-1)                          # canonical order
    vals = jnp.take_along_axis(grp, idx, axis=-1)         # (R*G, Ne)
    # Padded slots (zero values) are pointed at column 0 with value 0.
    vals = jnp.where(vals != 0, vals, jnp.zeros((), a.dtype))
    idx = jnp.where(vals != 0, idx, jnp.zeros((), jnp.int32))

    def lane_major(x):        # (R*G, Ne) -> (G, Ne, R), once, at pack time
        return jnp.transpose(x.reshape(r, g, ne), (1, 2, 0))

    return PackedSparse(values=lane_major(vals),
                        indices=lane_major(idx).astype(jnp.int32),
                        cfg=cfg, shape=(r, kdim))


@partial(jax.jit, static_argnames=("cfg", "shape"))
def unpack(values: jax.Array, indices: jax.Array, cfg: SparsityConfig,
           shape: tuple) -> jax.Array:
    """Scatter a packed representation back to a dense (R, K) matrix."""
    r, kdim = shape
    g = kdim // cfg.m
    ne = cfg.n_effective
    assert values.shape == (g, ne, r), (values.shape, (g, ne, r))
    # One-hot scatter: out[r, g, m] = sum_n values[g, n, r] * [indices==m]
    iota = jnp.arange(cfg.m, dtype=jnp.int32)
    onehot = (indices[..., None] == iota).astype(values.dtype)  # (G,Ne,R,M)
    dense = jnp.einsum("gnr,gnrm->rgm", values, onehot)
    return dense.reshape(r, kdim)


def unpack_packed(p: PackedSparse) -> jax.Array:
    return unpack(p.values, p.indices, p.cfg, p.shape)


# ---------------------------------------------------------------------------
# PackedWeight — the first-class packed-weight pytree
# ---------------------------------------------------------------------------

# Known packed layouts.  ``xwT`` is the serving orientation (y = x @ W^T with
# W row-sparse along the contraction dim); ``block`` is the two-level
# block-sparse format of kernels/demm_block_spmm.py — per row-block
# active-group lists (level 1) over the usual relaxed N:M packed pairs
# (level 2), converted ahead of time by :func:`pack_block`.
LAYOUT_XWT = "xwT"
LAYOUT_BLOCK = "block"
LAYOUTS = (LAYOUT_XWT, LAYOUT_BLOCK)

# Row-block height for the block layout: the MXU tile on TPU.  pack_block
# clamps it to the largest power-of-two divisor of the row count.
DEFAULT_BLOCK_R = 128

# Known quantized value dtypes.  ``None`` (the default) means the values
# child carries full-precision floats; ``"int8"`` means symmetric int8 with
# a traced ``scales`` child (per output row for the xwT layout, per
# (row-block, group, row) for the block layout) — see ``repro.quant``.
QDTYPE_INT8 = "int8"
QDTYPES = (QDTYPE_INT8,)


def expand_scales(scales: jax.Array, values: jax.Array) -> jax.Array:
    """Broadcast per-unit quantization scales over the packed value axes.

    The single home for the rank rule every dequant site shares
    (``repro.quant``, the kernels' references, ``sparsetrain.vjp``): the
    scale shape is the values shape without the Ne axis (second-minor),
    and per-row xwT scales also drop the group axis in front of it.  So
    units owning one Ne run (per-group xwT ``(*, G, O)``, the block layout's
    per-(row-block, group, row) ``(*, RB, A_max, block_r)``) gain one axis
    and per-row xwT units ``(*, O)`` gain two.
    """
    if scales.ndim == values.ndim - 1:
        return scales[..., None, :]
    return scales[..., None, None, :]


class PackedWeight:
    """A packed relaxed-N:M sparse weight as a registered JAX pytree.

    This is the paper's ``{value, col_idx}`` stream as a first-class object:
    ``values``/``indices`` are traced children (so ``jax.tree.map``, scan
    stacking, optimizers, and shardings all see them), while the
    :class:`SparsityConfig` (including k-reconfiguration), the per-layer
    dense ``(out, in)`` shape, and the ``layout`` tag ride along as static
    aux data — available at trace time for kernel dispatch and autotuning.

    Shapes: for the ``xwT`` layout ``values``/``indices`` are
    ``(*stack, G, Ne, O)`` with ``G = in_features // cfg.m`` and
    ``Ne = cfg.n_effective``.  For the ``block`` layout they are
    ``(RB, A_max, Ne, block_r)`` with a third traced child
    ``active_groups (RB, A_max) int32`` — the level-1 address stream that
    gates which B blocks the kernel DMAs at all — and the static block
    geometry ``block_geom = (block_r, a_max)`` rides in the aux data.
    ``dense_shape`` is always the per-layer 2-D ``(O, K)`` (leading stack
    dims — e.g. the scan-stacked layer axis — do not change it).

    Quantization (``repro.quant``): when ``qdtype`` is set (static aux, e.g.
    ``"int8"``) the ``values`` child holds quantized integers and a fourth
    traced child ``scales`` carries the symmetric dequantization scales —
    ``(*stack, O)`` float32 (per output row, the default) or
    ``(*stack, G, O)`` (per group) for ``xwT``,
    ``(*stack, RB, A_max, block_r)`` (per row-block × group × row) for
    ``block``.  The dense weight is ``scales ⊙ values`` broadcast over the
    packed axes; kernels dequantize in-register (w8a16).

    Contraction-dim sharding (``repro.sharding``): ``shard_axis`` (static,
    e.g. ``"model"``) marks the *shard-stacked* form produced by
    :func:`shard_packed_row_parallel` — the children carry an extra dim of
    size ``shards`` **between** the stack dims and the layout core, each
    slice locally renumbered over its ``K // shards`` column chunk, so a
    mesh can place one slice per device and combine partial products with
    ``psum``.  ``dense_shape`` stays the *global* ``(O, K)``; for the
    ``block`` layout ``block_geom[1]`` becomes the shared per-shard
    ``a_max``.  A *local* per-shard slice (inside ``shard_map``, see
    :func:`shard_slice`) instead has ``shard_axis=None`` with a local
    ``dense_shape`` and keeps ``shards`` as provenance so kernel dispatch
    and tune-cache keys can tell a shard-local problem from a global one.
    """

    __slots__ = ("values", "indices", "cfg", "dense_shape", "layout",
                 "active_groups", "block_geom", "scales", "qdtype",
                 "shard_axis", "shards", "tier_ne")

    def __init__(self, values, indices, *, cfg: SparsityConfig, dense_shape,
                 layout: str = LAYOUT_XWT, active_groups=None,
                 block_geom=None, scales=None, qdtype=None,
                 shard_axis=None, shards: int = 1, tier_ne=None):
        if not isinstance(cfg, SparsityConfig):
            raise TypeError(f"cfg must be a SparsityConfig, got {type(cfg)}")
        if layout not in LAYOUTS:
            raise ValueError(f"unknown layout {layout!r}; expected {LAYOUTS}")
        if qdtype is None:
            if scales is not None:
                raise ValueError(
                    "scales only apply to quantized weights; set qdtype "
                    "(repro.quant.quantize_packed does both)")
        else:
            if qdtype not in QDTYPES:
                raise ValueError(
                    f"unknown qdtype {qdtype!r}; expected one of {QDTYPES}")
            if scales is None:
                raise ValueError(
                    f"qdtype={qdtype!r} needs the scales child; quantize "
                    "with repro.quant.quantize_packed")
        dense_shape = tuple(int(d) for d in dense_shape)
        if len(dense_shape) != 2:
            raise ValueError(f"dense_shape must be 2-D (out, in), got "
                             f"{dense_shape}")
        shards = int(shards)
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if shard_axis is not None:
            if not isinstance(shard_axis, str):
                raise TypeError(f"shard_axis must be a mesh axis name "
                                f"(str) or None, got {shard_axis!r}")
            if shards < 2:
                raise ValueError(
                    "shard_axis set but shards < 2; the shard-stacked form "
                    "needs the shard-count dim (shard_packed_row_parallel)")
        vshape = getattr(values, "shape", None)
        if layout == LAYOUT_BLOCK:
            if active_groups is None:
                raise ValueError(
                    "block layout needs the active_groups child (the level-1 "
                    "address stream); pack with pack_block")
            if block_geom is None:
                if vshape is None or len(vshape) < 4:
                    raise ValueError(
                        "block layout needs block_geom=(block_r, a_max) when "
                        "values carry no shape to derive it from")
                block_geom = (int(vshape[-1]), int(vshape[-3]))
            block_geom = (int(block_geom[0]), int(block_geom[1]))
            if vshape is not None and len(vshape) >= 4:
                rb, amax, ne, br = (int(d) for d in vshape[-4:])
                if (ne != cfg.n_effective or br != block_geom[0]
                        or amax != block_geom[1] or rb * br != dense_shape[0]):
                    raise ValueError(
                        f"values shape {tuple(vshape)} is inconsistent with "
                        f"block_geom={block_geom} over dense {dense_shape} "
                        f"at cfg={cfg.pattern_name()}: expected "
                        f"(*, {dense_shape[0] // block_geom[0]}, "
                        f"{block_geom[1]}, {cfg.n_effective}, "
                        f"{block_geom[0]})")
            if (shard_axis is not None and vshape is not None
                    and len(vshape) >= 5 and int(vshape[-5]) != shards):
                raise ValueError(
                    f"shard-stacked block values {tuple(vshape)} carry "
                    f"{int(vshape[-5])} shard slices, expected shards="
                    f"{shards}")
        else:
            if active_groups is not None or block_geom is not None:
                raise ValueError(
                    f"active_groups/block_geom only apply to the "
                    f"{LAYOUT_BLOCK!r} layout, not {layout!r}")
            if vshape is not None and len(vshape) >= 3:
                g, ne, o = (int(d) for d in vshape[-3:])
                # Shard-stacked values hold G // shards groups per slice.
                span = shards if shard_axis is not None else 1
                if (ne != cfg.n_effective or o != dense_shape[0]
                        or g * cfg.m * span != dense_shape[1]):
                    raise ValueError(
                        f"values shape {tuple(vshape)} is inconsistent with "
                        f"the packed layout of cfg={cfg.pattern_name()} over "
                        f"dense {dense_shape}: expected "
                        f"(*, {dense_shape[1] // (cfg.m * span)}, "
                        f"{cfg.n_effective}, {dense_shape[0]})")
            if (shard_axis is not None and vshape is not None
                    and len(vshape) >= 4 and int(vshape[-4]) != shards):
                raise ValueError(
                    f"shard-stacked xwT values {tuple(vshape)} carry "
                    f"{int(vshape[-4])} shard slices, expected shards="
                    f"{shards}")
        sshape = getattr(scales, "shape", None)
        if qdtype is not None and sshape is not None and vshape is not None:
            per_unit = tuple(vshape[:-2]) + tuple(vshape[-1:])
            if layout == LAYOUT_BLOCK:
                want = (per_unit,)
            else:
                # xwT grants two granularities (repro.quant): per output
                # row (*stack, O) or per (group, row) (*stack, G, O).
                want = (tuple(vshape[:-3]) + tuple(vshape[-1:]), per_unit)
            if tuple(sshape) not in want:
                raise ValueError(
                    f"scales shape {tuple(sshape)} does not match values "
                    f"{tuple(vshape)} for the {layout!r} layout: expected "
                    f"one of {want} (per output row / per group for xwT, "
                    f"per row-block × group × row for block)")
        if tier_ne is not None:
            tier_ne = int(tier_ne)
            if not 1 <= tier_ne <= cfg.n_effective:
                raise ValueError(
                    f"tier_ne={tier_ne} outside [1, n_effective="
                    f"{cfg.n_effective}] of cfg={cfg.pattern_name()}")
            if tier_ne == cfg.n_effective:
                tier_ne = None      # the full tier is the canonical no-view
        self.values = values
        self.indices = indices
        self.cfg = cfg
        self.dense_shape = dense_shape
        self.layout = layout
        self.active_groups = active_groups
        self.block_geom = block_geom
        self.scales = scales
        self.qdtype = qdtype
        self.shard_axis = shard_axis
        self.shards = shards
        self.tier_ne = tier_ne

    # ---- static geometry -------------------------------------------------
    @property
    def out_features(self) -> int:
        return self.dense_shape[0]

    @property
    def in_features(self) -> int:
        return self.dense_shape[1]

    @property
    def groups(self) -> int:
        return self.in_features // self.cfg.m

    @property
    def stack_dims(self) -> tuple:
        """Leading (scan/vmap) stack dims in front of the layout's core:
        (G, Ne, O) for ``xwT``, (RB, A_max, Ne, block_r) for ``block``.
        The shard-stacked form's shard dim sits between the stack dims and
        the core (so layer-scan still slices axis 0) and is not a stack
        dim."""
        shape = getattr(self.values, "shape", None)
        if shape is None:
            return ()
        core = 4 if self.layout == LAYOUT_BLOCK else 3
        if self.shard_axis is not None:
            core += 1
        return tuple(shape[:-core])

    def replace(self, **kw) -> "PackedWeight":
        out = {"values": self.values, "indices": self.indices,
               "cfg": self.cfg, "dense_shape": self.dense_shape,
               "layout": self.layout, "active_groups": self.active_groups,
               "block_geom": self.block_geom, "scales": self.scales,
               "qdtype": self.qdtype, "shard_axis": self.shard_axis,
               "shards": self.shards, "tier_ne": self.tier_ne}
        out.update(kw)
        return PackedWeight(out.pop("values"), out.pop("indices"), **out)

    def __repr__(self):
        vs = getattr(self.values, "shape", "?")
        geom = f", block_geom={self.block_geom}" if self.block_geom else ""
        q = f", qdtype={self.qdtype!r}" if self.qdtype else ""
        sh = ""
        if self.shards > 1:
            sh = f", shards={self.shards}"
            if self.shard_axis is not None:
                sh += f" over {self.shard_axis!r}"
        tier = f", tier_ne={self.tier_ne}" if self.tier_ne else ""
        return (f"PackedWeight(values={vs}, cfg={self.cfg.pattern_name()!r}, "
                f"dense_shape={self.dense_shape}, layout={self.layout!r}"
                f"{geom}{q}{sh}{tier})")

    # ---- conversions -----------------------------------------------------
    @classmethod
    def from_dense(cls, w: jax.Array, cfg: SparsityConfig,
                   layout: str = LAYOUT_XWT, *, block_r: "int | None" = None,
                   a_max: "int | None" = None) -> "PackedWeight":
        """Prune (if needed) and pack a dense 2-D weight into ``layout``."""
        if layout == LAYOUT_BLOCK:
            return pack_block(w, cfg, block_r=block_r, a_max=a_max)
        p = pack(prune(w, cfg), cfg)
        return cls(p.values, p.indices, cfg=cfg, dense_shape=w.shape,
                   layout=layout)

    def dequantized_values(self) -> jax.Array:
        """The values child with quantization scales applied (float32 for a
        quantized weight; the raw values otherwise).  The scale shape is a
        prefix of the values shape, so per-row vs per-group xwT scales (and
        the block layout's per-(row-block, group, row) scales) are told
        apart by rank alone."""
        if self.qdtype is None:
            return self.values
        vals = self.values.astype(jnp.float32)
        return vals * expand_scales(self.scales, vals)

    def to_dense(self) -> jax.Array:
        """Scatter back to the dense weight (dequantizing if needed),
        restoring any stack dims.  Shard-stacked weights are merged back to
        the global packing first (concrete data only for ``block``); a
        draft-tier view (``tier_ne``) densifies only its tier prefix."""
        if self.tier_ne is not None:
            return narrow_tier(self).to_dense()
        if self.shard_axis is not None:
            return unshard_packed(self).to_dense()
        o, k = self.dense_shape
        if self.layout == LAYOUT_BLOCK:
            stack = self.stack_dims
            ag, vals, idxs = (self.active_groups, self.dequantized_values(),
                              self.indices)
            if stack:
                ag = ag.reshape(-1, *ag.shape[-2:])
                vals = vals.reshape(-1, *vals.shape[-4:])
                idxs = idxs.reshape(-1, *idxs.shape[-4:])
                dense = jax.vmap(lambda a, v, i: unpack_block(
                    a, v, i, self.cfg, self.dense_shape))(ag, vals, idxs)
                return dense.reshape(*stack, o, k)
            return unpack_block(ag, vals, idxs, self.cfg, self.dense_shape)
        vals, idxs = self.dequantized_values(), self.indices
        stack = self.stack_dims
        if stack:
            vals = vals.reshape(-1, *vals.shape[-3:])
            idxs = idxs.reshape(-1, *idxs.shape[-3:])
            dense = jax.vmap(lambda v, i: unpack(v, i, self.cfg, (o, k)))(
                vals, idxs)
            return dense.reshape(*stack, o, k)
        return unpack(vals, idxs, self.cfg, (o, k))


def _pw_flatten(pw: PackedWeight):
    aux = (pw.cfg, pw.dense_shape, pw.layout, pw.block_geom, pw.qdtype,
           pw.shard_axis, pw.shards, pw.tier_ne)
    children = [pw.values, pw.indices]
    if pw.layout == LAYOUT_BLOCK:
        children.append(pw.active_groups)
    if pw.qdtype is not None:
        children.append(pw.scales)
    return tuple(children), aux


def _pw_flatten_with_keys(pw: PackedWeight):
    keyed = [(jax.tree_util.GetAttrKey("values"), pw.values),
             (jax.tree_util.GetAttrKey("indices"), pw.indices)]
    if pw.layout == LAYOUT_BLOCK:
        keyed.append((jax.tree_util.GetAttrKey("active_groups"),
                      pw.active_groups))
    if pw.qdtype is not None:
        keyed.append((jax.tree_util.GetAttrKey("scales"), pw.scales))
    return tuple(keyed), (pw.cfg, pw.dense_shape, pw.layout, pw.block_geom,
                          pw.qdtype, pw.shard_axis, pw.shards, pw.tier_ne)


def _pw_unflatten(aux, children) -> PackedWeight:
    # Raw rebuild, no __init__ validation: tree transforms routinely carry
    # non-array leaves (None results, PartitionSpecs, sentinel objects) and
    # the aux was validated when the weight was packed.
    cfg, dense_shape, layout, block_geom, qdtype, shard_axis, shards, \
        tier_ne = aux
    pw = object.__new__(PackedWeight)
    children = list(children)
    scales = children.pop() if qdtype is not None else None
    if layout == LAYOUT_BLOCK:
        values, indices, active_groups = children
    else:
        (values, indices), active_groups = children, None
    pw.values = values
    pw.indices = indices
    pw.cfg = cfg
    pw.dense_shape = dense_shape
    pw.layout = layout
    pw.active_groups = active_groups
    pw.block_geom = block_geom
    pw.scales = scales
    pw.qdtype = qdtype
    pw.shard_axis = shard_axis
    pw.shards = shards
    pw.tier_ne = tier_ne
    return pw


jax.tree_util.register_pytree_with_keys(
    PackedWeight, _pw_flatten_with_keys, _pw_unflatten, _pw_flatten)


# ---------------------------------------------------------------------------
# Two-level block packing (the "block" layout)
# ---------------------------------------------------------------------------

def _choose_block_r(rows: int, cap: int = DEFAULT_BLOCK_R) -> int:
    """Largest power-of-two divisor of ``rows``, capped at ``cap``."""
    br = 1
    while br * 2 <= cap and rows % (br * 2) == 0:
        br *= 2
    return br


def _group_activity(w: jax.Array, block_r: int, m: int) -> jax.Array:
    """Active-group mask ``(..., RB, G)`` of ``(..., R, K)``: a group is
    active when any row of the row block has a non-zero in it.  The single
    home for the level-1 activity definition, shared by the stacked and
    unstacked packers so their ``a_max`` bounds can never diverge."""
    *lead, r, k = w.shape
    blocks = w.reshape(*lead, r // block_r, block_r, k // m, m)
    return jnp.any(blocks != 0, axis=(-3, -1))


def _needed_a_max(activity: jax.Array) -> int:
    """Max active groups over every row block (>= 1; concrete data only)."""
    return max(1, int(jnp.max(jnp.sum(activity, axis=-1))))


def pack_block(a: jax.Array, cfg: SparsityConfig, *,
               block_r: "int | None" = None,
               a_max: "int | None" = None) -> PackedWeight:
    """Ahead-of-time two-level conversion to the ``block`` layout.

    Level 1: per ``block_r``-row block, the sorted list of *active* M-groups
    (groups where any row of the block has a non-zero) — the address stream
    that gates which B blocks the kernel DMAs from HBM at all.  Level 2:
    within each active group, the usual relaxed N:M ``{values, indices}``
    pairs (magnitude top-``n_effective`` per row, like :func:`pack`).

    ``a_max`` bounds the active-group list length (static — it shapes the
    packed arrays).  When ``None`` it is computed from the data; under
    tracing (``jax.eval_shape`` dry-runs) data is unavailable, so the
    conservative upper bound ``G`` is used — pass ``a_max`` explicitly for
    shape-exact dry-runs.  An ``a_max`` larger than ``G`` pads with
    inactive slots (matching an existing checkpoint's geometry); an
    undersized ``a_max`` raises on concrete inputs, but **cannot be checked
    under tracing** (the bound is data-dependent): a traced call with an
    explicit ``a_max`` below the true active count silently truncates, so
    the caller owns that bound — pack on concrete weights (the AOT path)
    when in doubt.  Padded slots point at group 0 with all-zero values and
    contribute nothing.

    Returns a :class:`PackedWeight` with ``layout="block"``, traced children
    ``values``/``indices`` ``(RB, A_max, Ne, block_r)`` +
    ``active_groups (RB, A_max) int32``, and static
    ``block_geom=(block_r, a_max)`` in the aux.
    """
    _check_dims(a.shape, cfg.m)
    r, kdim = a.shape
    g = kdim // cfg.m
    ne = cfg.n_effective
    if block_r is None:
        block_r = _choose_block_r(r)
    if r % block_r:
        raise ValueError(f"rows {r} not divisible by block_r={block_r}")
    rb = r // block_r
    concrete = not isinstance(a, jax.core.Tracer)

    blocks = jnp.asarray(a).reshape(rb, block_r, g, cfg.m)
    activity = _group_activity(jnp.asarray(a), block_r, cfg.m)  # (RB, G)
    if a_max is None:
        a_max = _needed_a_max(activity) if concrete else g
    a_max = int(a_max)
    if concrete:
        needed = _needed_a_max(activity)
        if needed > a_max:
            raise ValueError(f"a_max={a_max} < {needed} active groups in the "
                             "densest row block")

    # Stable sort by (active desc, group id asc): actives first, ascending.
    sel_w = min(a_max, g)
    order = jnp.argsort(-activity.astype(jnp.int32), axis=-1,
                        stable=True)[:, :sel_w]                # (RB, sel_w)
    active = jnp.take_along_axis(activity, order, axis=-1)     # bool
    if a_max > sel_w:
        # a_max beyond the group count (e.g. matching an existing
        # checkpoint's geometry): pad with inactive slots.
        order = jnp.pad(order, ((0, 0), (0, a_max - sel_w)))
        active = jnp.pad(active, ((0, 0), (0, a_max - sel_w)))
    ag = jnp.where(active, order, 0).astype(jnp.int32)

    grp = jnp.swapaxes(blocks, 1, 2)                           # (RB, G, br, M)
    sel = jnp.take_along_axis(
        grp, order[:, :, None, None].astype(jnp.int32), axis=1
    )                                                          # (RB, A, br, M)
    mag = jnp.abs(sel)
    _, idx = jax.lax.top_k(mag, ne)                            # (RB, A, br, Ne)
    idx = jnp.sort(idx, axis=-1)
    vals = jnp.take_along_axis(sel, idx, axis=-1)
    # Padded slots alias group 0: zero them so duplicates contribute nothing.
    vals = jnp.where(active[:, :, None, None], vals, jnp.zeros((), a.dtype))
    idx = jnp.where(vals != 0, idx, jnp.zeros((), jnp.int32))
    # Lane-major storage: (RB, A, br, Ne) -> (RB, A, Ne, br).
    vals, idx = jnp.swapaxes(vals, -1, -2), jnp.swapaxes(idx, -1, -2)
    return PackedWeight(vals, idx.astype(jnp.int32), cfg=cfg,
                        dense_shape=(r, kdim), layout=LAYOUT_BLOCK,
                        active_groups=ag, block_geom=(block_r, a_max))


def pack_block_stacked(w: jax.Array, cfg: SparsityConfig, *,
                       block_r: "int | None" = None,
                       a_max: "int | None" = None) -> PackedWeight:
    """:func:`pack_block` for layer-stacked weights ``(*lead, O, K)``.

    All slices share one static ``a_max`` (the max active-group count over
    the stack) so the packed children stack to ``(*lead, RB, A_max, Ne,
    block_r)`` / ``(*lead, RB, A_max)`` and ``jax.lax.scan`` can slice the layer
    axis off exactly as for the xwT layout; ``dense_shape``/``block_geom``
    stay the per-layer statics."""
    lead = tuple(w.shape[:-2])
    if not lead:
        return pack_block(w, cfg, block_r=block_r, a_max=a_max)
    o, kdim = int(w.shape[-2]), int(w.shape[-1])
    _check_dims((o, kdim), cfg.m)
    if block_r is None:
        block_r = _choose_block_r(o)
    g = kdim // cfg.m
    wf = jnp.asarray(w).reshape(-1, o, kdim)
    concrete = not isinstance(w, jax.core.Tracer)
    if a_max is None:
        a_max = (_needed_a_max(_group_activity(wf, block_r, cfg.m))
                 if concrete else g)
    elif concrete:
        # Validate here: the per-slice packers below run under vmap, where
        # every input is a tracer and pack_block's own too-small-a_max check
        # is skipped — without this, an undersized a_max would silently drop
        # weights from the densest slice.
        needed = _needed_a_max(_group_activity(wf, block_r, cfg.m))
        if needed > int(a_max):
            raise ValueError(f"a_max={a_max} < {needed} active groups in "
                             "the densest row block of the stack")
    packed = jax.vmap(
        lambda a: pack_block(a, cfg, block_r=block_r, a_max=a_max))(wf)

    def fix(x):
        return x.reshape(*lead, *x.shape[1:])

    return packed.replace(values=fix(packed.values),
                          indices=fix(packed.indices),
                          active_groups=fix(packed.active_groups))


@partial(jax.jit, static_argnames=("cfg", "shape"))
def unpack_block(active_groups: jax.Array, values: jax.Array,
                 indices: jax.Array, cfg: SparsityConfig,
                 shape: tuple) -> jax.Array:
    """Scatter a two-level block packing back to a dense (R, K) matrix.
    Duplicate active-group ids accumulate (matching the kernel's
    revisit-accumulate semantics); padded all-zero slots contribute 0."""
    r, kdim = shape
    rb, a_max, ne, block_r = values.shape
    g = kdim // cfg.m
    assert rb * block_r == r, (values.shape, shape)
    iota = jnp.arange(cfg.m, dtype=jnp.int32)
    onehot = (indices[..., None] == iota).astype(values.dtype)
    per_slot = jnp.einsum("ranb,ranbm->rabm", values, onehot)  # (RB,A,br,M)

    def per_block(ag_b, slot_b):
        dense_b = jnp.zeros((block_r, g, cfg.m), values.dtype)
        return dense_b.at[:, ag_b, :].add(jnp.swapaxes(slot_b, 0, 1))

    dense = jax.vmap(per_block)(active_groups, per_slot)       # (RB,br,G,M)
    return dense.reshape(r, kdim)


# ---------------------------------------------------------------------------
# Contraction-dim sharding: the per-shard active-group renumbering pass
# ---------------------------------------------------------------------------
#
# Row-parallel (y = x @ W^T with the contraction dim split across devices)
# is where packed weights resist GSPMD: xwT indices are group-local so the
# G axis slices consistently, but the block layout's active_groups hold
# data-dependent *global* group ids — a device owning columns
# [s*K/S, (s+1)*K/S) must drop foreign groups and renumber the rest to its
# local coordinate frame before the kernel's address stream makes sense.
# These host-side passes produce the shard-stacked form consumed by the
# shard_map island in kernels/ops.py: children gain a size-S dim between
# the stack dims and the layout core, each slice renumbered over
# K_local = K/S, partial products combined with psum.

def _block_shard_arrays(pw: "PackedWeight", num_shards: int):
    """Concrete host arrays + the per-slot validity mask for block resharding.

    A slot is live iff any of its packed values is non-zero — exact for
    float block packings (an active group always keeps >= 1 non-zero;
    padded slots are all-zero by construction), unreliable for int8 where
    quantization may round a group's survivors to zero."""
    if pw.qdtype is not None:
        raise NotImplementedError(
            "renumbering quantized block weights is not supported (the "
            "all-zero-slot liveness test is unreliable under int8); keep "
            "them replicated (ShardingPlan(renumber='replicate'))")
    try:
        vals = np.asarray(pw.values)
        idx = np.asarray(pw.indices)
        ag = np.asarray(pw.active_groups)
    except jax.errors.TracerArrayConversionError as e:
        raise ValueError(
            "shard_packed_row_parallel needs concrete block weights (the "
            "per-shard a_max is data-dependent); reshard outside jit") from e
    return vals, idx, ag, np.any(vals != 0, axis=(-2, -1))


def shard_packed_row_parallel(pw: "PackedWeight", num_shards: int, *,
                              axis: str = "model") -> "PackedWeight":
    """Reshard a row-parallel packed weight over the contraction dim.

    Returns the shard-stacked form: children carry an extra dim of size
    ``num_shards`` between the stack dims and the layout core, slice ``s``
    holding the packing of columns ``[s*K/S, (s+1)*K/S)`` renumbered to its
    local frame.  ``xwT`` needs only a reshape (indices are group-local);
    ``block`` runs the renumbering pass: per (row-block, shard), foreign
    active groups are dropped, surviving global ids are rebased by the
    shard's group offset, and all shards share one static per-shard
    ``a_max`` (the densest local list).  ``dense_shape`` stays global.
    """
    num_shards = int(num_shards)
    if num_shards == 1:
        return pw
    if pw.shard_axis is not None:
        raise ValueError(f"{pw!r} is already shard-stacked")
    g = pw.groups
    if g % num_shards:
        raise ValueError(
            f"cannot split {g} groups (K={pw.in_features}, "
            f"M={pw.cfg.m}) over {num_shards} shards")
    gl = g // num_shards
    nstack = len(pw.stack_dims)

    if pw.layout == LAYOUT_XWT:
        vals, idx = pw.values, pw.indices
        # (*stack, G, Ne, O) -> (*stack, S, Gl, Ne, O): a pure reshape
        def reshard3(x):
            return x.reshape(*x.shape[:-3], num_shards, gl, *x.shape[-2:])
        scales = pw.scales
        if scales is not None:
            if scales.ndim == vals.ndim - 1:      # per-group (*stack, G, O)
                scales = scales.reshape(*scales.shape[:-2], num_shards, gl,
                                        scales.shape[-1])
            else:                                  # per-row (*stack, O)
                scales = jnp.broadcast_to(
                    scales[..., None, :],
                    (*scales.shape[:-1], num_shards, scales.shape[-1]))
        return pw.replace(values=reshard3(jnp.asarray(vals)),
                          indices=reshard3(jnp.asarray(idx)),
                          scales=scales, shard_axis=axis, shards=num_shards)

    vals, idx, ag, valid = _block_shard_arrays(pw, num_shards)
    shard_of = ag // gl                                   # (*stack, RB, A)
    per_shard = []
    for s in range(num_shards):
        in_s = valid & (shard_of == s)
        # Stable front-compaction: in-shard slots first, original (ascending
        # global id) order preserved, so local lists stay sorted.
        order = np.argsort(~in_s, axis=-1, kind="stable")
        per_shard.append((order, np.take_along_axis(in_s, order, axis=-1)))
    a_local = max(1, *(int(m.sum(-1).max()) for _, m in per_shard))

    out_v, out_i, out_a = [], [], []
    for s, (order, mask) in enumerate(per_shard):
        order, mask = order[..., :a_local], mask[..., :a_local]
        ag_s = np.take_along_axis(ag, order, axis=-1) - s * gl
        ag_s = np.where(mask, ag_s, 0).astype(np.int32)
        gather = order[..., None, None]
        v_s = np.where(mask[..., None, None],
                       np.take_along_axis(vals, gather, axis=-3), 0)
        i_s = np.where(v_s != 0,
                       np.take_along_axis(idx, gather, axis=-3),
                       0).astype(np.int32)
        out_v.append(v_s)
        out_i.append(i_s)
        out_a.append(ag_s)
    return pw.replace(values=jnp.asarray(np.stack(out_v, axis=nstack)),
                      indices=jnp.asarray(np.stack(out_i, axis=nstack)),
                      active_groups=jnp.asarray(np.stack(out_a, axis=nstack)),
                      block_geom=(pw.block_geom[0], a_local),
                      shard_axis=axis, shards=num_shards)


def unshard_packed(pw: "PackedWeight") -> "PackedWeight":
    """Merge a shard-stacked weight back to the global packing (the inverse
    renumbering).  Exact round trip up to ``a_max`` re-tightening and the
    canonical active-list order — compare via :meth:`PackedWeight.to_dense`.
    Needs concrete data for the ``block`` layout."""
    if pw.shard_axis is None:
        return pw
    s_count = pw.shards
    nstack = len(pw.stack_dims)

    if pw.layout == LAYOUT_XWT:
        def merge3(x):  # (*stack, S, Gl, Ne, O) -> (*stack, G, Ne, O)
            return x.reshape(*x.shape[:-4], x.shape[-4] * x.shape[-3],
                             *x.shape[-2:])
        scales = pw.scales
        if scales is not None:
            if scales.ndim == pw.values.ndim - 1:  # per-group
                scales = scales.reshape(*scales.shape[:-3],
                                        scales.shape[-3] * scales.shape[-2],
                                        scales.shape[-1])
            else:                                   # per-row: replicated
                scales = jax.lax.index_in_dim(scales, 0, axis=scales.ndim - 2,
                                              keepdims=False)
        return pw.replace(values=merge3(pw.values), indices=merge3(pw.indices),
                          scales=scales, shard_axis=None, shards=1)

    vals, idx, ag, valid = _block_shard_arrays(pw, s_count)
    gl = pw.groups // s_count
    a_loc = pw.block_geom[1]
    # Concatenate the per-shard lists along A in shard order (each slice
    # ascending within its chunk -> the merged list is globally ascending).
    ag_m = np.moveaxis(ag, nstack, -2)                 # (*stack, RB, S, Al)
    ag_m = ag_m + (np.arange(s_count) * gl)[:, None]
    ag_m = ag_m.reshape(*ag_m.shape[:-2], s_count * a_loc)
    vals_m = np.moveaxis(vals, nstack, -4)             # (*stack,RB,S,Al,br,Ne)
    vals_m = vals_m.reshape(*vals_m.shape[:-4],
                            s_count * a_loc, *vals_m.shape[-2:])
    idx_m = np.moveaxis(idx, nstack, -4)
    idx_m = idx_m.reshape(*idx_m.shape[:-4],
                          s_count * a_loc, *idx_m.shape[-2:])
    valid_m = np.moveaxis(valid, nstack, -2)
    valid_m = valid_m.reshape(*valid_m.shape[:-2], s_count * a_loc)

    a_max = max(1, int(valid_m.sum(-1).max()))
    order = np.argsort(~valid_m, axis=-1, kind="stable")[..., :a_max]
    mask = np.take_along_axis(valid_m, order, axis=-1)
    ag_g = np.where(mask, np.take_along_axis(ag_m, order, axis=-1),
                    0).astype(np.int32)
    gather = order[..., None, None]
    v_g = np.where(mask[..., None, None],
                   np.take_along_axis(vals_m, gather, axis=-3), 0)
    i_g = np.where(v_g != 0, np.take_along_axis(idx_m, gather, axis=-3),
                   0).astype(np.int32)
    return pw.replace(values=jnp.asarray(v_g), indices=jnp.asarray(i_g),
                      active_groups=jnp.asarray(ag_g),
                      block_geom=(pw.block_geom[0], a_max),
                      shard_axis=None, shards=1)


def shard_slice(pw: "PackedWeight", s) -> "PackedWeight":
    """Slice ``s`` of a shard-stacked weight as a *local* PackedWeight:
    ``dense_shape`` becomes the shard-local ``(O, K // shards)`` and
    ``shards`` is kept as provenance (tune-cache keys include it), with
    ``shard_axis=None`` so standard kernel dispatch applies.  ``s`` may be
    a traced index (used inside the shard_map island)."""
    if pw.shard_axis is None:
        raise ValueError(f"{pw!r} is not shard-stacked")
    dim = len(pw.stack_dims)
    o, k = pw.dense_shape

    def take(x):
        if x is None:
            return None
        return jnp.take(x, s, axis=dim)

    scales = pw.scales
    if scales is not None:
        scales = take(scales)
    return PackedWeight(
        take(pw.values), take(pw.indices), cfg=pw.cfg,
        dense_shape=(o, k // pw.shards), layout=pw.layout,
        active_groups=take(pw.active_groups), block_geom=pw.block_geom,
        scales=scales, qdtype=pw.qdtype, shard_axis=None, shards=pw.shards)


# ---------------------------------------------------------------------------
# Sparser-tier views (repro.spec): one buffer, two densities
# ---------------------------------------------------------------------------
#
# The inverse direction of the paper's §II-B reconfiguration: where
# ``reconfigure_k`` serves a *denser* kN:M pattern in k passes, a *tier view*
# serves a sparser pattern from the same stored stream by reading only the
# first ``tier_ne`` of the ``n_effective`` {value, col_idx} pairs per group.
# ``tier_ne`` is static aux on PackedWeight — the children are untouched, so
# a draft-tier view aliases the full tier's buffers (``draft.values is
# full.values``) and the narrowing happens at trace time inside kernel
# dispatch.  For the prefix to be the magnitude-top-``tier_ne`` slice, the
# per-group entry order must be magnitude-descending — ``tier_sort_packed``
# establishes that invariant once (full-tier compute is order-independent:
# both the one-hot scatter and the kernels' gather-accumulate sum over the
# Ne axis).

def tier_sort_packed(pw: PackedWeight) -> PackedWeight:
    """Reorder every group's {value, col_idx} pairs by descending |value|.

    Numerically a no-op for full-tier compute; it makes any prefix
    ``[:t]`` of the Ne axis the exact magnitude-top-``t`` sub-pattern, which
    is what a ``tier_ne`` draft view reads.  Sort keys are the raw packed
    magnitudes — valid for quantized weights too, because the dequant scale
    is constant along the Ne axis (per row / per group / per (rb, g, row)).
    Zero-padded slots sort last.  Stable, so equal-magnitude entries keep
    their canonical ascending-index order.
    """
    mag = jnp.abs(pw.values.astype(jnp.float32)
                  if pw.qdtype is not None else pw.values)
    order = jnp.argsort(-mag, axis=-2, stable=True)      # the Ne axis
    return pw.replace(
        values=jnp.take_along_axis(pw.values, order, axis=-2),
        indices=jnp.take_along_axis(pw.indices, order, axis=-2))


def narrow_tier(pw: PackedWeight) -> PackedWeight:
    """Materialize a ``tier_ne`` view: slice the Ne axis to the tier prefix
    and retag the config as the sparser ``tier_ne:M`` pattern.  Called at
    trace time by kernel dispatch (kernels/ops.py) — outside a trace the
    slice copies, which is exactly why the *view* form (static ``tier_ne``,
    shared buffers) is what lives in the params tree."""
    t = pw.tier_ne
    if t is None:
        return pw
    return pw.replace(
        values=pw.values[..., :t, :], indices=pw.indices[..., :t, :],
        cfg=SparsityConfig(n=t, m=pw.cfg.m, k=1), tier_ne=None)


def reconfigure_k(p: PackedSparse, k: int) -> PackedSparse:
    """View a packed kN:M matrix as ``k`` sequential N:M passes.

    Mirrors the paper's §II-B reconfiguration: an engine with N read ports
    serves a kN:M pattern by reading the same B block k times.  The packed
    (G, kN, R) tensors are reshaped to (G*k, N, R) views consumed pass by
    pass; numerically ``sum_k demm(pass_k) == demm(full)``.
    """
    ne = p.cfg.n_effective
    if ne % k:
        raise ValueError(f"cannot split n_effective={ne} into k={k} passes")
    n_pass = ne // k
    g, _, r = p.values.shape
    return dataclasses.replace(
        p,
        values=p.values.reshape(g * k, n_pass, r),
        indices=p.indices.reshape(g * k, n_pass, r),
        cfg=SparsityConfig(n=n_pass, m=p.cfg.m, k=k),
    )


# ---------------------------------------------------------------------------
# Host-side helpers (numpy; used by data/checkpoint tooling and tests)
# ---------------------------------------------------------------------------

def random_sparse_dense(rng: np.random.Generator, rows: int, cols: int,
                        cfg: SparsityConfig, dtype=np.float32) -> np.ndarray:
    """A dense matrix exactly satisfying N:M (each group gets <= n_effective
    non-zeros at uniformly random positions)."""
    _check_dims((rows, cols), cfg.m)
    g = cols // cfg.m
    out = np.zeros((rows, g, cfg.m), dtype=dtype)
    ne = cfg.n_effective
    for rr in range(rows):
        for gg in range(g):
            nnz = rng.integers(0, ne + 1)
            if nnz:
                pos = rng.choice(cfg.m, size=nnz, replace=False)
                out[rr, gg, pos] = rng.standard_normal(nnz).astype(dtype)
    return out.reshape(rows, cols)
