"""Kernel variant registry — the dispatch layer of ``repro.tune``.

Every DeMM matmul implementation (the pure-jnp reference, the fused Pallas
TPU kernel, its interpret-mode twin, and the scalar-prefetch block-spmm) is
registered here as a :class:`KernelVariant` declaring

  * how to *call* it with a uniform signature per op,
  * which *tunable parameters* it exposes (tile sizes) and their candidate
    values for a given problem,
  * on which *platforms / problems* it is supported,
  * its *default* (heuristic) parameters.

``kernels/ops.py`` dispatches through this registry instead of matching raw
backend strings, so a new kernel variant (a GPU backend, a different tiling
strategy) plugs in with one ``register_variant`` call and is immediately
visible to the autotuner, the benchmark harness, and ``backend="auto"``.

Ops and uniform signatures
--------------------------
``xwT``       : call(x, values, indices, cfg, w_shape, **params) -> (B, O)
``spmm``      : call(values, indices, b, cfg, a_shape, **params) -> (R, Cd)
``xwT_block`` : call(x, values, indices, active_groups, cfg, w_shape,
                **params) -> (B, O) — the two-level block layout packed ahead
                of time by ``core.sparsity.pack_block`` (values/indices
                (RB, A_max, Ne, block_r) + active_groups (RB, A_max)), fully
                dispatchable under jit (no host repacking).
``xwT_q8``    : call(x, values, indices, scales, cfg, w_shape, **params)
                -> (B, O) — int8 values + per-output-row scales (O,) or
                per-group scales (G, O) (repro.quant); kernels dequantize
                in-register (w8a16).
``xwT_block_q8``: call(x, values, indices, active_groups, scales, cfg,
                w_shape, **params) -> (B, O) — the quantized two-level
                layout, scales (RB, A_max, block_r).
``experts``   : call(x, group_sizes, values, indices, cfg, w_shape,
                **params) -> (R, O) — rows of x sorted by expert
                (``group_sizes[e]`` rows for expert e) through a stack of
                packed experts, values/indices (E, G, Ne, O).

A :class:`Problem` is the static description of one matmul instance — shapes,
dtype, sparsity pattern, platform — and is everything a variant needs to
decide support, defaults, and candidate tiles (no concrete arrays).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

from repro.core.sparsity import SparsityConfig

OPS = ("xwT", "spmm", "xwT_block", "xwT_q8", "xwT_block_q8", "experts")


def current_platform() -> str:
    """'tpu' | 'gpu' | 'cpu' of the default JAX backend."""
    return jax.default_backend()


@dataclasses.dataclass(frozen=True)
class Problem:
    """Static description of one sparse-matmul instance.

    ``rows``  — rows of the dense operand (batch tokens for xwT/xwT_block,
                output columns Cd for spmm's B).
    ``out``   — rows of the sparse operand (O for xwT, R for spmm).
    ``k``     — contraction dim (== groups * cfg.m).
    ``block_r``/``a_max`` — static block geometry of the two-level layout
                (``xwT_block`` only; 0 otherwise).  Fixed at pack time, so
                it is part of the problem, not a tunable parameter.
    ``shards`` — contraction-sharding degree when this is the *per-shard*
                problem of a renumbered row-parallel weight (``k``/``a_max``
                are then shard-local).  Part of the cache key so a tuned
                entry for the global shape is never silently reused for its
                TP slices (and vice versa).
    """

    op: str
    rows: int
    out: int
    k: int
    dtype: str                      # canonical jnp dtype name, e.g. "float32"
    sparsity: Tuple[int, int, int]  # (n, m, k_reconfig)
    platform: str = "cpu"
    block_r: int = 0
    a_max: int = 0
    shards: int = 1
    experts: int = 0                # stack depth E (``experts`` only)

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"unknown op {self.op!r}; expected one of {OPS}")

    @property
    def cfg(self) -> SparsityConfig:
        n, m, k = self.sparsity
        return SparsityConfig(n, m, k)

    @property
    def groups(self) -> int:
        return self.k // self.sparsity[1]

    @property
    def dense_flops(self) -> int:
        return 2 * self.rows * self.out * self.k

    @classmethod
    def for_xwT(cls, x_shape, w_shape, cfg: SparsityConfig, dtype,
                platform: Optional[str] = None, *,
                quantized: bool = False, shards: int = 1) -> "Problem":
        """``dtype`` is the *activation* dtype; quantized problems (int8
        weights, w8a16 kernels) are a distinct op — and therefore distinct
        tuning-cache keys — from their float twins."""
        return cls(op="xwT_q8" if quantized else "xwT",
                   rows=int(x_shape[0]), out=int(w_shape[0]),
                   k=int(x_shape[1]), dtype=jax.numpy.dtype(dtype).name,
                   sparsity=(cfg.n, cfg.m, cfg.k),
                   platform=platform or current_platform(),
                   shards=int(shards))

    @classmethod
    def for_spmm(cls, a_shape, b_shape, cfg: SparsityConfig, dtype,
                 platform: Optional[str] = None) -> "Problem":
        return cls(op="spmm", rows=int(b_shape[1]), out=int(a_shape[0]),
                   k=int(b_shape[0]), dtype=jax.numpy.dtype(dtype).name,
                   sparsity=(cfg.n, cfg.m, cfg.k),
                   platform=platform or current_platform())

    @classmethod
    def for_experts(cls, x_shape, w_shape, cfg: SparsityConfig, dtype,
                    experts: int,
                    platform: Optional[str] = None) -> "Problem":
        """``x_shape`` is the sorted rows' (R, K), ``w_shape`` one expert's
        dense (O, K)."""
        return cls(op="experts", rows=int(x_shape[0]), out=int(w_shape[0]),
                   k=int(x_shape[1]), dtype=jax.numpy.dtype(dtype).name,
                   sparsity=(cfg.n, cfg.m, cfg.k),
                   platform=platform or current_platform(),
                   experts=int(experts))

    @classmethod
    def for_xwT_block(cls, x_shape, pw, dtype,
                      platform: Optional[str] = None) -> "Problem":
        """Problem for a block-layout PackedWeight serving matmul; geometry,
        pattern, and quantization are read from the type's static aux data
        (a quantized node is the distinct ``xwT_block_q8`` op)."""
        o, k = pw.dense_shape
        block_r, a_max = pw.block_geom
        cfg = pw.cfg
        op = "xwT_block_q8" if pw.qdtype is not None else "xwT_block"
        return cls(op=op, rows=int(x_shape[0]), out=int(o),
                   k=int(k), dtype=jax.numpy.dtype(dtype).name,
                   sparsity=(cfg.n, cfg.m, cfg.k),
                   platform=platform or current_platform(),
                   block_r=int(block_r), a_max=int(a_max),
                   shards=int(getattr(pw, "shards", 1)))


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """One registered implementation of a DeMM op."""

    op: str
    name: str
    call: Callable
    # Problem -> {param: (candidate, ...)}; empty dict = nothing to tune.
    param_space: Callable[[Problem], Dict[str, Tuple[int, ...]]]
    # Problem -> {param: value}
    default_params: Callable[[Problem], Dict[str, int]]
    # Problem -> bool
    supported: Callable[[Problem], bool]
    # Variants that need host-side repacking of concrete arrays (cannot be
    # dispatched inside a jit trace); the autotuner may still measure them.
    measure_only: bool = False
    description: str = ""


_REGISTRY: Dict[Tuple[str, str], KernelVariant] = {}


def register_variant(variant: KernelVariant, *, overwrite: bool = False):
    key = (variant.op, variant.name)
    if key in _REGISTRY and not overwrite:
        raise ValueError(f"variant {key} already registered")
    _REGISTRY[key] = variant
    return variant


def get_variant(op: str, name: str) -> KernelVariant:
    try:
        return _REGISTRY[(op, name)]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r} for op {op!r}; registered: "
            f"{sorted(n for (o, n) in _REGISTRY if o == op)}") from None


def variants_for(op: str, problem: Optional[Problem] = None,
                 include_measure_only: bool = False) -> Sequence[KernelVariant]:
    """All registered variants of ``op``, optionally filtered to the ones
    supporting ``problem`` and dispatchable from inside a jit trace."""
    out = []
    for (o, _), v in sorted(_REGISTRY.items()):
        if o != op:
            continue
        if v.measure_only and not include_measure_only:
            continue
        if problem is not None and not v.supported(problem):
            continue
        out.append(v)
    return out


def backend_names(op: str) -> Tuple[str, ...]:
    return tuple(sorted(n for (o, n) in _REGISTRY if o == op))


# ---------------------------------------------------------------------------
# Tile-candidate helpers shared by the built-in variants
# ---------------------------------------------------------------------------

def _pow2_candidates(dim: int, lo: int, hi: int) -> Tuple[int, ...]:
    """Powers of two in [lo, hi] clipped to ``dim`` (always non-empty)."""
    cands = []
    v = lo
    while v <= hi:
        cands.append(min(v, dim))
        if v >= dim:
            break
        v *= 2
    return tuple(dict.fromkeys(cands)) or (min(dim, lo),)


# Interpret mode emulates the TPU kernel on CPU; above this dense-FLOP size
# measuring it is pointless (minutes per call) so the tuner skips it.
_INTERPRET_FLOP_LIMIT = 2 ** 26


def _register_builtin_variants():
    # Imported lazily so `repro.tune.registry` never forces Pallas at import.
    from repro.kernels import ref as kref
    from repro.kernels.demm_spmm import (LANES, SUBLANES,
                                         count_scatter_slices,
                                         demm_spmm_pallas, demm_xwT_pallas,
                                         fit_tile, group_chunk)

    def _legal_tiles(dim: int, lo: int, hi: int, align: int):
        return tuple(dict.fromkeys(
            fit_tile(dim, c, align) for c in _pow2_candidates(dim, lo, hi)))

    def xwT_ref_call(x, values, indices, cfg, w_shape, **_):
        return kref.xwT_ref(x, values, indices, cfg, w_shape)

    def xwT_pallas_call(x, values, indices, cfg, w_shape, *,
                        interpret, block_b=128, block_o=128, **_):
        count_scatter_slices("xwT", group_chunk(values.shape[0], cfg.m),
                             cfg.m)
        return demm_xwT_pallas(x, values, indices, cfg, block_b=block_b,
                               block_o=block_o, interpret=interpret)

    def xwT_tiles(p: Problem):
        # The kernel rounds a request down to a legal TPU tile (fit_tile),
        # so only requests that survive that rounding are distinct.
        return {
            "block_b": _legal_tiles(p.rows, 8, 512, SUBLANES),
            "block_o": _legal_tiles(p.out, 128, 512, LANES),
        }

    def xwT_defaults(p: Problem):
        return {"block_b": fit_tile(p.rows, 128, SUBLANES),
                "block_o": fit_tile(p.out, 128, LANES)}

    register_variant(KernelVariant(
        op="xwT", name="reference", call=xwT_ref_call,
        param_space=lambda p: {}, default_params=lambda p: {},
        supported=lambda p: True,
        description="pure-jnp decompress+matmul (XLA path)"))
    register_variant(KernelVariant(
        op="xwT", name="pallas",
        call=lambda *a, **kw: xwT_pallas_call(*a, interpret=False, **kw),
        param_space=xwT_tiles, default_params=xwT_defaults,
        supported=lambda p: p.platform == "tpu",
        description="fused Pallas TPU kernel"))
    register_variant(KernelVariant(
        op="xwT", name="pallas_interpret",
        call=lambda *a, **kw: xwT_pallas_call(*a, interpret=True, **kw),
        param_space=xwT_tiles, default_params=xwT_defaults,
        supported=lambda p: p.dense_flops <= _INTERPRET_FLOP_LIMIT,
        description="Pallas kernel in interpret mode (CPU checks)"))

    from repro.kernels.demm_experts import (DEFAULT_BLOCK_B,
                                            demm_experts_pallas)

    def experts_ref_call(x, group_sizes, values, indices, cfg, w_shape,
                         **_):
        return kref.experts_ref(x, group_sizes, values, indices, cfg,
                                w_shape)

    def experts_pallas_call(x, group_sizes, values, indices, cfg, w_shape,
                            *, interpret, block_b=DEFAULT_BLOCK_B, **_):
        count_scatter_slices("experts",
                             group_chunk(values.shape[1], cfg.m), cfg.m)
        return demm_experts_pallas(x, group_sizes, values, indices, cfg,
                                   block_b=block_b, interpret=interpret)

    def experts_tiles(p: Problem):
        return {"block_b": _legal_tiles(p.rows, 8, 512, SUBLANES)}

    def experts_defaults(p: Problem):
        return {"block_b": fit_tile(p.rows, DEFAULT_BLOCK_B, SUBLANES)}

    register_variant(KernelVariant(
        op="experts", name="reference", call=experts_ref_call,
        param_space=lambda p: {}, default_params=lambda p: {},
        supported=lambda p: True,
        description="every expert's dense product, each row kept from its "
                    "own expert (XLA path)"))
    register_variant(KernelVariant(
        op="experts", name="pallas",
        call=lambda *a, **kw: experts_pallas_call(*a, interpret=False, **kw),
        param_space=experts_tiles, default_params=experts_defaults,
        supported=lambda p: p.platform == "tpu",
        description="grouped Pallas TPU kernel over packed experts"))
    register_variant(KernelVariant(
        op="experts", name="pallas_interpret",
        call=lambda *a, **kw: experts_pallas_call(*a, interpret=True, **kw),
        param_space=experts_tiles, default_params=experts_defaults,
        supported=lambda p: p.dense_flops <= _INTERPRET_FLOP_LIMIT,
        description="grouped kernel in interpret mode (CPU checks)"))

    def spmm_ref_call(values, indices, b, cfg, a_shape, **_):
        return kref.spmm_ref(values, indices, b, cfg, a_shape)

    def spmm_pallas_call(values, indices, b, cfg, a_shape, *,
                         interpret, block_r=128, block_c=256, **_):
        count_scatter_slices("spmm", group_chunk(values.shape[0], cfg.m),
                             cfg.m)
        return demm_spmm_pallas(values, indices, b, cfg, block_r=block_r,
                                block_c=block_c, interpret=interpret)

    def spmm_tiles(p: Problem):
        return {
            "block_r": _legal_tiles(p.out, 128, 512, LANES),
            "block_c": _legal_tiles(p.rows, 8, 512, SUBLANES),
        }

    def spmm_defaults(p: Problem):
        return {"block_r": fit_tile(p.out, 128, LANES),
                "block_c": fit_tile(p.rows, 256, SUBLANES)}

    register_variant(KernelVariant(
        op="spmm", name="reference", call=spmm_ref_call,
        param_space=lambda p: {}, default_params=lambda p: {},
        supported=lambda p: True,
        description="pure-jnp decompress+matmul (XLA path)"))
    register_variant(KernelVariant(
        op="spmm", name="pallas",
        call=lambda *a, **kw: spmm_pallas_call(*a, interpret=False, **kw),
        param_space=spmm_tiles, default_params=spmm_defaults,
        supported=lambda p: p.platform == "tpu",
        description="fused Pallas TPU kernel"))
    register_variant(KernelVariant(
        op="spmm", name="pallas_interpret",
        call=lambda *a, **kw: spmm_pallas_call(*a, interpret=True, **kw),
        param_space=spmm_tiles, default_params=spmm_defaults,
        supported=lambda p: p.dense_flops <= _INTERPRET_FLOP_LIMIT,
        description="Pallas kernel in interpret mode (CPU checks)"))

    def spmm_block_call(values, indices, b, cfg, a_shape, *, interpret,
                        block_r=128, cd_block=256, **_):
        # Host-side repack into the two-level block-sparse format: only
        # callable on concrete arrays (measure_only), never under jit.
        import numpy as np

        from repro.core.sparsity import unpack
        from repro.kernels.demm_block_spmm import (
            demm_block_spmm_pallas, pack_block_sparse)

        r = int(a_shape[0])
        block_r = min(block_r, r)
        if r % block_r:
            raise ValueError(f"block_spmm needs r % block_r == 0, got "
                             f"{r} % {block_r}")
        dense = np.asarray(unpack(values, indices, cfg, tuple(a_shape)))
        ag, vals, idxs, _ = pack_block_sparse(dense, cfg, block_r=block_r)
        count_scatter_slices("spmm", 1, cfg.m)
        return demm_block_spmm_pallas(
            jax.numpy.asarray(ag), jax.numpy.asarray(vals),
            jax.numpy.asarray(idxs), b, cfg, r=r, cd_block=cd_block,
            interpret=interpret)

    block_spmm_params = dict(
        param_space=lambda p: {
            "block_r": tuple(c for c in _pow2_candidates(p.out, 8, 256)
                             if p.out % c == 0),
            "cd_block": tuple(c for c in _pow2_candidates(p.rows, 8, 256)
                              if p.rows % c == 0),
        },
        default_params=lambda p: {
            "block_r": max((c for c in _pow2_candidates(p.out, 8, 128)
                            if p.out % c == 0), default=p.out),
            "cd_block": max((c for c in _pow2_candidates(p.rows, 8, 256)
                             if p.rows % c == 0), default=p.rows),
        },
        measure_only=True)
    register_variant(KernelVariant(
        op="spmm", name="block_spmm",
        call=lambda *a, **kw: spmm_block_call(*a, interpret=False, **kw),
        supported=lambda p: p.platform == "tpu",
        description="scalar-prefetch block-gather kernel (host repack of the "
                    "flat spmm packing; ahead-of-time conversion dispatches "
                    "through the xwT_block op instead)", **block_spmm_params))
    register_variant(KernelVariant(
        op="spmm", name="block_spmm_interpret",
        call=lambda *a, **kw: spmm_block_call(*a, interpret=True, **kw),
        supported=lambda p: p.dense_flops <= _INTERPRET_FLOP_LIMIT,
        description="block-gather kernel in interpret mode (CPU checks)",
        **block_spmm_params))

    # ---- xwT_block: the two-level AOT block layout (serving orientation) --
    # Operands come pre-packed by core.sparsity.pack_block, so both variants
    # are dispatchable from inside a jit trace (no measure_only flag).

    def xwT_block_ref_call(x, values, indices, active_groups, cfg, w_shape,
                           **_):
        o, _k = w_shape
        return kref.block_spmm_ref(active_groups, values, indices, x.T, cfg,
                                   int(o)).T

    def xwT_block_pallas_call(x, values, indices, active_groups, cfg,
                              w_shape, *, interpret, cd_block=256, **_):
        from repro.kernels.demm_block_spmm import demm_block_xwT_pallas

        count_scatter_slices("xwT_block", 1, cfg.m)
        return demm_block_xwT_pallas(x, values, indices, active_groups, cfg,
                                     cd_block=int(cd_block),
                                     interpret=interpret)

    def xwT_block_tiles(p: Problem):
        return {"cd_block": _legal_tiles(p.rows, 8, 256, SUBLANES)}

    def xwT_block_defaults(p: Problem):
        return {"cd_block": fit_tile(p.rows, 256, SUBLANES)}

    register_variant(KernelVariant(
        op="xwT_block", name="reference", call=xwT_block_ref_call,
        param_space=lambda p: {}, default_params=lambda p: {},
        supported=lambda p: True,
        description="pure-jnp two-level scatter-add + matmul (XLA path)"))
    register_variant(KernelVariant(
        op="xwT_block", name="block_spmm",
        call=lambda *a, **kw: xwT_block_pallas_call(
            *a, interpret=False, **kw),
        param_space=xwT_block_tiles, default_params=xwT_block_defaults,
        supported=lambda p: p.platform == "tpu",
        description="scalar-prefetch block-gather Pallas kernel over the "
                    "ahead-of-time two-level packing"))
    register_variant(KernelVariant(
        op="xwT_block", name="block_spmm_interpret",
        call=lambda *a, **kw: xwT_block_pallas_call(
            *a, interpret=True, **kw),
        param_space=xwT_block_tiles, default_params=xwT_block_defaults,
        supported=lambda p: p.dense_flops <= _INTERPRET_FLOP_LIMIT,
        description="block-gather Pallas kernel in interpret mode (CPU "
                    "checks)"))

    # ---- int8 quantized ops (repro.quant): w8a16 dequant-in-register ------
    # Variant names mirror the float ops so heuristic_default's platform
    # preferences ("pallas" / "block_spmm" on TPU) apply unchanged.
    from repro.kernels.demm_q8 import (demm_block_xwT_q8_pallas,
                                       demm_xwT_q8_pallas)

    def xwT_q8_ref_call(x, values, indices, scales, cfg, w_shape, **_):
        return kref.xwT_q8_ref(x, values, indices, scales, cfg, w_shape)

    def xwT_q8_pallas_call(x, values, indices, scales, cfg, w_shape, *,
                           interpret, block_b=128, block_o=128, **_):
        count_scatter_slices("xwT_q8", group_chunk(values.shape[0], cfg.m),
                             cfg.m)
        return demm_xwT_q8_pallas(x, values, indices, scales, cfg,
                                  block_b=block_b, block_o=block_o,
                                  interpret=interpret)

    register_variant(KernelVariant(
        op="xwT_q8", name="reference", call=xwT_q8_ref_call,
        param_space=lambda p: {}, default_params=lambda p: {},
        supported=lambda p: True,
        description="pure-jnp dequantize + decompress + matmul (XLA path)"))
    register_variant(KernelVariant(
        op="xwT_q8", name="pallas",
        call=lambda *a, **kw: xwT_q8_pallas_call(*a, interpret=False, **kw),
        param_space=xwT_tiles, default_params=xwT_defaults,
        supported=lambda p: p.platform == "tpu",
        description="fused Pallas TPU kernel, int8 weights dequantized "
                    "in-register (w8a16)"))
    register_variant(KernelVariant(
        op="xwT_q8", name="pallas_interpret",
        call=lambda *a, **kw: xwT_q8_pallas_call(*a, interpret=True, **kw),
        param_space=xwT_tiles, default_params=xwT_defaults,
        supported=lambda p: p.dense_flops <= _INTERPRET_FLOP_LIMIT,
        description="int8 Pallas kernel in interpret mode (CPU checks)"))

    def xwT_block_q8_ref_call(x, values, indices, active_groups, scales,
                              cfg, w_shape, **_):
        o, _k = w_shape
        return kref.block_spmm_q8_ref(active_groups, values, indices,
                                      scales, x.T, cfg, int(o)).T

    def xwT_block_q8_pallas_call(x, values, indices, active_groups, scales,
                                 cfg, w_shape, *, interpret, cd_block=256,
                                 **_):
        count_scatter_slices("xwT_block_q8", 1, cfg.m)
        return demm_block_xwT_q8_pallas(x, values, indices, active_groups,
                                        scales, cfg, cd_block=int(cd_block),
                                        interpret=interpret)

    register_variant(KernelVariant(
        op="xwT_block_q8", name="reference", call=xwT_block_q8_ref_call,
        param_space=lambda p: {}, default_params=lambda p: {},
        supported=lambda p: True,
        description="pure-jnp dequantize + two-level scatter-add + matmul"))
    register_variant(KernelVariant(
        op="xwT_block_q8", name="block_spmm",
        call=lambda *a, **kw: xwT_block_q8_pallas_call(
            *a, interpret=False, **kw),
        param_space=xwT_block_tiles, default_params=xwT_block_defaults,
        supported=lambda p: p.platform == "tpu",
        description="scalar-prefetch block-gather Pallas kernel over the "
                    "quantized two-level packing (w8a16)"))
    register_variant(KernelVariant(
        op="xwT_block_q8", name="block_spmm_interpret",
        call=lambda *a, **kw: xwT_block_q8_pallas_call(
            *a, interpret=True, **kw),
        param_space=xwT_block_tiles, default_params=xwT_block_defaults,
        supported=lambda p: p.dense_flops <= _INTERPRET_FLOP_LIMIT,
        description="quantized block-gather Pallas kernel in interpret mode "
                    "(CPU checks)"))


_register_builtin_variants()
