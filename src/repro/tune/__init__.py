"""``repro.tune`` — kernel registry, autotuner, and dispatch cache.

The software analogue of the paper's DeMM(N, M, C, k) reconfiguration: the
engine wins by matching its datapath shape to the sparsity pattern, and the
Pallas kernels win by matching their tile shapes (``block_r``/``block_c``/
``block_b``) and backend to the (shape, dtype, N:M pattern, platform)
instance.  This package owns that choice:

  * :mod:`repro.tune.registry`  — registered kernel variants + param spaces.
  * :mod:`repro.tune.autotune`  — enumerate → VMEM/perfmodel prune → measure.
  * :mod:`repro.tune.cache`     — JSON-persistent (op, shapes, dtype,
    pattern, platform) → (backend, tiles) cache with heuristic fallback.

``kernels/ops.py`` resolves ``backend="auto"`` through
:func:`resolve_xwT` / :func:`resolve_spmm`: a pure cache/heuristic lookup on
static shapes, safe at jit-trace time.  Measurement happens only in explicit
:func:`autotune_xwT` / :func:`autotune_spmm` calls (see
``benchmarks/kernel_bench.py --autotune`` and ``launch/serve.py
--autotune``), whose results persist for later processes.
"""

from __future__ import annotations

from repro.core.sparsity import SparsityConfig
from repro.tune.autotune import (
    DEFAULT_VMEM_BUDGET,
    TuneResult,
    autotune_spmm,
    autotune_xwT,
    autotune_xwT_block,
    autotune_xwT_q8,
    enumerate_candidates,
    estimate_cycles,
    measure,
    prune_candidates,
    vmem_bytes,
)
from repro.tune.cache import (
    TuneCache,
    TunedConfig,
    default_cache,
    heuristic_default,
    problem_key,
    set_default_cache,
)
from repro.tune.registry import (
    KernelVariant,
    Problem,
    backend_names,
    current_platform,
    get_variant,
    register_variant,
    variants_for,
)

__all__ = [
    "DEFAULT_VMEM_BUDGET", "KernelVariant", "Problem", "TuneCache",
    "TuneResult", "TunedConfig", "autotune_spmm", "autotune_xwT",
    "autotune_xwT_block", "autotune_xwT_q8", "backend_names",
    "current_platform", "default_cache", "enumerate_candidates",
    "estimate_cycles", "get_variant", "heuristic_default", "measure",
    "problem_key", "prune_candidates", "register_variant", "resolve_spmm",
    "resolve_xwT", "resolve_xwT_block", "resolve_xwT_q8",
    "set_default_cache", "variants_for", "vmem_bytes",
]


def resolve_xwT(x_shape, w_shape, cfg: SparsityConfig, dtype,
                shards: int = 1) -> TunedConfig:
    """Static (backend, params) choice for ``backend="auto"`` xwT dispatch.

    Never measures: tuning-cache hit or heuristic default.  Shapes may come
    from tracers — only static metadata is consulted.  ``shards`` > 1 marks
    the shard-local problem of a renumbered row-parallel weight (distinct
    cache key from the same-shape global problem).
    """
    p = Problem.for_xwT(x_shape, w_shape, cfg, dtype, shards=shards)
    return default_cache().resolve(p)


def resolve_xwT_q8(x_shape, w_shape, cfg: SparsityConfig,
                   dtype, shards: int = 1) -> TunedConfig:
    """Static (backend, params) choice for ``backend="auto"`` dispatch of an
    int8-quantized xwT weight — its own ``xwT_q8`` cache key, so float and
    quantized tunings coexist.  Never measures."""
    p = Problem.for_xwT(x_shape, w_shape, cfg, dtype, quantized=True,
                        shards=shards)
    return default_cache().resolve(p)


def resolve_spmm(a_shape, b_shape, cfg: SparsityConfig, dtype) -> TunedConfig:
    """Static (backend, params) choice for ``backend="auto"`` spmm dispatch."""
    p = Problem.for_spmm(a_shape, b_shape, cfg, dtype)
    return default_cache().resolve(p)


def resolve_xwT_block(x_shape, pw, dtype) -> TunedConfig:
    """Static (backend, params) choice for ``backend="auto"`` dispatch of a
    block-layout :class:`~repro.core.sparsity.PackedWeight` — keyed by the
    full problem including the pack-time block geometry.  Never measures."""
    p = Problem.for_xwT_block(x_shape, pw, dtype)
    return default_cache().resolve(p)


def autotune_packed_tree(params, batch: int, dtype=None, *,
                         persist: bool = True, **tune_kw) -> dict:
    """Pre-tune every distinct packed-weight matmul shape in a param pytree.

    Walks ``params`` for :class:`~repro.core.sparsity.PackedWeight` nodes
    (as produced by ``launch.pack_tree``) and runs :func:`autotune_xwT` /
    :func:`autotune_xwT_q8` (or :func:`autotune_xwT_block`, which covers
    both float and quantized block nodes) once per distinct
    (O, K, pattern[, block geometry], qdtype) — all read from the type's
    static aux data, k-reconfiguration included — with a dummy activation
    batch of ``batch`` rows, so a subsequent jit trace with
    ``backend="auto"`` resolves every layer from measured entries instead
    of heuristics.  Returns {problem_key: TuneResult}.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sparsity import LAYOUT_BLOCK, PackedWeight, shard_slice

    dtype = dtype or jnp.float32
    seen = {}

    def tune_one(pw: PackedWeight):
        if pw.shard_axis is not None:
            # Shard-stacked row-parallel weight: what dispatches inside the
            # shard_map island is the shard-local problem (every slice has
            # identical static geometry), so tune slice 0 — its key carries
            # the shard-local k/a_max plus the |sN shard marker.
            pw = shard_slice(pw, 0)
        o, k = pw.dense_shape
        if pw.layout == LAYOUT_BLOCK:
            stack = pw.stack_dims
            if stack:   # layer-stacked: tune one slice (scan applies 2-D)
                first = (0,) * len(stack)
                pw = pw.replace(
                    values=pw.values[first], indices=pw.indices[first],
                    active_groups=pw.active_groups[first],
                    scales=(pw.scales[first] if pw.scales is not None
                            else None))
            p = Problem.for_xwT_block((batch, k), pw, dtype)
            key = problem_key(p)
            if key in seen:
                return
            x = jnp.asarray(
                np.random.default_rng(0).standard_normal((batch, k)), dtype)
            seen[key] = autotune_xwT_block(x, pw, persist=persist, **tune_kw)
            return
        quant = pw.qdtype is not None
        vals, idxs, scls = pw.values, pw.indices, pw.scales
        nstack = len(pw.stack_dims)
        if nstack:   # layer-stacked: tune one slice
            vals = vals.reshape(-1, *vals.shape[nstack:])[0]
            idxs = idxs.reshape(-1, *idxs.shape[nstack:])[0]
            if quant:
                scls = scls.reshape(-1, *scls.shape[nstack:])[0]
        p = Problem.for_xwT((batch, k), (o, k), pw.cfg, dtype,
                            quantized=quant, shards=pw.shards)
        key = problem_key(p)
        if key in seen:
            return
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((batch, k)), dtype)
        if quant:
            seen[key] = autotune_xwT_q8(x, vals, idxs, scls, pw.cfg, (o, k),
                                        persist=persist, **tune_kw)
        else:
            seen[key] = autotune_xwT(x, vals, idxs, pw.cfg, (o, k),
                                     persist=persist, **tune_kw)

    def visit(node):
        if isinstance(node, PackedWeight):
            tune_one(node)
        elif isinstance(node, dict):
            if "values" in node and "shape" in node:
                raise ValueError(
                    "legacy packed {values, indices, shape} dicts are no "
                    "longer supported; pack with launch.pack_tree to get "
                    "PackedWeight nodes")
            for v in node.values():
                visit(v)

    visit(params)
    return seen
