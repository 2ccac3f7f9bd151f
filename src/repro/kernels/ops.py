"""jit'd public ops over the DeMM kernels, with sparse-aware gradients.

Backend dispatch routes through the ``repro.tune`` kernel registry:

  * ``reference``        — pure-jnp decompress+matmul (XLA path; used inside
                           distributed jit steps and on CPU).
  * ``pallas``           — the Pallas TPU kernel (real hardware).
  * ``pallas_interpret`` — the Pallas kernel in interpret mode (CPU checks).
  * ``auto``             — resolve (backend, tile params) per problem from
                           the tuning cache (populated by
                           ``benchmarks/kernel_bench.py --autotune`` or
                           ``repro.tune.autotune_*``), falling back to a
                           platform heuristic.  Resolution is a static
                           shape-keyed lookup, safe under jit tracing.

New variants registered via ``repro.tune.register_variant`` become valid
backend strings here with no further changes.

Gradients:
  dL/dx       = dy @ W_dense
  dL/dvalues  = gather of (dyᵀ x) at the packed index positions — i.e. the
                gradient of a sparse weight exists only at its non-zero
                coordinates, which is what keeps DeMM serving and sparse
                fine-tuning consistent.
  indices / active_groups are non-differentiable.

The ``xwT`` custom_vjp lives here; the ``xwT_block`` / ``xwT_q8`` /
``xwT_block_q8`` ops route through ``repro.sparsetrain.vjp`` (dequant-and-
scatter backward through the jnp references), so ``jax.grad`` through
``ExecPolicy(mode="packed")`` is legal for every layout (DESIGN.md §11).

Observability (``repro.obs``, DESIGN.md §12): every dispatch increments a
``kernel_dispatch_total{op, backend}`` counter on the default registry and
runs the selected variant under an ``obs.annotate("demm/<op>/<backend>")``
scope.  Dispatch happens at jit-trace time, so the counters audit *which
variant each traced matmul resolved to* (making ``backend="auto"``
decisions inspectable) at zero steady-state cost, and the named scopes make
the lowered Pallas kernels show up named in TensorBoard/perfetto traces
(``obs.profile``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sparsity import (
    LAYOUT_BLOCK,
    LAYOUT_XWT,
    LAYOUTS,
    PackedWeight,
    SparsityConfig,
    unpack,
)

# Baseline backends always registered; `repro.tune.backend_names("xwT")` has
# the live list (plus "auto", resolved through the tuning cache).
BACKENDS = ("reference", "pallas", "pallas_interpret", "auto")


def _count_dispatch(op: str, backend: str):
    """Trace-time dispatch audit: counter plus a ``kernel_dispatch`` trace
    event.  Fires while jax traces the enclosing jit, i.e. inside whatever
    :mod:`repro.obs.context` the caller entered — under a serving engine
    the event inherits the dispatching request's ``trace_id``, correlating
    kernel compiles to the request that triggered them (DESIGN.md §16)."""
    from repro import obs

    m = obs.metrics()
    m.counter(
        "kernel_dispatch_total",
        help="DeMM matmul dispatches per (registry op, resolved backend)",
        op=op, backend=backend).inc()
    m.trace.event("kernel_dispatch", op=op, backend=backend)


def demm_matmul_packed(x: jax.Array, pw: PackedWeight,
                       backend: str = "reference") -> jax.Array:
    """y = x @ W^T for a first-class :class:`PackedWeight`.

    The layout tag picks the op: ``xwT`` weights run the row-packed DeMM
    matmul, ``block`` weights (two-level ahead-of-time packing from
    ``core.sparsity.pack_block``) run the scalar-prefetch block-spmm family.
    A quantized node (``pw.qdtype`` set, see ``repro.quant``) routes to the
    ``xwT_q8`` / ``xwT_block_q8`` twins, whose kernels dequantize the int8
    values in-register (w8a16); under ``jax.grad`` the quantized ops
    propagate exact dx (through the dequantized weight) and dL/dscales,
    while the int8 values stay non-differentiable — fine-tune values on the
    float packed form and re-quantize (``repro.sparsetrain``).
    The sparsity config (including k-reconfiguration), dense shape, block
    geometry, and qdtype come from the type's static aux data, so call
    sites never re-derive them from loose dict keys.  ``pw`` must be
    unstacked — scan bodies slice the layer axis off stacked weights before
    applying.

    A shard-stacked weight (``pw.shard_axis`` set — the renumbered
    row-parallel form from ``core.sparsity.shard_packed_row_parallel``)
    routes to the shard_map island: each mesh device runs the kernel on its
    local slice and K-chunk of ``x`` and the partial products are combined
    with ``psum``.  Without a matching mesh (single device, tests) the same
    math runs as a sequential sum over slices.
    """
    if getattr(pw, "tier_ne", None) is not None:
        # Draft-tier view (repro.spec): the params tree aliases the full
        # tier's buffers and only this static tag differs; the trace-time
        # slice narrows the address stream to the magnitude-top prefix
        # (tier_sort_packed invariant) before any dispatch decision — a
        # shard-stacked draft weight therefore keeps the single psum island
        # of its full-tier twin.
        from repro.core.sparsity import narrow_tier
        return demm_matmul_packed(x, narrow_tier(pw), backend)
    if getattr(pw, "shard_axis", None) is not None:
        return _demm_matmul_sharded(x, pw, backend)
    from repro.sharding import context as shctx

    ctx = shctx.get_context()
    if ctx is not None and ctx.tp > 1:
        return _demm_matmul_out_sharded(x, pw, backend, ctx)
    if pw.layout == LAYOUT_BLOCK:
        if getattr(pw.values, "ndim", 4) != 4:
            raise ValueError(
                f"demm_matmul_packed needs an unstacked (RB, A_max, Ne, "
                f"block_r) block weight, got values of shape "
                f"{pw.values.shape}")
        return demm_matmul_block(x, pw, backend)
    if pw.layout != LAYOUT_XWT:
        raise ValueError(
            f"unknown PackedWeight layout {pw.layout!r}; known layouts: "
            f"{LAYOUTS}")
    if getattr(pw.values, "ndim", 3) != 3:
        raise ValueError(
            f"demm_matmul_packed needs an unstacked (G, Ne, O) weight, got "
            f"values of shape {pw.values.shape}; slice the stack axis first")
    if pw.qdtype is not None:
        return demm_matmul_xwT_q8(x, pw.values, pw.indices, pw.scales,
                                  pw.cfg, pw.dense_shape, backend, pw.shards)
    return demm_matmul_xwT(x, pw.values, pw.indices, pw.cfg, pw.dense_shape,
                           backend, pw.shards)


def demm_experts_packed(x: jax.Array, group_sizes: jax.Array,
                        pw: PackedWeight,
                        backend: str = "reference") -> jax.Array:
    """y[r] = x[r] @ W_{e(r)}ᵀ for the rows of ``x`` sorted by expert
    (``group_sizes[e]`` consecutive rows for expert ``e``) over an
    unstacked expert stack ``pw`` (values ``(E, G, Ne, O)``, the float
    ``xwT`` layout).  Dispatch routes through the ``experts`` op of the
    ``repro.tune`` registry; the serving path only (no custom_vjp)."""
    from repro import obs, tune

    if (pw.layout != LAYOUT_XWT or pw.qdtype is not None
            or getattr(pw.values, "ndim", 4) != 4):
        raise ValueError(
            f"demm_experts_packed needs a float xwT expert stack of values "
            f"(E, G, Ne, O), got a {pw.layout} weight of values "
            f"{pw.values.shape}")
    params = {}
    if backend == "auto":
        choice = tune.resolve_experts(x.shape, pw, x.dtype)
        backend, params = choice.backend, choice.params
    variant = tune.get_variant("experts", backend)
    _count_dispatch("experts", backend)
    with obs.annotate(f"demm/experts/{backend}"):
        return variant.call(x, group_sizes, pw.values, pw.indices, pw.cfg,
                            tuple(pw.dense_shape), **params)


def _demm_matmul_sharded(x: jax.Array, pw: PackedWeight,
                         backend: str = "reference") -> jax.Array:
    """y = x @ W^T over a shard-stacked row-parallel weight.

    With a :class:`~repro.sharding.context.ShardingContext` whose mesh
    carries ``pw.shard_axis`` at size ``pw.shards``, this is the shard_map
    island: ``x`` is split along K (spec ``P(None, axis)``), every child of
    ``pw`` along its shard dim (spec ``P(axis)``), each device dispatches
    the ordinary packed kernel on its locally-renumbered slice, and partial
    products are ``psum``-combined.  Otherwise (single-device tests, meshes
    without the axis) the identical math runs as a sequential
    sum-over-slices, so outputs are bitwise-comparable across the two paths
    up to float summation order.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.sparsity import shard_slice
    from repro.sharding import context as shctx

    if x.ndim != 2:
        raise ValueError(f"sharded packed matmul needs 2-D x, got {x.shape}")
    axis, s_count = pw.shard_axis, pw.shards
    if len(pw.stack_dims):
        raise ValueError(
            f"demm_matmul_packed needs an unstacked shard-stacked weight, "
            f"got values of shape {pw.values.shape}; slice the stack axis "
            f"first")
    k_local = pw.in_features // s_count
    ctx = shctx.get_context()
    mesh = getattr(ctx, "mesh", None)
    if (mesh is None or axis not in mesh.shape
            or int(mesh.shape[axis]) != s_count):
        # No matching mesh: same partial-product math, sequentially.
        parts = [
            demm_matmul_packed(
                jax.lax.slice_in_dim(x, s * k_local, (s + 1) * k_local,
                                     axis=1),
                shard_slice(pw, s), backend)
            for s in range(s_count)
        ]
        return functools.reduce(jnp.add, parts)

    children, treedef = jax.tree_util.tree_flatten(pw)

    def local_fn(xl, *cl):
        pw_local = shard_slice(jax.tree_util.tree_unflatten(treedef, cl), 0)
        with shctx.suspend():
            y = demm_matmul_packed(xl, pw_local, backend)
        return jax.lax.psum(y, axis)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(None, axis),) + (P(axis),) * len(children),
                       out_specs=P(None, None), check_vma=False)
    return fn(x, *children)


def _demm_matmul_out_sharded(x: jax.Array, pw: PackedWeight, backend: str,
                             ctx) -> jax.Array:
    """y = x @ W^T under a tensor-parallel mesh, for a weight that is not
    shard-stacked: a shard_map island that splits the output dim (O for
    ``xwT``, the row-block axis for ``block``) over the ``model`` axis —
    the placement ``ShardingPlan`` gives column-parallel weights — and runs
    the kernel on each device's local operands.  The compiler cannot
    partition a Pallas kernel, so no packed matmul is left to it under a
    TP mesh.  A weight placed otherwise is resliced at the island's edge
    (a local slice, for a replicated one), so the result never depends on
    placement; a weight whose output dim does not divide TP runs whole on
    every device.  Activation rows stay split over the batch axes."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import context as shctx

    block = pw.layout == LAYOUT_BLOCK
    units = pw.values.shape[0] if block else pw.out_features
    split = "model" if units % ctx.tp == 0 else None
    dp = ctx.dp_degree()
    rows = ctx.batch_axes if dp > 1 and x.shape[0] % dp == 0 else None
    children, treedef = jax.tree_util.tree_flatten(pw)
    # block children lead with the row-block axis, xwT children end in O
    specs = [P(split) if block else P(*[None] * (c.ndim - 1), split)
             for c in children]
    o, k = pw.dense_shape
    local = (o // ctx.tp if split else o, k)

    def local_fn(xl, *cl):
        pw_local = jax.tree_util.tree_unflatten(treedef, cl).replace(
            dense_shape=local)
        with shctx.suspend():
            return demm_matmul_packed(xl, pw_local, backend)

    fn = jax.shard_map(local_fn, mesh=ctx.mesh,
                       in_specs=(P(rows, None), *specs),
                       out_specs=P(rows, split), check_vma=False)
    return fn(x, *children)


def demm_matmul_block(x: jax.Array, pw: PackedWeight,
                      backend: str = "reference") -> jax.Array:
    """y = x @ W^T for a ``block``-layout :class:`PackedWeight`.

    The two-level kernel computes the paper orientation C = A_sparse @ B, so
    the serving matmul is evaluated as ``(W_block @ x^T)^T`` with the
    active-group address stream gating which xᵀ blocks are touched at all.
    Dispatch routes through the ``xwT_block`` op of the ``repro.tune``
    registry (``xwT_block_q8`` for a quantized node); ``backend="auto"``
    resolves per (shape, dtype, pattern, block geometry, platform) through
    the tuning cache.  Both ops carry a custom_vjp
    (``repro.sparsetrain.vjp``), so this path is legal inside ``jax.grad``.
    """
    from repro import obs, tune
    from repro.sparsetrain import vjp as st_vjp

    params = {}
    if backend == "auto":
        choice = tune.resolve_xwT_block(x.shape, pw, x.dtype)
        backend, params = choice.backend, choice.params
    ptuple = tuple(sorted(params.items()))
    op = "xwT_block_q8" if pw.qdtype is not None else "xwT_block"
    _count_dispatch(op, backend)
    with obs.annotate(f"demm/{op}/{backend}"):
        if pw.qdtype is not None:
            return st_vjp.xwT_block_q8_grad(x, pw.values, pw.indices,
                                            pw.active_groups, pw.scales,
                                            pw.cfg, tuple(pw.dense_shape),
                                            backend, ptuple)
        return st_vjp.xwT_block_grad(x, pw.values, pw.indices,
                                     pw.active_groups, pw.cfg,
                                     tuple(pw.dense_shape), backend, ptuple)


def _dispatch_xwT(x, values, indices, cfg, w_shape, backend, shards=1):
    from repro import obs, tune

    params = {}
    if backend == "auto":
        choice = tune.resolve_xwT(x.shape, w_shape, cfg, x.dtype, shards)
        backend, params = choice.backend, choice.params
    variant = tune.get_variant("xwT", backend)
    _count_dispatch("xwT", backend)
    with obs.annotate(f"demm/xwT/{backend}"):
        return variant.call(x, values, indices, cfg, tuple(w_shape),
                            **params)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def demm_matmul_xwT(x, values, indices, cfg: SparsityConfig, w_shape,
                    backend: str = "reference", shards: int = 1):
    """y = x @ W_sparseᵀ; x (B, K), W packed (G, Ne, O) for dense (O, K).
    ``shards`` > 1 tags the shard-local problem of a renumbered row-parallel
    weight for ``backend="auto"`` cache keying; the math is unchanged."""
    return _dispatch_xwT(x, values, indices, cfg, w_shape, backend, shards)


def _xwT_fwd(x, values, indices, cfg, w_shape, backend, shards=1):
    y = _dispatch_xwT(x, values, indices, cfg, w_shape, backend, shards)
    return y, (x, values, indices)


def _xwT_bwd(cfg, w_shape, backend, shards, res, dy):
    from repro.sparsetrain.vjp import gather_xwT_slots

    x, values, indices = res
    o, k = w_shape
    w = unpack(values, indices, cfg, (o, k))                 # (O, K)
    dx = jnp.dot(dy, w.astype(dy.dtype))                      # (B, K)
    # dW = dyᵀ @ x, needed only at the packed coordinates.
    dw = jnp.dot(dy.T.astype(jnp.float32), x.astype(jnp.float32))  # (O, K)
    dvalues = gather_xwT_slots(dw, indices, cfg.m).astype(values.dtype)
    # Padded slots (value 0 at index 0) must not accumulate gradient, or they
    # would densify the pattern.
    dvalues = jnp.where(values != 0, dvalues, jnp.zeros((), values.dtype))
    return dx.astype(x.dtype), dvalues, None


demm_matmul_xwT.defvjp(_xwT_fwd, _xwT_bwd)


def demm_matmul_xwT_q8(x, values, indices, scales, cfg: SparsityConfig,
                       w_shape, backend: str = "reference", shards: int = 1):
    """y = x @ W_q8ᵀ; int8 values (G, Ne, O) + scales (O,) per output row or
    (G, O) per group (``repro.quant`` granularities).

    Carries a custom_vjp (``repro.sparsetrain.vjp``): dx and dL/dscales are
    exact; the int8 values are not a differentiable parameterization —
    fine-tune values on the float packed form and re-quantize with
    ``repro.quant.quantize_packed``.
    """
    from repro import obs, tune
    from repro.sparsetrain import vjp as st_vjp

    params = {}
    if backend == "auto":
        choice = tune.resolve_xwT_q8(x.shape, w_shape, cfg, x.dtype, shards)
        backend, params = choice.backend, choice.params
    _count_dispatch("xwT_q8", backend)
    with obs.annotate(f"demm/xwT_q8/{backend}"):
        return st_vjp.xwT_q8_grad(x, values, indices, scales, cfg,
                                  tuple(w_shape), backend,
                                  tuple(sorted(params.items())))


def demm_spmm(values, indices, b, cfg: SparsityConfig, a_shape,
              backend: str = "reference"):
    """C = A_sparse @ B (paper orientation)."""
    from repro import obs, tune

    params = {}
    if backend == "auto":
        choice = tune.resolve_spmm(a_shape, b.shape, cfg, b.dtype)
        backend, params = choice.backend, choice.params
    variant = tune.get_variant("spmm", backend)
    if variant.measure_only:
        raise ValueError(
            f"backend {backend!r} is measure-only (host repacking); use it "
            "through repro.tune.autotune_spmm or call its kernel directly")
    _count_dispatch("spmm", backend)
    with obs.annotate(f"demm/spmm/{backend}"):
        return variant.call(values, indices, b, cfg, tuple(a_shape),
                            **params)
