"""Partitioning rules: param-path patterns → PartitionSpec.

Megatron-style TP over the 'model' axis, DP over ('pod', 'data') for the
batch, EP for expert tensors, and a head-dim fallback for archs whose KV
head count does not divide the TP degree (DESIGN.md §5).

Rules are matched on the '/'-joined param path (first match wins), so the
same rule set serves every architecture family.  ``_sparse_*`` static
metadata and scalar leaves get a fully-replicated spec.

ZeRO-1: optimizer-state specs are derived from the param specs by sharding
the largest replicated dimension over 'data' (opt_state_specs).
"""

from __future__ import annotations

import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.sparsity import LAYOUT_BLOCK, PackedWeight
from repro.core.treeutil import key_path_str as _path_str


# (regex on path, spec builder(ndim) -> PartitionSpec)
# 'M' = model axis, 'D' = data axes tuple ('pod','data') or ('data',)
#
# Rules address the *linear's* dense weight path (".../w").  Packed sparse
# weights are not matched by leaf-name regexes: PackedWeight nodes are
# handled structurally (isinstance) in ``param_specs``, which classifies the
# node's module path as col/row-parallel via the same rules and shards the
# values/indices children by their known (G, Ne, O) geometry.

def _rules():
    return [
        # embeddings / unembedding: vocab-sharded
        (r"(embed|unembed)/table", lambda nd: P("model", None)),
        # MoE expert stacks (E, out, in): EP over model
        (r"moe/(gate|up|down)/w", lambda nd: P("model", None, None)),
        (r"moe/router/w", lambda nd: P(None, None)),
        # attention projections: column-parallel q/k/v, row-parallel o
        (r"(attn|xattn)/w[qkv]/w", "col"),
        (r"(attn|xattn)/wo/w", "row"),
        # MLP: column-parallel gate/up, row-parallel down
        (r"mlp/(gate|up)/w", "col"),
        (r"mlp/down/w", "row"),
        # mamba: column-parallel in_proj, row-parallel out_proj
        (r"mamba/in_proj/w", "col"),
        (r"mamba/out_proj/w", "row"),
        (r"mamba/conv_w", lambda nd: P(None, "model")),
        (r"mamba/(A_log|D|dt_bias)", lambda nd: P("model",)),
        # xlstm blocks
        (r"(blk)/(up|wq|wk|wv|w_in)/w", "col"),
        (r"(blk)/(down)/w", "row"),
        (r"blk/w_if/w", lambda nd: P(None, None)),
        (r"blk/r$", lambda nd: P(None, None, None)),  # tiny sLSTM recurrent
        # frontends / misc projections: column-parallel
        (r"(patch_proj|frame_proj)/w", "col"),
        # norms, biases, scalars: replicated
        (r".*", lambda nd: P(*([None] * nd))),
    ]


def _col_spec(ndim: int) -> P:
    """Column-parallel: output dim (axis 0 of (out, in) weights) sharded.
    (Packed weights are placed by ``_packed_spec``.)"""
    return P(*(["model"] + [None] * (ndim - 1)))


def _row_spec(ndim: int) -> P:
    """Row-parallel: contraction dim sharded.  Dense (out, in) -> axis 1.
    (Packed weights are placed by ``_packed_spec``.)"""
    if ndim == 1:
        return P(None)
    return P(*([None, "model"] + [None] * (ndim - 2)))


def spec_for_path(path: str, ndim: int) -> P:
    for pat, builder in _rules():
        if re.search(pat, path):
            if builder == "col":
                return _col_spec(ndim)
            if builder == "row":
                return _row_spec(ndim)
            spec = builder(ndim)
            # pad/truncate to ndim
            parts = list(spec) + [None] * (ndim - len(spec))
            return P(*parts[:ndim])
    return P(*([None] * ndim))




def _stacked_offset(leaf_ndim: int, spec_ndim: int) -> int:
    """Layer-stacked params have a leading (L,) axis (or (P, n_m) for xlstm
    periods): specs shift right by the extra leading dims."""
    return leaf_ndim - spec_ndim


def _linear_kind_impl(path: str, *, attn_kv_replicated: bool = False) -> str:
    probe = path.rstrip("/") + "/w"
    if attn_kv_replicated and re.search(r"(attn|xattn)/w[kv]/w", probe):
        return "replicated"
    for pat, builder in _rules():
        if re.search(pat, probe):
            return builder if builder in ("col", "row") else "replicated"
    return "replicated"


def linear_kind(path: str, **_kw) -> str:
    """Removed — the classifier lives on the plan object."""
    raise ValueError(
        "repro.sharding.partitioning.linear_kind was removed (PR 8 "
        "deprecation); use ShardingPlan(attn_kv_replicated=...)"
        ".linear_kind(path) — the plan carries the KV policy and per-node "
        "kind overrides")


def _packed_spec(kind: str, extra: int) -> P:
    """values/indices are (*stack, G, Ne, O): column-parallel shards the
    output axis O; row-parallel shards the group axis G (groups tile the
    contraction dim, and choose_group aligned M to the shard size); stack
    dims are replicated."""
    if kind == "col":
        core = [None, None, "model"]
    elif kind == "row":
        core = ["model", None, None]
    else:
        core = [None, None, None]
    return P(*([None] * extra + core))


def _block_packed_specs(kind: str, extra: int):
    """Specs for the block layout: values/indices are
    (*stack, RB, A_max, Ne, block_r) and active_groups (*stack, RB, A_max).
    Column-parallel shards the row-block axis RB (row blocks tile the output
    dim, so each TP shard owns whole row blocks and their address streams).
    Row-parallel would shard the contraction dim, but the active-group ids
    address *global* M-groups — a *non-renumbered* row-parallel block weight
    therefore stays replicated.  To genuinely shard it, run the renumbering
    pass (``core.sparsity.shard_packed_row_parallel``, applied by
    ``ShardingPlan.renumber_params``): the shard-stacked result is handled
    structurally in :func:`packed_weight_specs` via ``pw.shard_axis``."""
    if kind == "col":
        core, ag = ["model", None, None, None], ["model", None]
    else:
        core, ag = [None] * 4, [None] * 2
    return (P(*([None] * extra + core)), P(*([None] * extra + ag)))


def _shard_stacked_specs(pw: PackedWeight) -> PackedWeight:
    """Specs for the renumbered shard-stacked form: every child carries the
    shard dim at index ``len(stack_dims)``, placed on ``pw.shard_axis`` so
    each mesh device holds exactly its locally-renumbered slice (the
    shard_map island in kernels/ops.py consumes them in place)."""
    extra = len(pw.stack_dims)
    ax = pw.shard_axis

    def spec(child):
        return P(*([None] * extra + [ax] + [None] * (child.ndim - extra - 1)))

    repl = {"values": spec(pw.values), "indices": spec(pw.indices)}
    if pw.layout == LAYOUT_BLOCK:
        repl["active_groups"] = spec(pw.active_groups)
    if pw.qdtype is not None:
        repl["scales"] = spec(pw.scales)
    return pw.replace(**repl)


def packed_weight_specs(pw: PackedWeight, kind: str) -> PackedWeight:
    """Structural PartitionSpecs for a PackedWeight node, returned in the
    same PackedWeight container so spec/sharding trees mirror the params.

    Quantized nodes (``repro.quant``) shard the ``scales`` child alongside
    ``values``: the scale axes are the value axes without Ne (per output
    row or per group for xwT, per row-block × group × row for block), so
    column-parallel shards the same output axis; row-parallel shards
    per-group xwT scales on their group axis (it tiles the contraction dim
    exactly like the values' group axis) and leaves per-row scales
    replicated (no group axis to split).

    A renumbered shard-stacked node (``pw.shard_axis`` set) is placed on its
    own shard dim regardless of ``kind`` — the renumbering pass only ever
    produces row-parallel weights, and the shard dim *is* the contraction
    partition."""
    if pw.shard_axis is not None:
        return _shard_stacked_specs(pw)
    extra = len(pw.stack_dims)
    if pw.layout == LAYOUT_BLOCK:
        spec, ag_spec = _block_packed_specs(kind, extra)
        repl = {"values": spec, "indices": spec, "active_groups": ag_spec}
        if pw.qdtype is not None:
            core = (["model", None, None] if kind == "col" else [None] * 3)
            repl["scales"] = P(*([None] * extra + core))
        return pw.replace(**repl)
    spec = _packed_spec(kind, extra)
    repl = {"values": spec, "indices": spec}
    if pw.qdtype is not None:
        per_group = (getattr(pw.scales, "ndim", extra + 1) - extra) == 2
        if per_group:
            core = {"col": [None, "model"], "row": ["model", None]}.get(
                kind, [None, None])
        else:
            core = ["model"] if kind == "col" else [None]
        repl["scales"] = P(*([None] * extra + core))
    return pw.replace(**repl)


def _is_legacy_packed(node) -> bool:
    return isinstance(node, dict) and "values" in node and "shape" in node


def _param_specs_impl(params, *, attn_kv_replicated: bool = False,
                      kind_fn=None) -> dict:
    """PartitionSpec pytree matching ``params``.

    Handles layer stacking: rule specs are defined for the *unstacked*
    2-D/3-D weights; extra leading axes (scan stacking) are replicated.
    PackedWeight nodes are handled structurally: the module path picks
    col/row-parallel and the (G, Ne, O) geometry places the axes.

    ``attn_kv_replicated``: for archs whose KV head count does not divide
    TP (but whose Q heads do), K/V projection weights are replicated so the
    projected K/V tensors need no gather (DESIGN.md §5).

    ``kind_fn`` (path -> "col" | "row" | "replicated") overrides the rule
    table for PackedWeight nodes — the hook ShardingPlan.kind_overrides
    plugs into.
    """
    if kind_fn is None:
        def kind_fn(p):
            return _linear_kind_impl(p, attn_kv_replicated=attn_kv_replicated)

    def one(path, leaf):
        p = _path_str(path)
        if isinstance(leaf, PackedWeight):
            return packed_weight_specs(leaf, kind_fn(p))
        if _is_legacy_packed(leaf):
            raise ValueError(
                f"legacy packed {{values, indices, shape}} dict at {p!r} is "
                "no longer supported; pack with launch.pack_tree to get "
                "PackedWeight nodes")
        if not hasattr(leaf, "ndim"):
            return P()  # Static metadata
        nd = leaf.ndim
        # how many leading stack dims? infer from known rule arity:
        base_nd = _base_ndim(p, nd)
        extra = nd - base_nd
        if attn_kv_replicated and re.search(r"(attn|xattn)/w[kv]/w", p):
            base = P(*([None] * base_nd))
        else:
            base = spec_for_path(p, base_nd)
        return P(*([None] * extra + list(base)))

    return jax.tree_util.tree_map_with_path(
        one, params,
        is_leaf=lambda x: isinstance(x, PackedWeight) or _is_legacy_packed(x))


def param_specs(params, **_kw) -> dict:
    """Removed — spec derivation lives on the plan object."""
    raise ValueError(
        "repro.sharding.partitioning.param_specs was removed (PR 8 "
        "deprecation); use ShardingPlan(attn_kv_replicated=...)"
        ".param_specs(params) — the plan carries the KV policy, per-node "
        "kind overrides, and the renumber policy in one serializable "
        "object")


def _base_ndim(path: str, nd: int) -> int:
    """Arity of the unstacked tensor for this path."""
    if re.search(r"moe/(gate|up|down)/w", path):
        return 3
    if re.search(r"blk/r$", path):
        return 3
    if re.search(r"conv_w", path):
        return 2
    if re.search(r"(embed|unembed)/table", path):
        return 2
    if re.search(r"/w$", path):
        return 2
    if re.search(r"(scale|bias|A_log|D$|dt_bias)", path):
        return 1
    return min(nd, 2)


def opt_state_specs(pspecs, param_shapes=None, data_degree: int = 16) -> dict:
    """ZeRO-1: shard optimizer moments over 'data' on a still-replicated
    axis whose size divides the data degree (grads are reduce-scattered onto
    the shard, updates all-gathered back — SPMD inserts both).

    ``param_shapes`` (same structure) enables divisibility checks; without
    it, only the first None axis is used unchecked (legacy behaviour)."""

    def one(spec, shape=None):
        if not isinstance(spec, P):
            return spec
        parts = list(spec)
        candidates = [i for i, s in enumerate(parts) if s is None]
        if shape is not None:
            dims = shape.shape if hasattr(shape, "shape") else shape
            candidates = [i for i in candidates
                          if i < len(dims) and dims[i] % data_degree == 0]
            # prefer the largest divisible axis (best shard balance)
            candidates.sort(key=lambda i: -dims[i])
        if candidates:
            parts[candidates[0]] = "data"
            return P(*parts)
        return spec

    if param_shapes is None:
        return jax.tree_util.tree_map(
            one, pspecs, is_leaf=lambda x: isinstance(x, P))
    flat_s, treedef = jax.tree_util.tree_flatten(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    flat_p = treedef.flatten_up_to(param_shapes)
    return treedef.unflatten([one(s, p) for s, p in zip(flat_s, flat_p)])


def shardings_for(mesh: Mesh, specs) -> dict:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else
        NamedSharding(mesh, P()),
        specs, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activation/batch specs
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh):
    """The data-parallel axes present in this mesh ('pod' included)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def batch_spec(mesh: Mesh, ndim: int, *, seq_axis: Optional[int] = None,
               seq_shard: bool = False) -> P:
    """Batch tensors: leading axis over DP axes; optionally shard a sequence
    axis over 'data' (long-context decode)."""
    parts = [batch_axes(mesh)] + [None] * (ndim - 1)
    if seq_shard and seq_axis is not None:
        parts[0] = "pod" if "pod" in mesh.axis_names else None
        parts[seq_axis] = "data"
    return P(*parts)


def cache_spec(mesh: Mesh, ndim: int, *, batch_axis: int = 1,
               head_axis: int = 3, seq_axis: int = 2,
               shard_heads: bool, seq_shard: bool = False) -> P:
    """KV caches (L, B, S, H, Dh): batch over DP, heads over model (when the
    arch's KV heads divide TP), optionally sequence over 'data'."""
    parts = [None] * ndim
    if seq_shard:
        parts[seq_axis] = "data"
        if "pod" in mesh.axis_names:
            parts[batch_axis] = "pod"
    else:
        parts[batch_axis] = batch_axes(mesh)
    if shard_heads:
        parts[head_axis] = "model"
    return P(*parts)
