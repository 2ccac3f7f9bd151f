"""Mixture-of-Experts: a softmax router over E experts, top-k routing, and
SwiGLU experts.

The experts of a layer are stacked linears ``gate`` and ``up`` (``w`` of
shape ``(E, F, D)``) and ``down`` (``(E, D, F)``), pruned to the arch's
N:M pattern like every linear of the sparse scope; ``launch.pack_tree``
packs each stack into one :class:`~repro.core.sparsity.PackedWeight` with
a leading expert axis.  The router is a dense float32 linear ``(E, D)``.

Two dispatches:

* **dropless** (``capacity=None``; what ``decode_step`` and
  ``prefill_chunk`` call): softmax over all E, top-k (renormalised only
  when ``norm_topk_prob``), the N·k assignments sorted by expert with
  group sizes from a segment sum, every expert's rows through one grouped
  matmul (the ``demm_experts`` kernel for packed stacks), then each
  token's k outputs gathered back and summed with their weights.  No
  token is dropped, so a token's output does not depend on its batch.
* **capacity** (an explicit ``capacity=``, or :data:`BY_FACTOR` for
  C = tokens·k/E · capacity_factor; training): sort-based gather/scatter
  dispatch into an ``(E, C, D)`` buffer, NOT the one-hot dispatch-einsum,
  so the compiled FLOPs stay ≈ tokens × top_k × expert_FFN (DESIGN.md §6).
  Tokens past an expert's capacity are dropped (their residual passes
  through), the standard GShard/Switch behaviour.

Expert parallelism (an active sharding context whose TP divides E): the
capacity dispatch under ``shard_map``, expert stacks sharded over the
'model' axis on axis 0 and one all-gather returning the outputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig, choose_group
from repro.core import sparse_linear as sl
from repro.core.pruning import masked_weight
from repro.core.sparsity import PackedWeight, Static, unpack
from repro.models.layers import init_linear

# capacity=BY_FACTOR: C from ``capacity_factor`` and the tokens each shard
# routes (the training dispatch)
BY_FACTOR = "capacity_factor"

HIGHEST = jax.lax.Precision.HIGHEST


def _expert_stack(key, e: int, in_f: int, out_f: int, sparse, dtype):
    """``e`` linears ``(out_f, in_f)`` stacked on a leading expert axis.
    The N:M group is fitted to the whole contraction dim: an expert stack
    is sharded over its expert axis, never over K, so groups need no
    alignment to tensor-parallel shards."""
    keys = jax.random.split(key, e)
    if sparse is None:
        return {"w": jax.vmap(
            lambda k: init_linear(k, in_f, out_f, sparse=None,
                                  dtype=dtype)["w"])(keys)}
    cfg = choose_group(in_f, sparse.density, sparse.m)
    w = jax.vmap(lambda k: sl.init_sparse(k, in_f, out_f, cfg, dtype)["w"])(
        keys)
    return {"w": w, "sparsity": Static(cfg)}


def init_moe(key, d: int, cfg: MoEConfig, *, sparse=None, dtype=jnp.float32):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    e, f = cfg.num_experts, cfg.d_ff_expert
    return {
        "router": init_linear(kr, d, e, sparse=None, dtype=dtype),
        "gate": _expert_stack(k1, e, d, f, sparse, dtype),
        "up": _expert_stack(k2, e, d, f, sparse, dtype),
        "down": _expert_stack(k3, e, f, d, sparse, dtype),
    }


def apply_moe(params, x, cfg: MoEConfig, *, policy=None, capacity=None,
              stats: bool = False):
    """x: (B, T, D) -> (y (B, T, D), aux_loss scalar), and with ``stats``
    a third output: int32 ``[assignments routed, experts with a row]``.

    ``capacity=None`` runs the dropless dispatch; an int or
    :data:`BY_FACTOR` the capacity dispatch.  With an active sharding
    context (TP dividing E), dispatch runs under shard_map: routing and
    scatter are local per data shard; each model rank slices its experts
    from the (replicated-over-model) buffer, computes its expert FFNs, and
    one all-gather over 'model' returns the outputs (DESIGN.md §5 EP).
    """
    from repro.sharding import context as shctx

    policy = sl.resolve_policy(policy)
    ctx = shctx.get_context()
    if ctx is not None and cfg.num_experts % ctx.tp == 0:
        y, aux, st = _apply_moe_ep(params, x, cfg, ctx, policy=policy,
                                   capacity=None if capacity == BY_FACTOR
                                   else capacity)
        return (y, aux, st) if stats else (y, aux)
    if capacity is None:
        y, aux, st = _apply_moe_dropless(params, x, cfg, policy=policy)
        return (y, aux, st) if stats else (y, aux)
    y, aux, top_e = _apply_moe_capacity(params, x, cfg, policy=policy,
                                        capacity=capacity)
    return (y, aux, route_stats(top_e, cfg.num_experts)) if stats \
        else (y, aux)


def _route(params, xf, cfg: MoEConfig):
    """(top-k weights (N, k), top-k experts (N, k), probs (N, E)): the
    float32 router, softmax over all E, the top k, renormalised only when
    ``norm_topk_prob``."""
    logits = jnp.einsum("nd,ed->ne", xf.astype(jnp.float32),
                        params["router"]["w"].astype(jnp.float32),
                        precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                  # (N, E)
    gate_vals, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals, top_e, probs


def _aux_loss(probs, top_e, cfg: MoEConfig):
    """Switch load-balancing loss over the first choice of each token."""
    e = cfg.num_experts
    ce = jax.nn.one_hot(top_e[:, 0], e).mean(0)
    return cfg.router_aux_coef * e * jnp.sum(probs.mean(0) * ce)


def group_sizes(flat_e, e: int):
    """Rows routed to each expert, by a segment sum (no one-hot)."""
    return jax.ops.segment_sum(jnp.ones_like(flat_e), flat_e,
                               num_segments=e)


def _counts(sizes):
    """int32 ``[assignments routed, experts that received a row]`` from
    the rows routed to each expert."""
    return jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(jnp.int32)


def route_stats(top_e, e: int):
    """:func:`_counts` of a routing ``top_e`` (N, k)."""
    return _counts(group_sizes(top_e.reshape(-1), e))


def _dense_stack(node, policy):
    """An expert stack as dense ``(E, O, K)`` weights (masked to the N:M
    pattern unless the policy runs dense)."""
    if isinstance(node, PackedWeight):
        return jax.vmap(lambda v, i: unpack(v, i, node.cfg,
                                            node.dense_shape))(
            node.values, node.indices)
    w = node["w"]
    cfg = sl.node_sparsity(node)
    if cfg is None or policy.mode == "dense":
        return w
    cfg = policy.resolve_cfg(cfg)
    return jax.vmap(lambda wi: masked_weight(wi, cfg))(w)


def _grouped(node, xs, sizes, policy):
    """Rows ``xs`` sorted by expert (``sizes[e]`` rows of expert e) through
    their experts' linears: the packed grouped kernel for a packed stack,
    ``lax.ragged_dot`` for dense weights."""
    if isinstance(node, PackedWeight):
        from repro.kernels import ops

        return ops.demm_experts_packed(xs, sizes, node,
                                       backend=policy.backend)
    w = _dense_stack(node, policy)
    return jax.lax.ragged_dot(xs, w.swapaxes(1, 2).astype(xs.dtype), sizes,
                              preferred_element_type=jnp.float32)


def _apply_moe_dropless(params, x, cfg: MoEConfig, *, policy):
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    xf = x.reshape(b * t, d)
    gate_vals, top_e, probs = _route(params, xf, cfg)
    aux = _aux_loss(probs, top_e, cfg)

    flat_e = top_e.reshape(-1)                                # (N*k,)
    order = jnp.argsort(flat_e, stable=True)
    sizes = group_sizes(flat_e, e)
    xs = xf[order // k]                                       # sorted rows
    g = _grouped(params["gate"], xs, sizes, policy)
    u = _grouped(params["up"], xs, sizes, policy)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u.astype(x.dtype)
    out = _grouped(params["down"], h, sizes, policy)          # (N*k, D)
    # each token's k outputs, back in top-k order, weighted and summed
    back = out[jnp.argsort(order)].reshape(b * t, k, d)
    y = jnp.einsum("nkd,nk->nd", back.astype(jnp.float32), gate_vals,
                   precision=HIGHEST)
    return y.reshape(b, t, d).astype(x.dtype), aux, _counts(sizes)


def _apply_moe_capacity(params, x, cfg: MoEConfig, *, policy, capacity):
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    n_tok = b * t
    xf = x.reshape(n_tok, d)
    gate_vals, top_e, probs = _route(params, xf, cfg)
    aux = _aux_loss(probs, top_e, cfg)

    # ---- sort-based dispatch ----
    if capacity == BY_FACTOR:
        capacity = int(cfg.capacity_factor * n_tok * k / e) or 1
    flat_e = top_e.reshape(-1)                                # (N*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(n_tok), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sg, stok = flat_e[order], flat_gate[order], flat_tok[order]
    # position within expert: rank among same-expert entries
    sizes = group_sizes(flat_e, e)
    pos = jnp.arange(se.shape[0]) - (jnp.cumsum(sizes) - sizes)[se]
    keep = pos < capacity
    slot = jnp.where(keep, se * capacity + pos, e * capacity)  # overflow slot

    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[stok].astype(x.dtype))          # drop overflow
    buf = buf[:-1].reshape(e, capacity, d)

    # ---- expert FFN (E-sharded einsums; all-to-all at the boundaries) ----
    def ffn(node, a):
        return jnp.einsum("ecd,efd->ecf", a,
                          _dense_stack(node, policy).astype(x.dtype))

    g = ffn(params["gate"], buf)
    u = ffn(params["up"], buf)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    out = ffn(params["down"], h)

    # ---- return scatter + weighted combine ----
    out_flat = out.reshape(e * capacity, d)
    gathered = jnp.where(keep[:, None],
                         out_flat[jnp.minimum(slot, e * capacity - 1)],
                         jnp.zeros((1, d), x.dtype))
    y = jnp.zeros((n_tok, d), jnp.float32)
    y = y.at[stok].add(gathered.astype(jnp.float32) * sg[:, None])
    return y.reshape(b, t, d).astype(x.dtype), aux, top_e


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map over the active mesh)
# ---------------------------------------------------------------------------

def _apply_moe_ep(params, x, cfg: MoEConfig, ctx, *, policy, capacity):
    from jax.sharding import PartitionSpec as P

    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    dp = ctx.batch_axes
    dp_deg = ctx.dp_degree()
    tp = ctx.tp
    n_local = max(1, (b // dp_deg)) * t
    if capacity is None:
        capacity = max(1, int(cfg.capacity_factor * n_local * k / e))
    e_local = e // tp

    def local_fn(router_w, w_gate, w_up, w_down, x_loc):
        bl, tl, _ = x_loc.shape
        n_tok = bl * tl
        xf = x_loc.reshape(n_tok, d)
        gate_vals, top_e, probs = _route({"router": {"w": router_w}}, xf,
                                         cfg)
        aux = jax.lax.pmean(_aux_loss(probs, top_e, cfg), dp[-1])
        # the whole batch's routing: every data shard's rows per expert
        counts = _counts(jax.lax.psum(group_sizes(top_e.reshape(-1), e),
                                      dp))

        flat_e = top_e.reshape(-1)
        flat_gate = gate_vals.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(n_tok), k)
        order = jnp.argsort(flat_e, stable=True)
        se, sg, stok = flat_e[order], flat_gate[order], flat_tok[order]
        same = jax.nn.one_hot(se, e, dtype=jnp.int32)
        pos = (jnp.cumsum(same, axis=0) - 1)[jnp.arange(se.shape[0]), se]
        keep = pos < capacity
        slot = jnp.where(keep, se * capacity + pos, e * capacity)

        buf = jnp.zeros((e * capacity + 1, d), x_loc.dtype)
        buf = buf.at[slot].set(xf[stok].astype(x_loc.dtype))
        buf = buf[:-1].reshape(e, capacity, d)

        # my experts' slice (buffer is replicated over 'model': free slice)
        rank = jax.lax.axis_index("model")
        my = jax.lax.dynamic_slice_in_dim(buf, rank * e_local, e_local, 0)
        g = jnp.einsum("ecd,efd->ecf", my, w_gate.astype(x_loc.dtype))
        u = jnp.einsum("ecd,efd->ecf", my, w_up.astype(x_loc.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * u
        out_loc = jnp.einsum("ecf,edf->ecd", h, w_down.astype(x_loc.dtype))
        # gather every rank's expert outputs: (E, C, D) on all model ranks
        out = jax.lax.all_gather(out_loc, "model", axis=0, tiled=True)

        out_flat = out.reshape(e * capacity, d)
        gathered = jnp.where(
            keep[:, None],
            out_flat[jnp.minimum(slot, e * capacity - 1)],
            jnp.zeros((1, d), x_loc.dtype))
        y = jnp.zeros((n_tok, d), jnp.float32)
        y = y.at[stok].add(gathered.astype(jnp.float32) * sg[:, None])
        return y.reshape(bl, tl, d).astype(x_loc.dtype), aux, counts

    return jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(dp, None, None)),
        out_specs=(P(dp, None, None), P(), P()),
        check_vma=False,
    )(params["router"]["w"], _dense_stack(params["gate"], policy),
      _dense_stack(params["up"], policy), _dense_stack(params["down"], policy),
      x)
