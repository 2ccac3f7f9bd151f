"""Mixture-of-Experts with sort-based (dropping) token dispatch.

Dispatch is gather/scatter-based — NOT the one-hot dispatch-einsum — so the
compiled FLOPs stay ≈ tokens × top_k × expert_FFN (the dispatch einsum is
O(tokens² · top_k · d) and would destroy the MODEL_FLOPS/HLO ratio; see
DESIGN.md §6).

Expert parallelism: expert weight tensors are (E, ...) sharded over the
'model' mesh axis.  Under jit/SPMD the gather into the (E, C, D) buffer and
the return scatter lower to all-to-alls over 'model'.  Tokens beyond an
expert's capacity C = tokens·top_k/E · capacity_factor are dropped (their
residual passes through), the standard GShard/Switch behaviour.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core.sparse_linear import DENSE_POLICY
from repro.models.layers import apply_linear, init_linear


def init_moe(key, d: int, cfg: MoEConfig, *, sparse=None, dtype=jnp.float32):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    e, f = cfg.num_experts, cfg.d_ff_expert
    scale_in = d ** -0.5
    scale_out = f ** -0.5
    return {
        "router": init_linear(kr, d, e, sparse=None, dtype=dtype),
        # expert weights: (E, in, out) — sharded over 'model' on axis 0
        "w_gate": jax.random.normal(k1, (e, d, f), dtype) * scale_in,
        "w_up": jax.random.normal(k2, (e, d, f), dtype) * scale_in,
        "w_down": jax.random.normal(k3, (e, f, d), dtype) * scale_out,
    }


def apply_moe(params, x, cfg: MoEConfig, *, policy=None, capacity: int | None = None):
    """x: (B, T, D) -> (y (B, T, D), aux_loss scalar).

    With an active sharding context, dispatch runs under shard_map: routing
    and scatter are local per data shard; each model rank slices its experts
    from the (replicated-over-model) buffer, computes its expert FFNs, and
    one all-gather over 'model' returns the outputs (DESIGN.md §5 EP).
    """
    from repro.sharding import context as shctx

    ctx = shctx.get_context()
    if ctx is not None and cfg.num_experts % ctx.tp == 0:
        return _apply_moe_ep(params, x, cfg, ctx, policy=policy,
                             capacity=capacity)
    return _apply_moe_local(params, x, cfg, policy=policy,
                            capacity=capacity)


def _apply_moe_local(params, x, cfg: MoEConfig, *, policy=None, capacity: int | None = None):
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    n_tok = b * t
    xf = x.reshape(n_tok, d)

    logits = apply_linear(params["router"], xf, DENSE_POLICY).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (N, E)
    gate_vals, top_e = jax.lax.top_k(probs, k)               # (N, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)              # renormalize

    # ---- load-balancing auxiliary loss (Switch) ----
    me = probs.mean(0)                                        # (E,)
    one_hot_top = jax.nn.one_hot(top_e[:, 0], e)
    ce = one_hot_top.mean(0)
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    if capacity is None:
        capacity = int(cfg.capacity_factor * n_tok * k / e) or 1
    flat_e = top_e.reshape(-1)                                # (N*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(n_tok), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sg, stok = flat_e[order], flat_gate[order], flat_tok[order]
    # position within expert: rank among same-expert entries
    same = jax.nn.one_hot(se, e, dtype=jnp.int32)             # (N*k, E)
    pos = (jnp.cumsum(same, axis=0) - 1)[jnp.arange(se.shape[0]), se]
    keep = pos < capacity
    slot = jnp.where(keep, se * capacity + pos, e * capacity)  # overflow slot

    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[stok].astype(x.dtype))          # drop overflow
    buf = buf[:-1].reshape(e, capacity, d)

    # ---- expert FFN (E-sharded einsums; all-to-all at the boundaries) ----
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(x.dtype))

    # ---- return scatter + weighted combine ----
    out_flat = out.reshape(e * capacity, d)
    gathered = jnp.where(keep[:, None],
                         out_flat[jnp.minimum(slot, e * capacity - 1)],
                         jnp.zeros((1, d), x.dtype))
    y = jnp.zeros((n_tok, d), jnp.float32)
    y = y.at[stok].add(gathered.astype(jnp.float32) * sg[:, None])
    return y.reshape(b, t, d).astype(x.dtype), aux


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
        logits = jnp.einsum("nd,od->no", xf.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, top_e = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(0)
        ce = jax.nn.one_hot(top_e[:, 0], e).mean(0)
        aux = cfg.router_aux_coef * e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dp[-1])

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
        g = jnp.einsum("ecd,edf->ecf", my, w_gate.astype(x_loc.dtype))
        u = jnp.einsum("ecd,edf->ecf", my, w_up.astype(x_loc.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * u
        out_loc = jnp.einsum("ecf,efd->ecd", h, w_down.astype(x_loc.dtype))
        # gather every rank's expert outputs: (E, C, D) on all model ranks
        out = jax.lax.all_gather(out_loc, "model", axis=0, tiled=True)

        out_flat = out.reshape(e * capacity, d)
        gathered = jnp.where(
            keep[:, None],
            out_flat[jnp.minimum(slot, e * capacity - 1)],
            jnp.zeros((1, d), x_loc.dtype))
        y = jnp.zeros((n_tok, d), jnp.float32)
        y = y.at[stok].add(gathered.astype(jnp.float32) * sg[:, None])
        return y.reshape(bl, tl, d).astype(x_loc.dtype), aux

    y, aux = jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(params["router"]["w"], params["w_gate"], params["w_up"],
      params["w_down"], x)
    return y, aux
