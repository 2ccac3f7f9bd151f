"""The OLMoE decoder layer (allenai/OLMoE-1B-7B-0924, arXiv:2409.02060):
RMSNorm, rotary multi-head attention with QK-norm, RMSNorm, then a softmax
router over ``num_experts`` SwiGLU experts of which each token takes the
top ``num_experts_per_tok``.  With n1 = RMS(x) and n2 = RMS(h):

    h = x + W_o Attn(RoPE(RMS_q(W_q n1)), RoPE(RMS_k(W_k n1)), W_v n1)
    p = softmax(W_r n2), T = the top k of p
    y = h + sum_{e in T} p_e W_down,e (silu(W_gate,e n2) * W_up,e n2)

The weights of T are renormalised to sum to 1 only where the configuration
says ``norm_topk_prob`` (OLMoE does not).  RMS_q and RMS_k normalise the
whole q (Hq Dh) and k (Hkv Dh) projections, before the heads split.  Every
linear but the router is pruned to the configuration's N:M; the experts
are ``(E, O, K)`` stacks, the router a dense ``(E, d)`` matrix.

The reference takes every expert over every position and weights each by
its gate, which is zero outside a position's top k, one expert at a time.
"""

import math

import jax
import jax.numpy as jnp

from chipbench import costs, weights
from chipbench.reference import _attention, _mm, _rms, _rope

SPARSE = "stated by the config file"


def dims(config: dict, base: dict) -> dict:
    return {**base, "experts": int(config["num_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "norm_topk": bool(config["norm_topk_prob"])}


def tree(dims: dict) -> dict:
    d, ff, dh, e = dims["d"], dims["ff"], dims["dh"], dims["experts"]
    q, kv = dims["hq"] * dh, dims["hkv"] * dh
    sds = weights._sds

    def lin(*shape):
        return {"w": sds(*shape), "sparsity": SPARSE}

    return {"ln1": {"scale": sds(d)}, "ln2": {"scale": sds(d)},
            "attn": {"wq": lin(q, d), "wk": lin(kv, d), "wv": lin(kv, d),
                     "wo": lin(d, q), "q_norm": {"scale": sds(q)},
                     "k_norm": {"scale": sds(kv)}},
            "moe": {"router": {"w": sds(e, d)}, "gate": lin(e, ff, d),
                    "up": lin(e, ff, d), "down": lin(e, d, ff)}}


def gates(probs, dims: dict):
    """(T, E) weight of each expert at each position: its probability
    inside the position's top k (renormalised there where the
    configuration says so), zero outside."""
    top, idx = jax.lax.top_k(probs, dims["top_k"])
    if dims["norm_topk"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, dims["experts"]) * top[..., None],
                   axis=1)


def forward(w, h, dims: dict, low: bool):
    t = h.shape[0]
    hq, hkv, dh, eps = dims["hq"], dims["hkv"], dims["dh"], dims["eps"]
    att = w["attn"]
    a = _rms(h, w["ln1"]["scale"], eps)
    q = _rms(_mm(a, att["wq"]["w"], low), att["q_norm"]["scale"], eps)
    k = _rms(_mm(a, att["wk"]["w"], low), att["k_norm"]["scale"], eps)
    v = _mm(a, att["wv"]["w"], low).reshape(t, hkv, dh)
    q = _rope(q.reshape(t, hq, dh), dims["theta"])
    k = _rope(k.reshape(t, hkv, dh), dims["theta"])
    h = h + _mm(_attention(q, k, v), att["wo"]["w"], low)
    b = _rms(h, w["ln2"]["scale"], eps)
    moe = w["moe"]
    g = gates(jax.nn.softmax(_mm(b, moe["router"]["w"], low), axis=-1), dims)

    def expert(y, e):
        w_gate, w_up, w_down, weight = e
        act = jax.nn.silu(_mm(b, w_gate, low)) * _mm(b, w_up, low)
        return y + weight[:, None] * _mm(act, w_down, low), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (moe["gate"]["w"], moe["up"]["w"], moe["down"]["w"],
                         g.T))
    return h + y


def active_weights(params, dims: dict) -> int:
    """Attention's kept weights, ``top_k / experts`` of the experts' and
    the whole router: what one token multiplies."""
    layers = params["layers"]
    router = layers["moe"]["router"]["w"]
    return (costs.kept_weights(layers["attn"])
            + costs.kept_weights(layers["moe"]) * dims["top_k"]
            // dims["experts"]
            + math.prod(router.shape))


def arch_changes(config: dict) -> dict:
    from repro.configs.base import MoEConfig

    return {"d_ff": 0, "qk_norm": True,
            "norm_eps": float(config["rms_norm_eps"]),
            "moe": MoEConfig(
                num_experts=int(config["num_experts"]),
                experts_per_token=int(config["num_experts_per_tok"]),
                d_ff_expert=int(config["intermediate_size"]),
                norm_topk_prob=bool(config["norm_topk_prob"]))}
