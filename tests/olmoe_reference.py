"""A plain OLMoE forward for the tests: float32 ``jax.numpy`` at
``Precision.HIGHEST``, no kernels, cache, batching or dispatch, one token
at a time through its top-k experts.

With n1 = RMS(x) and n2 = RMS(h), each layer computes

    h = x + W_o Attn(RoPE(RMS_q(W_q n1)), RoPE(RMS_k(W_k n1)), W_v n1)
    p = softmax(W_r n2), T = the top k of p
    y = h + sum_{e in T} p_e W_down,e (silu(W_gate,e n2) * W_up,e n2)

with the top-k weights renormalised only under ``norm_topk_prob``.  It
reads the program's dense (unpacked) parameter tree, ``model.init``'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    """x (..., K) @ w (O, K)ᵀ."""
    return jnp.einsum("...k,ok->...o", x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x (T, H, Dh) at positions 0..T-1, halves rotated together."""
    t, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal softmax attention; q (T, Hq, Dh), k/v (T, Hkv, Dh)."""
    t, hq, dh = q.shape
    group = hq // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * dh ** -0.5
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(
        t, hq * dh)


def forward(params, tokens, cfg):
    """Logits (T, vocab) of every position of ``tokens``, and the routing:
    per layer, each position's top-k experts (L, T, k)."""
    eps, moe = cfg.norm_eps, cfg.moe
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    x = jnp.asarray(params["embed"]["table"], jnp.float32)[
        np.asarray(tokens)]
    t = x.shape[0]
    routing = []
    for i in range(cfg.num_layers):
        blk = jax.tree.map(lambda a: jnp.asarray(a[i], jnp.float32),
                           params["layers"])
        att = blk["attn"]
        a = _rms(x, blk["ln1"]["scale"], eps)
        q = _rms(_mm(a, att["wq"]["w"]), att["q_norm"]["scale"], eps)
        k = _rms(_mm(a, att["wk"]["w"]), att["k_norm"]["scale"], eps)
        v = _mm(a, att["wv"]["w"]).reshape(t, hkv, dh)
        q = _rope(q.reshape(t, hq, dh), cfg.rope_theta)
        k = _rope(k.reshape(t, hkv, dh), cfg.rope_theta)
        h = x + _mm(_attention(q, k, v), att["wo"]["w"])
        b = _rms(h, blk["ln2"]["scale"], eps)
        ex = blk["moe"]
        probs = np.asarray(jax.nn.softmax(_mm(b, ex["router"]["w"]), -1))
        rows, tops = [], []
        for pos in range(t):
            top = np.argsort(-probs[pos], kind="stable")[
                :moe.experts_per_token]
            weights = probs[pos, top]
            if moe.norm_topk_prob:
                weights = weights / weights.sum()
            y = h[pos]
            for e, w in zip(top, weights):
                act = jax.nn.silu(_mm(b[pos], ex["gate"]["w"][e])) \
                    * _mm(b[pos], ex["up"]["w"][e])
                y = y + w * _mm(act, ex["down"]["w"][e])
            rows.append(y)
            tops.append(top)
        x = jnp.stack(rows)
        routing.append(np.stack(tops))
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = _mm(x, params["unembed"]["table"][:cfg.vocab_size])
    return np.asarray(logits), np.stack(routing)
