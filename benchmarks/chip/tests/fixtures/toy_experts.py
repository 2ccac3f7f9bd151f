"""A toy mixture-of-experts layer kind, for the tests: dense rotary
attention, then ``num_experts`` SwiGLU experts over ``(E, O, K)`` stacks
pruned to the configuration's N:M, mixed by the top ``num_experts_per_tok``
probabilities of a softmax router (not renormalised).  The reference
computes every expert on every token and weights each by its gate, which
is zero outside a token's top experts.

The tests copy it into ``layers/`` of a copy of the benchmark's directory:
a layer kind that arrives as one new file.
"""

import jax
import jax.numpy as jnp

from chipbench import costs, weights
from chipbench.reference import _attention, _mm, _rms, _rope

SPARSE = "stated by the config file"


def dims(config: dict, base: dict) -> dict:
    return {**base, "experts": int(config["num_experts"]),
            "top_k": int(config["num_experts_per_tok"])}


def tree(dims: dict) -> dict:
    d, ff, dh, e = dims["d"], dims["ff"], dims["dh"], dims["experts"]
    q, kv = dims["hq"] * dh, dims["hkv"] * dh
    sds = weights._sds

    def lin(*shape):
        return {"w": sds(*shape), "sparsity": SPARSE}

    return {"ln1": {"scale": sds(d)}, "ln2": {"scale": sds(d)},
            "attn": {"wq": lin(q, d), "wk": lin(kv, d), "wv": lin(kv, d),
                     "wo": lin(d, q)},
            "moe": {"router": {"w": sds(e, d)}, "gate": lin(e, ff, d),
                    "up": lin(e, ff, d), "down": lin(e, d, ff)}}


def _experts(x, stack, low):
    """x (T, K) through every matrix of ``stack`` (E, O, K): (E, T, O)."""
    return jax.vmap(lambda w: _mm(x, w, low))(stack)


def forward(w, h, dims: dict, low: bool):
    t = h.shape[0]
    hq, hkv, dh = dims["hq"], dims["hkv"], dims["dh"]
    a = _rms(h, w["ln1"]["scale"], dims["eps"])
    q = _mm(a, w["attn"]["wq"]["w"], low).reshape(t, hq, dh)
    k = _mm(a, w["attn"]["wk"]["w"], low).reshape(t, hkv, dh)
    v = _mm(a, w["attn"]["wv"]["w"], low).reshape(t, hkv, dh)
    q, k = _rope(q, dims["theta"]), _rope(k, dims["theta"])
    h = h + _mm(_attention(q, k, v), w["attn"]["wo"]["w"], low)
    b = _rms(h, w["ln2"]["scale"], dims["eps"])
    moe = w["moe"]
    probs = jax.nn.softmax(_mm(b, moe["router"]["w"], low), axis=-1)
    top, idx = jax.lax.top_k(probs, dims["top_k"])
    gates = jnp.sum(jax.nn.one_hot(idx, dims["experts"]) * top[..., None],
                    axis=1)                                   # (T, E)
    g = jax.nn.silu(_experts(b, moe["gate"]["w"], low))
    u = _experts(b, moe["up"]["w"], low)
    y = jax.vmap(lambda x, wd: _mm(x, wd, low))(g * u, moe["down"]["w"])
    return h + jnp.einsum("te,etd->td", gates, y,
                          precision=jax.lax.Precision.HIGHEST)


def active_weights(params, dims: dict) -> int:
    """Attention's kept weights and ``top_k / experts`` of the experts'."""
    layers = params["layers"]
    return (costs.kept_weights(layers["attn"])
            + costs.kept_weights(layers["moe"]) * dims["top_k"]
            // dims["experts"])


def arch_changes(config: dict) -> dict:
    from repro.configs.base import MoEConfig

    return {"d_ff": 0, "moe": MoEConfig(
        num_experts=int(config["num_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        d_ff_expert=int(config["intermediate_size"]))}
