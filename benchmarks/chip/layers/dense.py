"""The dense decoder layer: RMSNorm, rotary GQA attention (causal), RMSNorm
and a SiLU-gated MLP, every linear pruned to the configuration's N:M.

A configuration names its layer kind with the key ``"layer"`` (this one
where it names none); the harness finds ``layers/<kind>.py`` by that name
(:func:`chipbench.spec.layer_of`).  A layer module defines:

* ``dims(config, base)``   — the sizes it needs on top of
  :func:`chipbench.weights.dims_of` (``base``);
* ``tree(dims)``           — one layer's shapes, with a ``sparsity`` marker
  on each pruned linear, under the served tree's names; the program's
  tree has to match it;
* ``forward(w, h, dims, low)`` — the reference layer over ``h`` (T, d) in
  float32 at ``Precision.HIGHEST``; with ``low`` every matmul's operands
  are rounded to float8 (the control);
* ``active_weights(params, dims)`` — the kept weights one token
  multiplies, from the served tree;
* ``arch_changes(config)`` — replacements of the program's ``ArchConfig``
  beyond those :func:`chipbench.cell.arch_config` makes.
"""

import jax

from chipbench import costs, weights
from chipbench.reference import _attention, _mm, _rms, _rope


def dims(config: dict, base: dict) -> dict:
    return base


def tree(dims: dict) -> dict:
    d, ff, dh = dims["d"], dims["ff"], dims["dh"]
    q, kv = dims["hq"] * dh, dims["hkv"] * dh
    sds = weights._sds

    def lin(o, k):
        return {"w": sds(o, k), "sparsity": "stated by the config file"}

    return {"ln1": {"scale": sds(d)}, "ln2": {"scale": sds(d)},
            "attn": {"wq": lin(q, d), "wk": lin(kv, d), "wv": lin(kv, d),
                     "wo": lin(d, q)},
            "mlp": {"gate": lin(ff, d), "up": lin(ff, d), "down": lin(d, ff)}}


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
    g = jax.nn.silu(_mm(b, w["mlp"]["gate"]["w"], low))
    return h + _mm(g * _mm(b, w["mlp"]["up"]["w"], low),
                   w["mlp"]["down"]["w"], low)


def active_weights(params, dims: dict) -> int:
    """Every kept weight: each token goes through every linear."""
    return costs.kept_weights(params)


def arch_changes(config: dict) -> dict:
    return {}
