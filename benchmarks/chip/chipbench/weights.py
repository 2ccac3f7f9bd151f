"""Seeded weights, made by the benchmark.

The benchmark, not the program, draws every weight from ``--seed``, so the
reference that decides ``correct`` can draw the same weights again without
taking anything the program made.  The program is given them in its own
parameter layout (the tree shapes of ``model.init``, read with
``jax.eval_shape``) and packs each layer itself, with its own
``pack_tree``, inside one jitted call on the device: the dense float32 tree
of a full-width model never exists whole.

Each sparse linear is a normal matrix pruned by magnitude to the N:M
pattern the configuration file states for its contraction dim, scaled so
rows have unit expected squared norm; the program's packing then keeps
exactly those weights.  A stack of linears, ``w`` of shape ``(..., O, K)``
(a layer's experts, say), draws each matrix by the same rule from its own
key: the path's key folded with the matrix's flat index.  Norm scales are
``1 + 0.1 N(0, 1)``, the embedding is ``N(0, 1)`` and the head is scaled
so logits have a standard deviation of about ``logit_std``.

A layer's tree, its names and shapes with a ``sparsity`` marker on each
pruned linear, comes from the configuration's layer kind
(``layers/<kind>.py``, :func:`chipbench.spec.layer_of`)."""

from __future__ import annotations

import functools
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Groups = Dict[int, Tuple[int, int]]     # contraction dim -> (n, m)


def groups_of(config: dict) -> Groups:
    return {int(k): (int(v[0]), int(v[1]))
            for k, v in config["groups"].items()}


def seed_key(seed: int) -> jax.Array:
    key = jax.random.PRNGKey(0)
    for shift in (0, 32, 64):
        key = jax.random.fold_in(key, np.uint32((seed >> shift) & 0xFFFFFFFF))
    return key


def _path_key(key, path: str):
    return jax.random.fold_in(key, np.uint32(zlib.crc32(path.encode())))


@functools.lru_cache(maxsize=None)
def kept_rms(n: int, m: int) -> float:
    """RMS of the ``n`` largest-magnitude of ``m`` standard normals, by a
    fixed host sample (the same constant on every machine)."""
    x = np.abs(np.random.default_rng(0).standard_normal((1 << 16, m)))
    top = -np.sort(-x, axis=1)[:, :n]
    return float(np.sqrt(np.mean(top ** 2)))


def topn_mask(w: jax.Array, n: int, m: int) -> jax.Array:
    """Keep the ``n`` largest |w| of every group of ``m`` along the last
    axis; ties at the threshold go to the lowest column."""
    o, k = w.shape
    mag = jnp.abs(w).reshape(o, k // m, m)
    thresh = jax.lax.top_k(mag, n)[0][..., n - 1:n]
    above = mag > thresh
    tie = mag == thresh
    room = n - above.sum(-1, keepdims=True)
    keep = above | (tie & (jnp.cumsum(tie, axis=-1) <= room))
    return keep.reshape(o, k)


def sparse_linear(key, o: int, k: int, n: int, m: int) -> jax.Array:
    w = jax.random.normal(key, (o, k), jnp.float32)
    scale = 1.0 / (np.sqrt(k * n / m) * kept_rms(n, m))
    return jnp.where(topn_mask(w, n, m), w * np.float32(scale), 0.0)


def _linear(key, shape, path: str, sparse: bool, groups: Groups):
    """The ``w`` of a linear node: ``(O, K)``, or a stack ``(..., O, K)``
    whose every leading index is drawn from ``fold_in(key, index)`` by the
    same rule (one expert of a stack, say)."""
    *lead, o, k = shape
    if sparse:
        if k not in groups:
            raise KeyError(f"{path}: the configuration file states no "
                           f"N:M group for contraction dim {k}")
        n, m = groups[k]

        def draw(sub):
            return sparse_linear(sub, o, k, n, m)
    else:
        def draw(sub):
            return jax.random.normal(sub, (o, k), jnp.float32) / np.sqrt(k)
    if not lead:
        return draw(key)
    stack = jax.lax.map(lambda i: draw(jax.random.fold_in(key, i)),
                        jnp.arange(int(np.prod(lead)), dtype=jnp.uint32))
    return stack.reshape(*lead, o, k)


def _fill(key, node, path: str, groups: Groups, logit_std: float):
    """A weight tree shaped like ``node`` (eval_shape output, Static
    metadata kept), every array drawn from ``key`` and its path."""
    if isinstance(node, dict) and "w" in node:
        w = _linear(_path_key(key, path), node["w"].shape, path,
                    "sparsity" in node, groups)
        return {**node, "w": w}
    if isinstance(node, dict):
        return {name: _fill(key, child, f"{path}/{name}", groups, logit_std)
                for name, child in node.items()}
    if not hasattr(node, "shape"):
        return node                       # Static metadata
    sub = _path_key(key, path)
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        return 1.0 + 0.1 * jax.random.normal(sub, node.shape, jnp.float32)
    if leaf == "table":
        std = (logit_std / np.sqrt(node.shape[-1])
               if "unembed" in path else 1.0)
        return jax.random.normal(sub, node.shape, jnp.float32) * std
    raise KeyError(f"no rule to draw parameter {path} {node.shape}")


def dims_of(config: dict) -> dict:
    """The sizes every layer kind's weight tree and reference use, from
    the configuration file alone (a layer kind adds its own:
    :func:`layer_dims`)."""
    d, hq = int(config["hidden_size"]), int(config["num_attention_heads"])
    v = int(config["vocab_size"])
    return {"layers": int(config["num_hidden_layers"]), "d": d,
            "ff": int(config["intermediate_size"]), "hq": hq,
            "hkv": int(config["num_key_value_heads"]),
            "dh": int(config.get("head_dim", d // hq)), "vocab": v,
            # the served tables carry rows up to a multiple of 256
            "vocab_rows": -(-v // 256) * 256,
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"])}


def layer_dims(config: dict, layer) -> dict:
    """:func:`dims_of` with what the configuration's layer kind adds."""
    return layer.dims(config, dims_of(config))


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def top_tree(dims: dict) -> dict:
    rows, d = dims["vocab_rows"], dims["d"]
    return {"embed": {"table": _sds(rows, d)},
            "unembed": {"table": _sds(rows, d)},
            "final_norm": {"scale": _sds(d)}}


def _shape_map(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): tuple(x.shape) for p, x in flat
            if hasattr(x, "shape")}


def _arrays_only(tree):
    """``tree`` without the sparsity markers of a layer kind's ``tree``."""
    if isinstance(tree, dict):
        return {k: _arrays_only(v) for k, v in tree.items()
                if not isinstance(v, str)}
    return tree


def layer_key(key, layer):
    return jax.random.fold_in(_path_key(key, "layers"), layer)


def layer_weights(key, layer, tree: dict, groups: Groups,
                  logit_std: float) -> dict:
    """Dense (pruned) float32 weights of layer ``layer``, shaped like
    ``tree`` (a layer kind's ``tree(dims)``)."""
    return _arrays_only(_fill(layer_key(key, layer), tree, "", groups,
                              logit_std))


def top_weights(key, dims: dict, groups: Groups, logit_std: float) -> dict:
    """Every parameter outside the layer stack."""
    return _fill(key, top_tree(dims), "", groups, logit_std)


def served_builder(model, config: dict, layer, pack_layer):
    """``build(key)``: the program's params, every layer drawn and handed
    to ``pack_layer`` (the program's packing) inside a ``lax.map``, so one
    dense layer is live at a time.  The program's parameter tree must have
    exactly the names and shapes of the layer kind's ``tree``, else the
    reference would compute another model."""
    dims, groups = layer_dims(config, layer), groups_of(config)
    logit_std = float(config["logit_std"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layer_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
        shapes["layers"])
    top = {k: v for k, v in shapes.items() if k != "layers"}
    for got, want in ((layer_shapes, layer.tree(dims)),
                      (top, top_tree(dims))):
        if _shape_map(got) != _shape_map(want):
            raise ValueError(f"the program's parameter tree "
                             f"{_shape_map(got)} differs from the "
                             f"benchmark's {_shape_map(want)}")
    if shapes["layers"]["ln1"]["scale"].shape[0] != dims["layers"]:
        raise ValueError("the program's layer count differs from the "
                         "configuration file's")

    def build(key):
        layers = jax.lax.map(
            lambda i: pack_layer(_fill(layer_key(key, i), layer_shapes, "",
                                       groups, logit_std)),
            jnp.arange(dims["layers"], dtype=jnp.uint32))
        return {**_fill(key, top, "", groups, logit_std), "layers": layers}

    return build


def build_served(model, config: dict, layer, seed: int, pack_layer):
    """The program's params on the device, in one jitted call."""
    return jax.jit(served_builder(model, config, layer, pack_layer))(
        seed_key(seed))
