"""The olmoe_1b_7b configuration and its layer kind (``layers/olmoe.py``):
resolved by name, the same weight tree as the program serves, a reference
layer that matches a per-token loop, the per-token work it charges, the
rows-per-touch reader, and one tiny cell run through the harness on the
CPU.  Beside them, the dense configurations' two serving programs lower
to the same HLO as before OLMoE's QK-norm, norm eps and routing counts
came into the program."""

import hashlib
import json
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench import cell as cell_mod
from chipbench import spec, weights

CONFIG_FILE = os.path.join(chipbench_tiny.BENCH_DIR, "configs",
                           "olmoe_1b_7b.json")

# OLMoE's structure at a tiny size: QK-norm, 8 experts, top-2, no
# renormalisation of the top-2 weights, eps 1e-5
TINY = {
    "arch": "olmoe_1b_7b", "layer": "olmoe", "num_hidden_layers": 2,
    "hidden_size": 128, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": False, "vocab_size": 512, "rope_theta": 10000,
    "sparsity": "2:32", "groups": {"128": [2, 32], "64": [2, 32]},
    "param_dtype": "float32", "compute_dtype": "bfloat16",
    "rms_norm_eps": 1e-5, "logit_std": 2.0,
}

# SHA-256 of the dense configurations' decode step and prefill chunk,
# lowered at chipbench_tiny's sizes and engine on the CPU (packed weights,
# interpret-mode kernels) with debug info left out, computed on the
# program before this configuration (commit 687bddc) by ``_lowered``
DENSE_HLO = {
    "decode_step": "55ddfecd88603a662375f8307f5d7c7c323f9089e2e9a0f5e8079bc1"
                   "02c7790f",
    "prefill_chunk": "fd9f305e03740ee40328eb1b586a27a4a1656c30beac571351027a"
                     "8170fdd31d",
}


@pytest.fixture(scope="module")
def olmoe():
    return spec.layer_of(TINY)


def _config() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def _model(config, layer):
    from repro.models.families import build_model

    return build_model(cell_mod.arch_config(config, layer))


def test_the_cell_resolves_the_olmoe_layer_kind():
    cell = spec.resolve("olmoe_1b_7b.docqa")
    assert cell.layer.__file__ == os.path.join(chipbench_tiny.BENCH_DIR,
                                               "layers", "olmoe.py")
    dims = weights.layer_dims(cell.config, cell.layer)
    assert (dims["experts"], dims["top_k"], dims["norm_topk"]) == \
        (64, 8, False)
    assert (dims["d"], dims["ff"], dims["hq"], dims["dh"], dims["eps"]) == \
        (2048, 1024, 16, 128, 1e-5)
    assert cell.config["reduced"] == []
    assert [m.name for m in cell.per_layer][-1] == \
        "expert_rows_per_touch.prefill"


def test_the_configuration_file_is_the_programs_olmoe():
    """What the file states and the layer kind replaces is what the
    program's own olmoe_1b_7b configuration holds."""
    from repro.configs.base import get_arch

    config = _config()
    got = cell_mod.arch_config(config, spec.layer_of(config))
    want = get_arch("olmoe_1b_7b")
    for field in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                  "d_ff", "vocab_size", "rope_theta", "qk_norm",
                  "norm_eps", "moe", "resolved_head_dim", "sparsity"):
        assert getattr(got, field) == getattr(want, field), field


def test_tree_is_the_programs_served_tree(olmoe):
    from repro.launch.pack_tree import pack_tree

    model = _model(TINY, olmoe)
    dims = weights.layer_dims(TINY, olmoe)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape[1:],
                                                        s.dtype),
                         shapes["layers"])
    got, want = weights._shape_map(layer), weights._shape_map(
        olmoe.tree(dims))
    assert got == want
    assert got["['moe']['gate']['w']"] == (8, 64, 128)
    assert got["['attn']['q_norm']['scale']"] == (128,)
    # the harness's own check passes, and the served tree packs the stacks
    served = jax.eval_shape(
        weights.served_builder(model, TINY, olmoe, pack_tree),
        jax.random.PRNGKey(0))
    gate = served["layers"]["moe"]["gate"]
    assert gate.dense_shape == (64, 128) and gate.values.shape[:2] == (2, 8)


def _np_rms(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def _np_rope(x, theta):
    t, _, dh = x.shape
    freqs = 1.0 / theta ** (np.arange(0, dh, 2) / dh)
    ang = np.arange(t)[:, None] * freqs
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_forward_matches_a_per_token_loop(olmoe, norm_topk):
    config = {**TINY, "norm_topk_prob": norm_topk}
    dims = weights.layer_dims(config, olmoe)
    w = weights.layer_weights(weights.seed_key(2**33 + 3), 1,
                              olmoe.tree(dims), weights.groups_of(config),
                              2.0)
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (256, 128)),
                   np.float32)
    got = np.asarray(jax.jit(lambda w, h: olmoe.forward(w, h, dims, False))(
        w, h))
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), w)
    att, moe, eps = p["attn"], p["moe"], dims["eps"]
    t, hq, dh = 256, dims["hq"], dims["dh"]
    a = _np_rms(h, p["ln1"]["scale"], eps)
    q = _np_rms(a @ att["wq"]["w"].T, att["q_norm"]["scale"], eps)
    k = _np_rms(a @ att["wk"]["w"].T, att["k_norm"]["scale"], eps)
    v = (a @ att["wv"]["w"].T).reshape(t, hq, dh)
    q = _np_rope(q.reshape(t, hq, dh), dims["theta"])
    k = _np_rope(k.reshape(t, hq, dh), dims["theta"])
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(dh)
    s = np.where(np.tril(np.ones((t, t), bool))[None], s, -np.inf)
    s = np.exp(s - s.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", s, v).reshape(t, hq * dh)
    h1 = h + o @ att["wo"]["w"].T
    want = np.empty_like(h1)
    for i in range(t):
        b = _np_rms(h1[i], p["ln2"]["scale"], eps)
        logits = moe["router"]["w"] @ b
        pr = np.exp(logits - logits.max())
        pr /= pr.sum()
        top = np.argsort(-pr)[:2]
        gates = pr[top] / (pr[top].sum() if norm_topk else 1.0)
        out = h1[i].copy()
        for e, g in zip(top, gates):
            z = moe["gate"]["w"][e] @ b
            out += g * (moe["down"]["w"][e]
                        @ (z / (1 + np.exp(-z)) * (moe["up"]["w"][e] @ b)))
        want[i] = out
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # the control's float8 pass departs from it
    low = np.asarray(olmoe.forward(w, h, dims, True))
    assert np.max(np.abs(low - got)) > 1e-3


def _packed(lead, o, k, n, m):
    """A stand-in for a packed weight: what ``costs.kept_weights`` reads."""
    return SimpleNamespace(dense_shape=(o, k),
                           values=jax.ShapeDtypeStruct(
                               (*lead, k // m, n, o), jnp.float32),
                           cfg=SimpleNamespace(n=n, m=m))


def test_active_weights_count_attention_the_top_experts_and_router(olmoe):
    dims = weights.layer_dims(TINY, olmoe)
    layers, e = 2, 8
    attn = {name: _packed((layers,), 128, 128, 2, 32)
            for name in ("wq", "wk", "wv", "wo")}
    moe = {"router": {"w": jax.ShapeDtypeStruct((layers, e, 128),
                                                jnp.float32)},
           "gate": _packed((layers, e), 64, 128, 2, 32),
           "up": _packed((layers, e), 64, 128, 2, 32),
           "down": _packed((layers, e), 128, 64, 2, 32)}
    params = {"layers": {"attn": attn, "moe": moe}}
    attn_kept = layers * 4 * 128 * 128 * 2 // 32
    expert_kept = layers * e * 3 * 64 * 128 * 2 // 32
    assert olmoe.active_weights(params, dims) == \
        attn_kept + expert_kept * 2 // 8 + layers * e * 128


def test_active_weights_at_full_width():
    """At OLMoE's widths: 16 layers of 4 attention projections (2048 x
    2048) and 64 x 3 experts (1024 x 2048), 8:128, top-8, and the router."""
    from repro.launch.pack_tree import pack_tree

    config = _config()
    layer = spec.layer_of(config)
    shapes = jax.eval_shape(
        weights.served_builder(_model(config, layer), config, layer,
                               pack_tree), jax.random.PRNGKey(0))
    attn = 16 * 4 * 2048 * 2048 // 16
    experts = 16 * 64 * 3 * 1024 * 2048 // 16
    router = 16 * 64 * 2048
    assert layer.active_weights(
        shapes, weights.layer_dims(config, layer)) == \
        attn + experts * 8 // 64 + router == 69_206_016


def _rows_per_touch():
    return spec.load_reader(chipbench_tiny.BENCH_DIR,
                            "expert_rows_per_touch.prefill")


def test_rows_per_touch_reads_both_programs():
    read = _rows_per_touch()
    rec = {"counters_window": {
        "serve_moe_rows_total{program=prefill}": 2048 * 16 * 3,
        "serve_moe_rows_total{program=decode}": 32 * 16 * 10,
        "serve_moe_experts_touched_total{program=prefill}": 64 * 16 * 3,
        "serve_moe_experts_touched_total{program=decode}": 26 * 16 * 10,
        "serve_compiles_total{program=decode}": 0}}
    assert read(rec) == pytest.approx((2048 * 3 + 320) / (64 * 3 + 260))
    one = {"counters_window": {
        "serve_moe_rows_total{program=prefill}": 4096,
        "serve_moe_experts_touched_total{program=prefill}": 128}}
    assert read(one) == 32.0


def test_rows_per_touch_is_none_without_routing_counts():
    read = _rows_per_touch()
    # a dense model, or a program without the counters
    assert read({"counters_window": {
        "serve_compiles_total{program=decode}": 0}}) is None
    assert read({"counters_window": {
        "serve_moe_rows_total{program=decode}": 0,
        "serve_moe_experts_touched_total{program=decode}": 0}}) is None


# the tiny OLMoE's own limit: its sound run reads about 0.015 and its
# float8 control about 0.42 (CPU, interpret mode), so 0.1 sits between them
TINY_LIMIT = 0.1


def test_a_tiny_olmoe_cell_runs_through_the_harness(olmoe):
    """Served through the same set-up, loop, engine and interpret-mode
    kernels as a chip run, then checked by the reference: correct, the
    float8 control far above the program and not correct by the same
    limit, and every routing counted."""
    cell = spec.Cell(name="tiny.olmoe", chips=1, config_name="tiny",
                     config=TINY, traffic_name="tiny",
                     traffic=chipbench_tiny.MIX,
                     limits={"served_logit_gap": {"limit": TINY_LIMIT}},
                     end_to_end=[], per_layer=[], layer=olmoe)
    rec = cell_mod.run(cell, 2**33 + 5, 2.0, False, t_start=time.monotonic(),
                       devices=jax.devices(), peak=chipbench_tiny.PEAK,
                       opts=cell_mod.Options(backend="pallas_interpret",
                                             control=True))
    gap = rec["check"]["served_logit_gap"]
    assert cell_mod.correct(rec) and gap["requests"] >= 2
    assert gap["control"] > 4 * gap["value"]
    as_control = {"check": {"served_logit_gap": dict(
        gap, value=gap["control"])}}
    assert not cell_mod.correct(as_control)
    touch = _rows_per_touch()(rec)
    assert touch is not None and 1.0 <= touch <= 2 * 16
    assert rec["dims"]["experts"] == 8


def _lowered(program: str) -> str:
    from repro.core.sparse_linear import ExecPolicy
    from repro.launch.pack_tree import pack_tree
    from repro.paged import PagedLayout

    cfg = chipbench_tiny.CONFIG
    layer = spec.layer_of(cfg)
    model = _model(cfg, layer)
    params = jax.eval_shape(weights.served_builder(model, cfg, layer,
                                                   pack_tree),
                            jax.random.PRNGKey(0))
    eng = chipbench_tiny.MIX["engine"]
    layout = PagedLayout.for_serve(eng["max_len"],
                                   page_size=eng["page_size"],
                                   num_pages=eng["num_pages"],
                                   num_slots=eng["num_slots"])
    state = jax.eval_shape(lambda: model.init_decode_state(
        eng["num_slots"], eng["max_len"], dtype=jnp.float32, paged=layout))
    policy = ExecPolicy(mode="packed", backend="pallas_interpret")
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    if program == "decode_step":
        low = jax.jit(lambda p, s, t: model.decode_step(
            p, s, t, policy=policy)).lower(
            params, state,
            jax.ShapeDtypeStruct((eng["num_slots"], 1), jnp.int32))
    else:
        low = jax.jit(lambda p, s, t, a, b: model.prefill_chunk(
            p, s, t, a, b, policy=policy)).lower(
            params, state,
            jax.ShapeDtypeStruct((eng["prefill_chunk"],), jnp.int32),
            i32, i32)
    return low.as_text(debug_info=False)


@pytest.mark.parametrize("program", sorted(DENSE_HLO))
def test_dense_programs_lower_as_before(program):
    text = _lowered(program)
    assert hashlib.sha256(text.encode()).hexdigest() == DENSE_HLO[program]
