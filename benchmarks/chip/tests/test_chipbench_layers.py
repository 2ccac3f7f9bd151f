"""A configuration brings its own layer kind, and a kernel family its own
costs, as new files: ``layers/<kind>.py`` and ``kernels/<family>.py`` in
the benchmark's directory, found by name.  The toy mixture-of-experts kind
(``fixtures/toy_experts.py``) and a grouped kernel's cost file are added to
a copy of that directory and resolved there, and no file of the copy's
``chipbench/`` changes."""

import hashlib
import json
import os
import shutil
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench import cell as cell_mod
from chipbench import costs, spec, tracefile, weights
from chipbench.tracefile import Trace

FIXTURES = os.path.join(chipbench_tiny.HERE, "fixtures")
REPO = chipbench_tiny.REPO_ROOT

TOY = {
    "arch": "olmoe_1b_7b", "layer": "toy_experts", "num_hidden_layers": 2,
    "hidden_size": 32, "intermediate_size": 16, "num_attention_heads": 2,
    "num_key_value_heads": 2, "vocab_size": 64, "rope_theta": 10000,
    "num_experts": 4, "num_experts_per_tok": 2,
    "sparsity": "2:8", "groups": {"32": [2, 8], "16": [2, 8]},
    "param_dtype": "float32", "compute_dtype": "bfloat16",
    "rms_norm_eps": 1e-6, "logit_std": 2.0,
    "source": "https://example.org/toy", "reduced": [],
}

# a grouped expert kernel: each row of x goes through one expert's packed
# weights, so a call does 2 * rows * (values of one expert) operations
GROUPED_COST = '''
from chipbench import costs


def call(hlo):
    head, _, rest = hlo.partition(" custom-call(")
    ins = costs._shapes(rest.split("custom_call_target", 1)[0])
    outs = costs._shapes(head.split(" = ", 1)[-1])
    rows, per_expert = ins[0][1][0], int(costs.np.prod(ins[1][1][1:]))
    nbytes = sum(costs.DTYPE_BYTES[dt] * int(costs.np.prod(shape))
                 for dt, shape in ins + outs)
    return costs.Call(flops=2.0 * rows * per_expert, bytes=float(nbytes))
'''

GROUPED_HLO = ("%demm_grouped.7 = f32[16,32]{1,0} custom-call("
               "bf16[16,32]{1,0} %x, f32[4,2,4,16]{3,2,1,0} %values, "
               "s32[4,2,4,16]{3,2,1,0} %indices, s32[4]{0} %sizes), "
               "custom_call_target=\"tpu_custom_call\"")
DENSE_HLO = ("%demm_xwT.3 = f32[16,64]{1,0} custom-call(bf16[16,32]{1,0} "
             "%x, f32[4,2,64]{2,1,0} %values, s32[4,2,64]{2,1,0} "
             "%indices), custom_call_target=\"tpu_custom_call\"")


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of the repository's benchmark files, and a check that the
    copy's ``chipbench/`` is the same after the test."""
    repo = tmp_path / "repo"
    bench_dir = repo / "benchmarks" / "chip"
    shutil.copytree(chipbench_tiny.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), repo)
    before = _digest(bench_dir / "chipbench")
    yield repo, bench_dir
    assert _digest(bench_dir / "chipbench") == before


@pytest.fixture
def toy(bench_copy):
    """The toy kind added as one new file, and resolved by its name."""
    _, bench_dir = bench_copy
    shutil.copy(os.path.join(FIXTURES, "toy_experts.py"),
                bench_dir / "layers" / "toy_experts.py")
    return spec.layer_of(TOY, str(bench_dir))


def test_a_cell_resolves_its_configurations_layer_kind(bench_copy, toy):
    repo, bench_dir = bench_copy
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(TOY))
    (bench_dir / "traffic" / "tiny_mix.json").write_text(
        json.dumps(chipbench_tiny.MIX))
    (bench_dir / "limits" / "toy.tiny_mix.json").write_text(json.dumps(
        {"served_logit_gap": {"limit": 0.5}}))
    bench = json.loads((repo / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": TOY["source"],
                             "file": "benchmarks/chip/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.tiny_mix", "config": "toy",
                               "traffic": "tiny_mix", "chips": 1,
                               "why": "test"})
    (repo / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("toy.tiny_mix", repo_root=str(repo),
                        bench_dir=str(bench_dir))
    assert cell.layer.__file__ == str(bench_dir / "layers" /
                                      "toy_experts.py")
    assert weights.layer_dims(TOY, cell.layer)["experts"] == 4
    # the kind's replacements come after the harness's own
    arch = cell_mod.arch_config(TOY, cell.layer)
    assert arch.d_ff == 0 and arch.d_model == 32 and arch.num_layers == 2
    assert (arch.moe.num_experts, arch.moe.experts_per_token,
            arch.moe.d_ff_expert) == (4, 2, 16)
    # the configurations of BENCHMARK.json stay dense
    for w in ("stablelm_3b.chat", "internlm2_20b_16l.longprompt"):
        got = spec.resolve(w, repo_root=str(repo), bench_dir=str(bench_dir))
        assert got.layer.__file__ == str(bench_dir / "layers" / "dense.py")


def test_each_expert_is_drawn_with_its_own_key_and_pattern(toy):
    dims = weights.layer_dims(TOY, toy)
    tree = toy.tree(dims)
    groups = weights.groups_of(TOY)
    key = weights.seed_key(2**33 + 17)
    w = weights.layer_weights(key, 1, tree, groups, 2.0)
    layer_key = weights.layer_key(key, 1)
    for name, (o, k) in (("gate", (16, 32)), ("up", (16, 32)),
                         ("down", (32, 16))):
        stack = np.asarray(w["moe"][name]["w"])
        assert stack.shape == (4, o, k)
        n, m = groups[k]
        path_key = weights._path_key(layer_key, f"/moe/{name}")
        for e in range(4):
            want = np.asarray(weights.sparse_linear(
                jax.random.fold_in(path_key, e), o, k, n, m))
            np.testing.assert_array_equal(stack[e] != 0, want != 0)
            np.testing.assert_allclose(stack[e], want, rtol=1e-6, atol=0)
            assert np.all((stack[e].reshape(o, k // m, m) != 0).sum(-1)
                          == n)
        assert not np.array_equal(stack[0], stack[1])
    # the router is dense, and a 2-D linear draws as before
    assert np.all(np.asarray(w["moe"]["router"]["w"]) != 0)
    np.testing.assert_array_equal(
        np.asarray(w["attn"]["wq"]["w"]),
        np.asarray(weights.sparse_linear(
            weights._path_key(layer_key, "/attn/wq"), 32, 32, 2, 8)))


def _np_rms(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def test_forward_matches_a_loop_over_each_tokens_top_experts(bench_copy,
                                                             toy):
    _, bench_dir = bench_copy
    dense = spec.load_layer(str(bench_dir), "dense")
    dims = weights.layer_dims(TOY, toy)
    w = weights.layer_weights(weights.seed_key(3), 0, toy.tree(dims),
                              weights.groups_of(TOY), 2.0)
    h = jax.random.normal(jax.random.PRNGKey(5), (256, 32), jnp.float32)
    got = np.asarray(jax.jit(lambda w, h: toy.forward(w, h, dims, False))(
        w, h))
    # attention as the dense kind computes it (its MLP zeroed out)
    mlp = {n: {"w": jnp.zeros((16, 32) if n != "down" else (32, 16))}
           for n in ("gate", "up", "down")}
    h1 = np.asarray(dense.forward({**w, "mlp": mlp}, h, dims, False),
                    np.float64)
    moe = {k: np.asarray(v["w"], np.float64) for k, v in w["moe"].items()}
    scale = np.asarray(w["ln2"]["scale"], np.float64)
    want = np.empty_like(h1)
    for t in range(h1.shape[0]):
        b = _np_rms(h1[t], scale, dims["eps"])
        logits = moe["router"] @ b
        p = np.exp(logits - logits.max())
        p /= p.sum()
        out = h1[t].copy()
        for e in np.argsort(-p)[:2]:
            g = moe["gate"][e] @ b
            out += p[e] * (moe["down"][e]
                           @ (g / (1 + np.exp(-g)) * (moe["up"][e] @ b)))
        want[t] = out
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # the control's float8 pass departs from it
    low = np.asarray(toy.forward(w, h, dims, True))
    assert np.max(np.abs(low - got)) > 1e-3


def _packed(lead, o, k, n, m):
    """A stand-in for a packed weight: what ``costs.kept_weights`` reads."""
    return SimpleNamespace(dense_shape=(o, k),
                           values=jax.ShapeDtypeStruct(
                               (*lead, k // m, n, o), jnp.float32),
                           cfg=SimpleNamespace(n=n, m=m))


def test_active_weights_count_attention_and_the_top_experts(toy):
    dims = weights.layer_dims(TOY, toy)
    layers = 2
    attn = {name: _packed((layers,), o, 32, 2, 8)
            for name, o in (("wq", 32), ("wk", 32), ("wv", 32), ("wo", 32))}
    moe = {"router": {"w": jax.ShapeDtypeStruct((layers, 4, 32),
                                                jnp.float32)},
           "gate": _packed((layers, 4), 16, 32, 2, 8),
           "up": _packed((layers, 4), 16, 32, 2, 8),
           "down": _packed((layers, 4), 32, 16, 2, 8)}
    params = {"layers": {"attn": attn, "moe": moe}}
    attn_kept = layers * 4 * 32 * 32 * 2 // 8
    expert_kept = layers * 4 * 3 * 16 * 32 * 2 // 8
    assert costs.kept_weights(params) == attn_kept + expert_kept
    assert toy.active_weights(params, dims) == \
        attn_kept + expert_kept * 2 // 4


def test_a_kernel_family_brings_its_own_costs(bench_copy):
    _, bench_dir = bench_copy
    (bench_dir / "kernels").mkdir()
    (bench_dir / "kernels" / "demm_grouped.py").write_text(GROUPED_COST)
    peak = chipbench_tiny.PEAK
    call = spec.load_kernel_call(str(bench_dir), "demm_grouped")(GROUPED_HLO)
    assert call.flops == 2.0 * 16 * 2 * 4 * 16
    assert call.bytes == (16 * 32 * 2 + 2 * 4 * 2 * 4 * 16 * 4 + 4 * 4
                          + 16 * 32 * 4)
    # without the file, the generic count charges every expert to each row
    assert costs.kernel_call(GROUPED_HLO).flops == 4 * call.flops
    assert spec.load_kernel_call(chipbench_tiny.BENCH_DIR,
                                 "demm_grouped") is None

    us = 1000
    trace = Trace(devices={"/device:TPU:0": [
        ("demm_grouped.7", 0, 10 * us), ("demm_xwT.3", 10 * us, 15 * us),
        ("demm_grouped.7", 20 * us, 30 * us)]},
        spans=[("bench.window", 0, 40 * us)],
        kernels={"demm_grouped.7": GROUPED_HLO, "demm_xwT.3": DENSE_HLO})
    r = tracefile.reduce(trace, peak, bench_dir=str(bench_dir))
    grouped, dense = r["kernels"]["demm_grouped"], r["kernels"]["demm_xwT"]
    assert grouped["calls"] == 2 and dense["calls"] == 1
    assert grouped["s"] == pytest.approx(20e-6)
    assert dense["s"] == pytest.approx(5e-6)
    assert grouped["least_s"] == pytest.approx(2 * call.least_s(peak))
    assert dense["least_s"] == pytest.approx(
        costs.kernel_call(DENSE_HLO).least_s(peak))
    assert r["kernel_s"] == pytest.approx(grouped["s"] + dense["s"])
    assert r["kernel_least_s"] == pytest.approx(grouped["least_s"]
                                                + dense["least_s"])
    # the benchmark's own directory has no such file: the generic count
    r0 = tracefile.reduce(trace, peak)
    assert r0["kernels"]["demm_grouped"]["least_s"] == pytest.approx(
        2 * costs.kernel_call(GROUPED_HLO).least_s(peak))


def test_unknown_layer_kind_and_incomplete_modules_refused(bench_copy):
    _, bench_dir = bench_copy
    with pytest.raises(spec.SpecError, match="no_such_kind"):
        spec.layer_of({**TOY, "layer": "no_such_kind"}, str(bench_dir))
    (bench_dir / "layers" / "half.py").write_text(
        "def dims(config, base):\n    return base\n\n"
        "def tree(dims):\n    return {}\n")
    with pytest.raises(spec.SpecError,
                       match="forward, active_weights, arch_changes"):
        spec.load_layer(str(bench_dir), "half")
    (bench_dir / "kernels").mkdir()
    (bench_dir / "kernels" / "demm_x.py").write_text("COST = 1\n")
    with pytest.raises(spec.SpecError, match="call"):
        spec.load_kernel_call(str(bench_dir), "demm_x")
