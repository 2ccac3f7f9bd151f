"""The layer kinds change what can be added, not what is read: for the two
configurations of ``BENCHMARK.json`` (the ``dense`` kind) the weights, the
reference's gaps, the work counts and the trace reduction are the same, bit
for bit, as before layer kinds existed.

Every constant here was computed on the benchmark code before layer kinds
(commit a53e8fe) by the same expression each test evaluates, with that
code's own signatures: ``weights.layer_weights(key, i, dims, groups, 2.0)``
where the tests pass the dense kind's ``tree(dims)``;
``reference.gaps(CFG, seed, samples, [256], control=True)`` where they pass
the kind too; ``costs.kept_weights`` of ``jax.eval_shape`` of
``weights.served_builder(model, cfg, pack_tree)`` with the model of
``cell.arch_config(cfg)``, where they ask ``active_weights``; and
``tracefile.reduce(tracefile.load(fixture), chipbench_tiny.PEAK)``.  Hashes
are SHA-256: of each weight leaf's path and float32 bytes in path order, of
the gap arrays' bytes (served, then control), and of ``json.dumps`` of a
reduction's ranked lists.
"""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

import chipbench_tiny
from chipbench import cell as cell_mod
from chipbench import costs, reference, spec, tracefile, traffic, weights

CFG = chipbench_tiny.CONFIG
DENSE = spec.layer_of(CFG)

WEIGHTS = {
    7: {"layers": ["8d5748c560b30cd0d3fbc76a07be351dfc00e383f872fc29ae57b4c5"
                   "bd9496bf",
                   "b9f11000249e02e72425ff9d45b8ac187fde035cd7352259678a674f"
                   "07cd89c6"],
        "top": "cbd070c9e8a041872e5a699e7aef23a7031267c4c9970629bc19c290d738"
               "0a78"},
    2**33 + 99: {
        "layers": ["44eeef953f8d078b0e5926173889bb4d5776b9b293cfc644c67fb3d2"
                   "7f4c61e9",
                   "7bff0ab2129421fb4f13a87ad0cd3804d6fa200b3aecc96a2addddeb"
                   "1545d88a"],
        "top": "c1ff6a8d84593c5d0913a5a830cea6fd92ed45950ab91cc4575f9228cacd"
               "2a1d"},
}

GAPS = {"sha256": "3ebd7382a4d1a43a921cf0c26c4692348ba9a11fefbee582ac235b4d"
                  "f267eec6",
        "served_widest": 9.360050201416016,
        "control_widest": 0.5364136695861816}

# (token_flops, prompt_flops, kept weights of the served tree at full width)
WORK = {"stablelm_3b": (897139308286.0, 284322894588910.0, 158597120),
        "internlm2_20b_16l": (1174512891646.0, 292428712324078.0,
                              390070272)}
KEPT = 1_234_567_891

REDUCED = {
    "kernel_trace": {
        "window_s": 0.064280935, "busy_s": 0.001542768,
        "kernel_s": 0.001397876, "kernel_events": 2,
        "kernel_least_s": 2.28855873015873e-05, "gaps": 6,
        "device_ops": "b7707bfd9611f719a2f522e7f069d0c008920f7f362167d44c167a"
                      "ceac452119",
        "idle_gaps": "b95137b059f41abd63753df9d92af28150b12a26433aa4ca5d4036b"
                     "1cb4b18c9"},
    "engine_trace": {
        "window_s": 0.145013906, "busy_s": 0.080444193,
        "kernel_s": 0.06065176, "kernel_events": 140,
        "kernel_least_s": 0.0013547807570207567, "gaps": 81,
        "device_ops": "c27bbb2cc0466cc4c6d24c8ad44a2624339ff7864240d6f3cbb2dd"
                      "9f1d72e1ea",
        "idle_gaps": "0de446a20d05aea1a232986587c34e89b21007d8aa3212a75de38c4"
                     "4b09f9842"},
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                          key=lambda px: jax.tree_util.keystr(px[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x, np.float32).tobytes())
    return h.hexdigest()


def _config(name: str) -> dict:
    with open(os.path.join(chipbench_tiny.BENCH_DIR, "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_dense_kind_adds_no_sizes():
    base = weights.dims_of(CFG)
    assert weights.layer_dims(CFG, DENSE) == base
    assert DENSE.arch_changes(CFG) == {}


@pytest.mark.parametrize("seed", sorted(WEIGHTS))
def test_weights_are_the_same_bits(seed):
    dims, groups = weights.dims_of(CFG), weights.groups_of(CFG)
    key = weights.seed_key(seed)
    tree = DENSE.tree(dims)
    got = [_digest(weights.layer_weights(key, i, tree, groups, 2.0))
           for i in range(dims["layers"])]
    assert got == WEIGHTS[seed]["layers"]
    assert _digest(weights.top_weights(key, dims, groups, 2.0)) == \
        WEIGHTS[seed]["top"]


def test_reference_gaps_are_the_same_bits():
    seed = 2**31 + 5
    rng = np.random.default_rng(11)
    samples = [reference.Served(
        uid=uid, prompt=traffic.make_prompt(seed, uid, p, CFG["vocab_size"]),
        served=rng.integers(0, CFG["vocab_size"], n).astype(np.int64))
        for uid, (p, n) in enumerate([(24, 12), (41, 20), (60, 40)])]
    got = reference.gaps(CFG, DENSE, seed, samples, [256], control=True)
    h = hashlib.sha256()
    for g in got.served + got.control:
        assert g.dtype == np.float32
        h.update(np.asarray(g).tobytes())
    assert h.hexdigest() == GAPS["sha256"]
    assert reference.Gaps.widest(got.served) == GAPS["served_widest"]
    assert reference.Gaps.widest(got.control) == GAPS["control_widest"]


@pytest.mark.parametrize("name", sorted(WORK))
def test_work_counts_are_the_same(name):
    from repro.launch.pack_tree import pack_tree
    from repro.models.families import build_model

    cfg = _config(name)
    layer = spec.layer_of(cfg)
    dims = weights.layer_dims(cfg, layer)
    assert dims == weights.dims_of(cfg)
    token, prompt, kept = WORK[name]
    assert costs.token_flops(KEPT, dims, np.arange(1, 2049, 7)) == token
    assert costs.prompt_flops(KEPT, dims, 98_765, 123_456_789, 17) == prompt
    model = build_model(cell_mod.arch_config(cfg, layer))
    shapes = jax.eval_shape(
        weights.served_builder(model, cfg, layer, pack_tree),
        jax.random.PRNGKey(0))
    assert layer.active_weights(shapes, dims) == kept


@pytest.mark.parametrize("name", sorted(REDUCED))
def test_trace_reduction_is_the_same(name):
    trace = tracefile.load(os.path.join(chipbench_tiny.HERE, "fixtures",
                                        name + ".xplane.pb"))
    r = tracefile.reduce(trace, chipbench_tiny.PEAK)
    want = REDUCED[name]
    for key in ("window_s", "busy_s", "kernel_s", "kernel_events",
                "kernel_least_s", "gaps"):
        assert r[key] == want[key], key
    for key in ("device_ops", "idle_gaps"):
        assert hashlib.sha256(json.dumps(r[key]).encode()).hexdigest() == \
            want[key], key
    # one family here, so its numbers are the totals
    assert r["kernels"] == {"demm_xwT": {
        "s": r["kernel_s"], "least_s": r["kernel_least_s"],
        "calls": r["kernel_events"]}}
