"""OLMoE served through the normal path at a tiny size: packed experts in
the paged engine against the plain float32 reference
(``tests/olmoe_reference.py``), dropless routing, the routing counters, and
``pack_tree`` on a layer-and-expert stack.

The tiny model keeps every structural choice of allenai/OLMoE-1B-7B-0924:
QK-norm, top-k weights not renormalised, RMSNorm eps 1e-5, packed N:M
experts (2 layers, d 256, 8 experts of width 64, top-2; 8:128 becomes
2:32 on the experts and 1:16 on attention, whose groups align to a 16-way
shard of K = 256).  It runs in float32 so the comparison sees the
program's arithmetic, not bfloat16 rounding."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import olmoe_reference
from repro import obs
from repro.configs.base import MoEConfig, get_arch
from repro.core.sparse_linear import ExecPolicy
from repro.core.sparsity import PackedWeight, SparsityConfig, prune, unpack
from repro.launch.pack_tree import pack_tree
from repro.models.families import build_model
from repro.paged import PagedServeConfig
from repro.serve import Request, make_engine

CFG = dataclasses.replace(
    get_arch("olmoe_1b_7b"), num_layers=2, d_model=256, num_heads=4,
    num_kv_heads=4, head_dim=64, vocab_size=512,
    sparsity=SparsityConfig(2, 32, 1), compute_dtype="float32",
    moe=MoEConfig(num_experts=8, experts_per_token=2, d_ff_expert=64,
                  norm_topk_prob=False))
SERVE = PagedServeConfig(num_slots=4, max_len=64, page_size=8, num_pages=40,
                         prefill_chunk=16)
BACKEND = "pallas_interpret"     # the grouped kernel, interpreted


class Recorder:
    """Greedy sampler keeping the logits row behind every token, keyed by
    (uid, position of the token it picks)."""

    def __init__(self, vocab):
        self.vocab, self.rows = vocab, {}

    def sample(self, logits, uid, pos):
        row = np.asarray(logits, np.float32)[:self.vocab]
        self.rows[(uid, pos)] = row
        return int(np.argmax(row))


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    dense = model.init(jax.random.PRNGKey(7))
    return model, dense, pack_tree(dense)


def _serve(model, params, prompts, max_new, cfg=SERVE, metrics=None):
    eng = make_engine(model, params, cfg,
                      policy=ExecPolicy(mode="packed", backend=BACKEND),
                      metrics=(obs.MetricsRegistry() if metrics is None
                               else metrics))
    eng.sampler = Recorder(CFG.vocab_size)
    reqs = [Request(uid=uid, prompt=np.asarray(p, np.int32),
                    max_new_tokens=max_new) for uid, p in prompts.items()]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng, {r.uid: list(r.output) for r in reqs}


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def test_served_tree_holds_packed_expert_stacks(model_and_params):
    _, dense, packed = model_and_params
    moe = packed["layers"]["moe"]
    for name, (o, k) in (("gate", (64, 256)), ("up", (64, 256)),
                         ("down", (256, 64))):
        pw = moe[name]
        assert isinstance(pw, PackedWeight)
        assert pw.dense_shape == (o, k) and pw.cfg == SparsityConfig(2, 32)
        assert pw.values.shape == (2, 8, k // 32, 2, o)
    # the router stays a dense float32 linear
    assert moe["router"]["w"].shape == (2, 8, 256)
    assert moe["router"]["w"].dtype == jnp.float32
    assert "sparsity" not in dense["layers"]["moe"]["router"]
    attn = packed["layers"]["attn"]
    assert attn["q_norm"]["scale"].shape == (2, 256)
    assert attn["k_norm"]["scale"].shape == (2, 256)


def test_paged_prefill_and_decode_match_the_reference(model_and_params):
    model, dense, packed = model_and_params
    prompts = {0: _prompt(0, 13), 1: _prompt(1, 21), 2: _prompt(2, 30)}
    eng, out = _serve(model, packed, prompts, max_new=5)
    rows = eng.sampler.rows
    for uid, prompt in prompts.items():
        seq = np.concatenate([prompt, out[uid][:-1]])
        want, _ = olmoe_reference.forward(dense, seq, CFG)
        for j in range(len(out[uid])):
            pos = len(prompt) + j
            # float32 end to end: the program differs from the reference
            # only in summation order (blocked matmuls, the flash
            # attention's online softmax, the grouped kernel's Sᵀ), about
            # 1e-6 of a logit per operation over 2 layers and the head;
            # a wrong expert or a missing weight moves logits by ~1
            np.testing.assert_allclose(rows[(uid, pos)], want[pos - 1],
                                       rtol=1e-4, atol=1e-4)


def test_a_tokens_logits_do_not_depend_on_its_decode_batch(
        model_and_params):
    """Dropless routing: the same request alone (slot 0) and sharing every
    decode step with three others (submitted first, so it takes slot 3 and
    its rows sort after theirs within each expert) gives the same logits,
    bit for bit."""
    model, _, packed = model_and_params
    alone, _ = _serve(model, packed, {0: _prompt(5, 11)}, max_new=6)
    shared, _ = _serve(model, packed,
                       {1: _prompt(6, 9), 2: _prompt(7, 14),
                        3: _prompt(8, 10), 0: _prompt(5, 11)}, max_new=6)
    mine = sorted(k for k in alone.sampler.rows if k[0] == 0)
    assert len(mine) == 6
    for key in mine:
        np.testing.assert_array_equal(shared.sampler.rows[key],
                                      alone.sampler.rows[key])


def test_routing_counters_equal_the_references_routing(model_and_params):
    """One slot, a prompt of whole chunks (no padding rows): the prefill
    counts each chunk's assignments and, per layer, the distinct experts
    its tokens picked; a decode step routes one token to k experts."""
    model, dense, packed = model_and_params
    metrics = obs.MetricsRegistry()
    chunk, new = SERVE.prefill_chunk, 4
    prompt = _prompt(9, 2 * chunk)
    one_slot = dataclasses.replace(SERVE, num_slots=1)
    _serve(model, packed, {0: prompt}, max_new=new, cfg=one_slot,
           metrics=metrics)
    _, routing = olmoe_reference.forward(dense, prompt, CFG)   # (L, T, k)
    layers, k = CFG.num_layers, CFG.moe.experts_per_token
    touched = sum(len(np.unique(routing[layer, c:c + chunk]))
                  for layer in range(layers)
                  for c in range(0, len(prompt), chunk))
    got = {(c["name"], c["labels"]["program"]): c["value"]
           for c in metrics.snapshot(meta=False)["counters"]
           if c["name"].startswith("serve_moe_")}
    steps = new - 1           # the first token comes from the prefill
    assert got == {
        ("serve_moe_rows_total", "prefill"): layers * len(prompt) * k,
        ("serve_moe_experts_touched_total", "prefill"): touched,
        ("serve_moe_rows_total", "decode"): steps * layers * k,
        ("serve_moe_experts_touched_total", "decode"): steps * layers * k,
    }


def test_dense_engine_counts_no_routing():
    cfg = get_arch("stablelm_3b").reduced()
    model = build_model(cfg)
    metrics = obs.MetricsRegistry()
    eng = make_engine(model, pack_tree(model.init(jax.random.PRNGKey(0))),
                      SERVE, policy=ExecPolicy(mode="packed"),
                      metrics=metrics)
    eng.submit(Request(uid=0, prompt=np.arange(9, dtype=np.int32),
                       max_new_tokens=3))
    eng.run_until_drained()
    names = {c["name"] for c in metrics.snapshot(meta=False)["counters"]}
    assert not any(n.startswith("serve_moe_") for n in names)
    assert not model.routes_experts


def test_only_the_paged_programs_return_routing_counts(model_and_params):
    """A contiguous decode state gives (logits, state); a paged one adds
    the int32 [assignments routed, experts touched] of its one step."""
    from repro.paged import PagedLayout

    model, _, packed = model_and_params
    policy = ExecPolicy(mode="packed")
    toks = jnp.zeros((2, 1), jnp.int32)
    out = model.decode_step(packed, model.init_decode_state(
        2, 16, dtype=jnp.float32), toks, policy=policy)
    assert len(out) == 2
    layout = PagedLayout.for_serve(16, page_size=8, num_pages=8,
                                   num_slots=2)
    out = model.decode_step(packed, model.init_decode_state(
        2, 16, dtype=jnp.float32, paged=layout), toks, policy=policy)
    assert len(out) == 3 and out[2].dtype == jnp.int32
    # both slots are routed top-2 in each of the 2 layers, active or not
    assert int(out[2][0]) == 2 * 2 * CFG.moe.experts_per_token
    assert 1 <= int(out[2][1]) <= 2 * 2 * CFG.moe.experts_per_token


@pytest.mark.parametrize("lead", [(2, 3), (3,)])
def test_pack_tree_round_trips_layer_and_expert_stacks(lead):
    """``(L, E, O, K)`` (and ``(E, O, K)``) stacks pack slice by slice,
    keep their leading axes and unpack to the pruned matrices."""
    cfg = SparsityConfig(2, 32)
    w = jax.random.normal(jax.random.PRNGKey(3), (*lead, 48, 128))
    pw = pack_tree({"w": w, "sparsity": cfg})
    assert isinstance(pw, PackedWeight) and pw.dense_shape == (48, 128)
    assert pw.values.shape == (*lead, 128 // 32, 2, 48)
    for idx in np.ndindex(*lead):
        np.testing.assert_array_equal(
            np.asarray(unpack(pw.values[idx], pw.indices[idx], cfg,
                              (48, 128))),
            np.asarray(prune(w[idx], cfg)))
