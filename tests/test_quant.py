"""Tests for ``repro.quant``: int8 quantized packed execution across
pack → kernels → tune → sharding → checkpoint → serve.

Covers the ISSUE-4 acceptance set: float↔int8 parity within the symmetric
quantization error bound for both layouts on ragged and stacked-scan
shapes, the elementwise quantization-error bound, and a
pack→quantize→checkpoint→restore→serve round-trip that preserves the
``qdtype`` tag and the scales child.
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import sparse_linear as sl
from repro.core.sparse_linear import ExecPolicy
from repro.core.sparsity import (PackedWeight, SparsityConfig, pack_block,
                                 pack_block_stacked, random_sparse_dense)
from repro.quant import (activation_calibration, amax_scales,
                         dequantize_packed, quantize_packed, quantize_tree)

CFG = SparsityConfig(2, 16)


def _pw(key=0, o=16, k=64, cfg=CFG):
    params = sl.init_sparse(jax.random.PRNGKey(key), k, o, cfg)
    return params, sl.pack_params(params, cfg)


def _block_pw(key=0, o=32, k=64, cfg=CFG, block_r=8):
    w = jnp.asarray(random_sparse_dense(np.random.default_rng(key), o, k,
                                        cfg))
    return w, pack_block(w, cfg, block_r=block_r)


def _parity_tol(q, x):
    """Guaranteed output bound for symmetric round-to-nearest: every weight
    errs by <= scale/2, so |Δy| <= 0.5 * max_scale * max_row ‖x‖₁."""
    return (0.5 * float(jnp.max(q.scales))
            * float(jnp.max(jnp.sum(jnp.abs(x), axis=-1))))


# ---------------------------------------------------------------------------
# Pytree contract
# ---------------------------------------------------------------------------

def test_quantized_pytree_children_and_aux():
    _, pw = _pw()
    q = quantize_packed(pw)
    assert q.qdtype == "int8" and q.values.dtype == jnp.int8
    assert q.scales.shape == (16,) and q.scales.dtype == jnp.float32
    leaves, treedef = jax.tree_util.tree_flatten(q)
    assert len(leaves) == 3      # values, indices, scales
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.qdtype == "int8" and rebuilt.cfg == CFG
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(q)[0]]
    assert paths == [".values", ".indices", ".scales"]
    # block layout: 4 children, scales per (row-block, group, row)
    _, bpw = _block_pw()
    bq = quantize_packed(bpw)
    assert bq.scales.shape == bq.values.shape[:-2] + bq.values.shape[-1:]
    leaves_b, treedef_b = jax.tree_util.tree_flatten(bq)
    assert len(leaves_b) == 4    # + active_groups
    assert jax.tree_util.tree_unflatten(treedef_b, leaves_b).qdtype == "int8"


def test_quantized_constructor_validation():
    _, pw = _pw()
    with pytest.raises(ValueError, match="scales"):
        PackedWeight(pw.values, pw.indices, cfg=CFG, dense_shape=(16, 64),
                     qdtype="int8")                      # missing scales
    with pytest.raises(ValueError, match="qdtype"):
        PackedWeight(pw.values, pw.indices, cfg=CFG, dense_shape=(16, 64),
                     scales=jnp.ones((16,)))             # scales w/o qdtype
    with pytest.raises(ValueError, match="unknown qdtype"):
        quantize_packed(pw, "int4")
    with pytest.raises(ValueError, match="scales shape"):
        PackedWeight(jnp.zeros((4, 2, 16), jnp.int8), pw.indices, cfg=CFG,
                     dense_shape=(16, 64), scales=jnp.ones((4,)),
                     qdtype="int8")
    q = quantize_packed(pw)
    with pytest.raises(ValueError, match="already quantized"):
        quantize_packed(q)


def test_quantization_error_bound_and_dequantize():
    """Round-to-nearest symmetric: |w - deq(q(w))| <= scale/2 per row, and
    dequantize_packed returns a float node with no scales child."""
    _, pw = _pw(o=32, k=128)
    q = quantize_packed(pw)
    err = jnp.abs(q.dequantized_values() - pw.values)
    bound = 0.5 * q.scales[None, None, :] * (1 + 1e-6)
    assert bool(jnp.all(err <= bound))
    d = dequantize_packed(q)
    assert d.qdtype is None and d.scales is None
    np.testing.assert_array_equal(np.asarray(d.indices), np.asarray(q.indices))
    # amax calibration really uses the per-row max
    np.testing.assert_allclose(
        np.asarray(amax_scales(pw)),
        np.asarray(jnp.max(jnp.abs(pw.values), axis=(0, 1)) / 127.0),
        rtol=1e-6)


# ---------------------------------------------------------------------------
# Kernel parity (both layouts, ragged + stacked shapes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [5, 8])   # ragged and tile-aligned
def test_xwT_q8_parity_all_backends(batch):
    params, pw = _pw()
    q = quantize_packed(pw)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 64))
    y_f = np.asarray(sl.apply(pw, x, ExecPolicy(mode="packed")))
    tol = _parity_tol(q, x)
    ys = {}
    for backend in ("reference", "pallas_interpret", "auto"):
        y = np.asarray(sl.apply(
            q, x, ExecPolicy(mode="packed", backend=backend)))
        assert np.max(np.abs(y - y_f)) <= tol, backend
        ys[backend] = y
    # the backends agree with each other to fp precision (same dequant math)
    np.testing.assert_allclose(ys["reference"], ys["pallas_interpret"],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("batch", [5, 8])
def test_block_q8_parity_all_backends(batch):
    w, bpw = _block_pw()
    q = quantize_packed(bpw)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 64))
    y_f = np.asarray(sl.apply(bpw, x, ExecPolicy(mode="packed")))
    tol = _parity_tol(q, x)
    ys = {}
    for backend in ("reference", "block_spmm_interpret", "auto"):
        y = np.asarray(sl.apply(
            q, x, ExecPolicy(mode="packed", backend=backend)))
        assert np.max(np.abs(y - y_f)) <= tol, backend
        ys[backend] = y
    np.testing.assert_allclose(ys["reference"], ys["block_spmm_interpret"],
                               rtol=1e-4, atol=1e-5)


def test_stacked_scan_slicing_quantized():
    """quantize_tree on scan-stacked weights: tree-map layer slicing (what
    lax.scan does) slices the scales child too, for both layouts."""
    from repro.launch.pack_tree import pack_tree
    from repro.core.sparsity import Static

    w = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 32))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32))
    pol = ExecPolicy(mode="packed")
    for layout in ("xwT", "block"):
        tree = pack_tree({"layers": {"w": w, "sparsity": Static(CFG)}},
                         layout=layout, quantize="int8")
        pw = tree["layers"]
        assert pw.qdtype == "int8" and pw.stack_dims == (3,)
        assert pw.scales.shape[0] == 3
        sliced = jax.tree.map(lambda a: a[1], pw)
        # per-slice quantization of the per-slice packing gives the same node
        if layout == "block":
            br, a_max = pw.block_geom
            per = quantize_packed(pack_block(w[1], CFG, block_r=br,
                                             a_max=a_max))
        else:
            per = quantize_packed(sl.pack_params({"w": w[1]}, CFG))
        np.testing.assert_allclose(np.asarray(sl.apply(sliced, x, pol)),
                                   np.asarray(sl.apply(per, x, pol)),
                                   rtol=1e-5, atol=1e-5)


def test_activation_calibration_not_worse_on_calibration_batch():
    """The activation observer minimizes the weighted proxy over a clip grid
    that includes amax (ratio 1.0), so its true output error on the
    calibration batch should not be dramatically worse — and the scales stay
    within the searched grid of the amax baseline."""
    _, pw = _pw(o=32, k=128)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 128))
    q_amax = quantize_packed(pw)
    q_act = quantize_packed(pw, observer=activation_calibration(x))
    ratio = np.asarray(q_act.scales / q_amax.scales)
    assert np.all(ratio <= 1.0 + 1e-6) and np.all(ratio >= 0.8 - 1e-6)
    y = np.asarray(sl.apply(pw, x, ExecPolicy(mode="packed")))
    err_amax = np.abs(np.asarray(
        sl.apply(q_amax, x, ExecPolicy(mode="packed"))) - y).mean()
    err_act = np.abs(np.asarray(
        sl.apply(q_act, x, ExecPolicy(mode="packed"))) - y).mean()
    assert err_act <= err_amax * 1.5


# ---------------------------------------------------------------------------
# tune / dispatch
# ---------------------------------------------------------------------------

def test_quant_tune_cache_keys_distinct_from_float(tmp_path):
    from repro import tune

    _, pw = _pw()
    q = quantize_packed(pw)
    pf = tune.Problem.for_xwT((4, 64), (16, 64), CFG, jnp.float32)
    pq = tune.Problem.for_xwT((4, 64), (16, 64), CFG, jnp.float32,
                              quantized=True)
    assert pq.op == "xwT_q8"
    assert tune.problem_key(pf) != tune.problem_key(pq)
    _, bpw = _block_pw()
    bq = quantize_packed(bpw)
    pb = tune.Problem.for_xwT_block((4, 64), bpw, jnp.float32)
    pbq = tune.Problem.for_xwT_block((4, 64), bq, jnp.float32)
    assert pb.op == "xwT_block" and pbq.op == "xwT_block_q8"
    assert tune.problem_key(pb) != tune.problem_key(pbq)


def test_autotune_packed_tree_quant_nodes(tmp_path):
    """autotune_packed_tree recognizes quantized nodes (xwT and stacked
    block) and tunes them under their own op keys."""
    from repro import tune

    _, pw = _pw()
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 32))
    bq = quantize_packed(pack_block_stacked(w, CFG))
    tree = {"mlp": {"gate": quantize_packed(pw)}, "layers": bq}
    cache = tune.TuneCache(path=str(tmp_path / "cache.json"))
    results = tune.autotune_packed_tree(tree, 4, persist=False, cache=cache,
                                        max_measure=1, warmup=1, iters=1)
    ops = sorted(r.problem.op for r in results.values())
    assert ops == ["xwT_block_q8", "xwT_q8"]
    for r in results.values():
        assert any(c.status == "measured" for c in r.candidates)


# ---------------------------------------------------------------------------
# Sharding
# ---------------------------------------------------------------------------

def test_param_specs_shard_scales_alongside_values():
    from repro.launch.pack_tree import pack_tree
    from repro.models.layers import init_linear
    from repro.sharding.plan import ShardingPlan

    def lin(key):
        return init_linear(jax.random.PRNGKey(key), 64, 32, sparse=CFG)
    tree = pack_tree({"mlp": {"gate": lin(0), "down": lin(1)}},
                     quantize="int8")
    specs = ShardingPlan().param_specs(tree)
    assert specs["mlp"]["gate"].values == P(None, None, "model")   # col
    assert specs["mlp"]["gate"].scales == P("model")
    assert specs["mlp"]["down"].values == P("model", None, None)   # row
    assert specs["mlp"]["down"].scales == P(None)                  # no G axis
    btree = pack_tree({"mlp": {"gate": lin(0), "down": lin(1)}},
                      layout="block", quantize="int8")
    bspecs = ShardingPlan().param_specs(btree)
    assert bspecs["mlp"]["gate"].values == P("model", None, None, None)
    assert bspecs["mlp"]["gate"].scales == P("model", None, None)
    assert bspecs["mlp"]["down"].scales == P(None, None, None)
    # per-group xwT scales (G, O) shard the group axis under row-parallel —
    # it tiles the contraction dim exactly like the values' group axis
    gtree = pack_tree({"mlp": {"gate": lin(0), "down": lin(1)}},
                      quantize="int8", granularity="per_group")
    gspecs = ShardingPlan().param_specs(gtree)
    assert gspecs["mlp"]["gate"].scales == P(None, "model")
    assert gspecs["mlp"]["down"].scales == P("model", None)


@pytest.mark.parametrize("batch", [5, 8])
def test_xwT_q8_per_group_scales(batch):
    """Per-group xwT granularity: scales (G, O), tighter error than
    per-row, full backend parity (reference / Pallas / auto)."""
    params, pw = _pw(o=16, k=64)
    q = quantize_packed(pw, granularity="per_group")
    assert q.scales.shape == (4, 16)
    # per-group error bound: every value errs <= its group scale / 2
    err = jnp.abs(q.dequantized_values() - pw.values)
    assert bool(jnp.all(err <= 0.5 * q.scales[:, None, :] * (1 + 1e-6)))
    # per-group grids are never coarser than the row grid
    qr = quantize_packed(pw)
    assert bool(jnp.all(q.scales <= qr.scales[None, :] * (1 + 1e-6)))
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 64))
    ys = {}
    for backend in ("reference", "pallas_interpret", "auto"):
        ys[backend] = np.asarray(sl.apply(
            q, x, ExecPolicy(mode="packed", backend=backend)))
    np.testing.assert_allclose(ys["reference"], ys["pallas_interpret"],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ys["reference"], ys["auto"],
                               rtol=1e-4, atol=1e-5)
    # matches the dequantized dense weight exactly (the oracle)
    np.testing.assert_allclose(
        ys["reference"], np.asarray(jnp.dot(x, q.to_dense().T)),
        rtol=1e-4, atol=1e-4)


def test_per_group_granularity_validation():
    _, bpw = _block_pw()
    with pytest.raises(ValueError, match="granularity"):
        quantize_packed(bpw, granularity="per_group")   # block: already
    params, pw = _pw()
    with pytest.raises(ValueError, match="granularity"):
        quantize_packed(pw, granularity="per_tensor")


# ---------------------------------------------------------------------------
# Checkpoint round-trip + serve (the acceptance regression)
# ---------------------------------------------------------------------------

def test_quant_checkpoint_restore_serve_roundtrip():
    """pack → quantize → save → elastic restore from a shape-only template →
    serve: qdtype and scales survive and outputs are bit-identical."""
    from repro.train import checkpoint as ckpt

    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    pol = ExecPolicy(mode="packed", backend="auto")
    for make in (lambda: _pw()[1], lambda: _block_pw()[1]):
        q = quantize_packed(make())
        y = np.asarray(sl.apply(q, x, pol))
        with tempfile.TemporaryDirectory() as d:
            ckpt.save({"lin": q}, d, 1)
            template = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                {"lin": q})
            restored = ckpt.restore(template, d, 1)["lin"]
        assert restored.qdtype == "int8"
        assert restored.cfg == CFG
        assert restored.values.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(restored.scales),
                                      np.asarray(q.scales))
        np.testing.assert_array_equal(np.asarray(sl.apply(restored, x, pol)),
                                      y)


def test_quantized_decode_step_matches_float_closely():
    """A whole reduced model decodes with quantized packed weights; logits
    stay close to the float packed path (end-to-end w8a16 sanity)."""
    from repro.configs.base import get_arch
    from repro.launch.pack_tree import pack_tree
    from repro.models.families import build_model

    arch = get_arch("gemma3_1b").reduced()
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    packed = pack_tree(params)
    quant = pack_tree(params, quantize="int8")
    state = model.init_decode_state(2, 16, dtype=jnp.float32)
    toks = jnp.zeros((2, 1), jnp.int32)
    pol = ExecPolicy(mode="packed")
    l_f, _ = model.decode_step(packed, state, toks, policy=pol)
    l_q, _ = model.decode_step(quant, state, toks, policy=pol)
    # int8 per-row quantization perturbs logits only slightly
    assert float(jnp.max(jnp.abs(l_q - l_f))) < 0.15 * (
        1 + float(jnp.max(jnp.abs(l_f))))
