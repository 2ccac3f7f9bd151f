"""Tests for the first-class PackedWeight pytree + unified ExecPolicy API:
registration, whole-tree packing, structural sharding rules, checkpoint
round-trip onto a different mesh, and the deprecation shims."""

import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import sparse_linear as sl
from repro.core.sparse_linear import DEFAULT_POLICY, ExecPolicy, resolve_policy
from repro.core.sparsity import PackedWeight, SparsityConfig, Static
from repro.models.layers import apply_linear, init_linear

CFG = SparsityConfig(2, 16)


def _pw(key=0, o=16, k=64, cfg=CFG):
    params = sl.init_sparse(jax.random.PRNGKey(key), k, o, cfg)
    return params, sl.pack_params(params, cfg)


# ---------------------------------------------------------------------------
# Pytree registration
# ---------------------------------------------------------------------------

def test_packed_weight_is_registered_pytree():
    _, pw = _pw()
    leaves, treedef = jax.tree_util.tree_flatten(pw)
    assert len(leaves) == 2
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(rebuilt, PackedWeight)
    assert rebuilt.cfg == pw.cfg
    assert rebuilt.dense_shape == pw.dense_shape
    assert rebuilt.layout == pw.layout


def test_packed_weight_tree_map_keeps_aux():
    _, pw = _pw()
    doubled = jax.tree.map(lambda a: a * 2, pw)
    assert isinstance(doubled, PackedWeight)
    assert doubled.cfg == pw.cfg
    np.testing.assert_array_equal(np.asarray(doubled.indices),
                                  np.asarray(pw.indices) * 2)


def test_packed_weight_key_paths():
    _, pw = _pw()
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(pw)[0]]
    assert paths == [".values", ".indices"]


def test_packed_weight_static_aux_under_jit():
    params, pw = _pw()
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))

    @jax.jit
    def f(pw_, x_):
        # aux data is static: visible at trace time
        assert pw_.cfg == CFG and pw_.dense_shape == (16, 64)
        return sl.apply(pw_, x_, ExecPolicy(mode="packed"))

    np.testing.assert_allclose(np.asarray(f(pw, x)),
                               np.asarray(sl.apply_masked(params, x, CFG)),
                               rtol=1e-3, atol=1e-3)


def test_packed_weight_to_dense_roundtrip():
    params, pw = _pw()
    np.testing.assert_allclose(
        np.asarray(pw.to_dense()),
        np.asarray(jnp.where(params["w"] != 0, params["w"], 0.0)),
        rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# ExecPolicy
# ---------------------------------------------------------------------------

def test_exec_policy_hashable_and_normalized():
    a = ExecPolicy(mode="packed", backend="auto", cfg_overrides={"k": 2})
    b = ExecPolicy(mode="packed", backend="auto", cfg_overrides=(("k", 2),))
    assert a == b and hash(a) == hash(b)
    assert a.resolve_cfg(SparsityConfig(4, 32, 1)) == SparsityConfig(4, 32, 2)
    with pytest.raises(ValueError):
        ExecPolicy(mode="bogus")


def test_resolve_policy_legacy_kwargs():
    assert resolve_policy(None, None, None) is DEFAULT_POLICY
    p = resolve_policy(None, "packed", "auto")
    assert p == ExecPolicy(mode="packed", backend="auto")
    with pytest.raises(ValueError):
        resolve_policy(ExecPolicy(), "packed", None)


def test_cfg_override_k_reconfigures_packed_apply():
    """An n_effective-preserving k override reinterprets a packed weight as
    k passes (paper §II-B) without changing numerics."""
    cfg = SparsityConfig(4, 32, 1)
    params = sl.init_sparse(jax.random.PRNGKey(0), 64, 16, cfg)
    pw = sl.pack_params(params, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    base = sl.apply(pw, x, ExecPolicy(mode="packed"))
    recfg = sl.apply(pw, x, ExecPolicy(mode="packed",
                                       cfg_overrides={"n": 2, "k": 2}))
    np.testing.assert_allclose(np.asarray(base), np.asarray(recfg),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):  # layout-changing override is rejected
        sl.apply(pw, x, ExecPolicy(mode="packed", cfg_overrides={"n": 8}))


# ---------------------------------------------------------------------------
# init_linear metadata + pack_tree
# ---------------------------------------------------------------------------

def test_init_linear_stores_full_sparsity_config():
    # 256 // PRODUCTION_TP = 16 = the requested group, so choose_group keeps
    # the 4:16 pattern and init_linear re-expresses it as the requested k=2
    p = init_linear(jax.random.PRNGKey(0), 256, 32,
                    sparse=SparsityConfig(2, 16, 2))
    cfg = p["sparsity"].value
    assert isinstance(cfg, SparsityConfig)
    assert cfg.k == 2 and cfg.n_effective == 4
    assert "_sparse_m" not in p


def test_pack_tree_emits_packed_weights_including_stacked():
    from repro.launch.pack_tree import pack_tree

    cfg = SparsityConfig(2, 16)
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 32))  # stacked L=3
    tree = {"layers": {"mlp": {"gate": {"w": w, "sparsity": Static(cfg)}}},
            "norm": {"scale": jnp.ones((8,))}}
    packed = pack_tree(tree)
    pw = packed["layers"]["mlp"]["gate"]
    assert isinstance(pw, PackedWeight)
    assert pw.dense_shape == (8, 32)           # per-layer shape
    assert pw.stack_dims == (3,)
    assert pw.values.shape == (3, 2, 2, 8)     # (L, G, Ne, O)
    # dense weights untouched
    np.testing.assert_array_equal(np.asarray(packed["norm"]["scale"]),
                                  np.asarray(tree["norm"]["scale"]))
    # stacked pack == per-slice pack
    per = sl.pack_params({"w": w[1]}, cfg)
    np.testing.assert_array_equal(np.asarray(pw.values[1]),
                                  np.asarray(per.values))


# ---------------------------------------------------------------------------
# Structural sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_structural_for_packed_weights():
    from repro.sharding.plan import ShardingPlan

    cfg = SparsityConfig(2, 16)
    def lin(key):
        return init_linear(jax.random.PRNGKey(key), 64, 32, sparse=cfg)
    from repro.launch.pack_tree import pack_tree
    tree = pack_tree({"mlp": {"gate": lin(0), "down": lin(1)},
                      "attn": {"wq": lin(2)}})
    specs = ShardingPlan().param_specs(tree)
    assert isinstance(specs["mlp"]["gate"], PackedWeight)
    assert specs["mlp"]["gate"].values == P(None, None, "model")    # col
    assert specs["mlp"]["down"].values == P("model", None, None)    # row
    assert specs["attn"]["wq"].values == P(None, None, "model")     # col
    # kv-replication classifies structurally too
    tree2 = pack_tree({"attn": {"wk": lin(3)}})
    specs2 = ShardingPlan(attn_kv_replicated=True).param_specs(tree2)
    assert specs2["attn"]["wk"].values == P(None, None, None)


# ---------------------------------------------------------------------------
# Checkpoint round-trip (elastic restore onto a different mesh)
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_packed_model_different_mesh():
    """pack_tree -> save -> restore onto a (different) mesh via shardings ->
    decode step produces identical logits, SparsityConfig.k included."""
    from repro.configs.base import get_arch
    from repro.launch.pack_tree import pack_tree, pack_tree_shapes
    from repro.models.families import build_model
    from repro.sharding import partitioning as part
    from repro.train import checkpoint as ckpt

    arch = get_arch("stablelm_3b").reduced()
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    packed = pack_tree(params)

    # saved-side: unsharded host save
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(packed, d, 7)

        # restoring process: fresh template from shapes only, placed on a
        # mesh the saver never saw
        pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        template = pack_tree_shapes(model, pshapes)
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
        from repro.sharding.plan import ShardingPlan
        shardings = part.shardings_for(
            mesh, ShardingPlan().param_specs(template))
        restored = ckpt.restore(template, d, 7, shardings=shardings)

    for a, b in zip(jax.tree_util.tree_leaves(packed),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    state = model.init_decode_state(2, 16, dtype=jnp.float32)
    toks = jnp.zeros((2, 1), jnp.int32)
    pol = ExecPolicy(mode="packed")
    l0, _ = model.decode_step(packed, state, toks, policy=pol)
    l1, _ = model.decode_step(restored, state, toks, policy=pol)
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


def test_checkpoint_manifest_is_authoritative_for_sparsity():
    """A stale template (wrong k) is corrected from the manifest on restore."""
    from repro.train import checkpoint as ckpt

    cfg = SparsityConfig(1, 16, 2)
    params = sl.init_sparse(jax.random.PRNGKey(0), 32, 8, cfg)
    pw = sl.pack_params(params, cfg)
    tree = {"lin": pw, "meta": Static(cfg)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(tree, d, 1)
        stale = {"lin": pw.replace(cfg=SparsityConfig(2, 16, 1)),
                 "meta": Static(SparsityConfig(2, 16, 1))}
        restored = ckpt.restore(stale, d, 1)
    assert restored["lin"].cfg == cfg
    assert restored["meta"].value == cfg


# ---------------------------------------------------------------------------
# Legacy dict conventions: shims dropped after one release; every consumer
# now fails with a clear ValueError pointing at pack_tree / init_linear.
# ---------------------------------------------------------------------------

def test_legacy_packed_dict_rejected_everywhere():
    params, pw = _pw()
    legacy = {"values": pw.values, "indices": pw.indices,
              "shape": Static(pw.dense_shape),
              "_sparse_m": Static(CFG.m), "_sparse_n": Static(CFG.n)}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    with pytest.raises(ValueError, match="pack_tree"):
        apply_linear(legacy, x, mode="packed")
    with pytest.raises(ValueError, match="pack_tree"):
        sl.apply_packed(legacy, x, CFG)
    from repro.launch.pack_tree import pack_tree
    with pytest.raises(ValueError, match="pack_tree"):
        pack_tree({"mlp": {"gate": legacy}})
    from repro import tune
    with pytest.raises(ValueError, match="pack_tree"):
        tune.autotune_packed_tree({"mlp": {"gate": legacy}}, 4)
    from repro.sharding.plan import ShardingPlan
    with pytest.raises(ValueError, match="pack_tree"):
        ShardingPlan().param_specs({"mlp": {"gate": legacy}})


def test_legacy_masked_metadata_rejected():
    params, _ = _pw()
    legacy = {"w": params["w"], "_sparse_m": Static(CFG.m),
              "_sparse_n": Static(CFG.n)}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    with pytest.raises(ValueError, match="init_linear"):
        apply_linear(legacy, x)
    # non-dict / non-PackedWeight params keep a TypeError
    with pytest.raises(TypeError, match="PackedWeight"):
        sl.apply_packed(params["w"], x)


# ---------------------------------------------------------------------------
# Block layout (two-level ahead-of-time packing)
# ---------------------------------------------------------------------------

def _block_pw(key=0, o=32, k=64, cfg=CFG, block_r=8):
    """A dense N:M weight and its two-level block packing."""
    from repro.core.sparsity import pack_block, random_sparse_dense

    w = jnp.asarray(random_sparse_dense(np.random.default_rng(key), o, k, cfg))
    return w, pack_block(w, cfg, block_r=block_r)


def test_pack_block_geometry_and_pytree():
    from repro.core.sparsity import unpack_block

    w, pw = _block_pw()
    assert pw.layout == "block"
    br, a_max = pw.block_geom
    assert br == 8
    assert pw.values.shape == (4, a_max, CFG.n_effective, 8)
    assert pw.indices.shape == pw.values.shape
    assert pw.active_groups.shape == (4, a_max)
    # three traced children; aux (incl. geometry) survives a flatten cycle
    leaves, treedef = jax.tree_util.tree_flatten(pw)
    assert len(leaves) == 3
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.block_geom == pw.block_geom
    assert rebuilt.layout == "block" and rebuilt.cfg == CFG
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(pw)[0]]
    assert paths == [".values", ".indices", ".active_groups"]
    # lossless for a pattern-satisfying weight
    np.testing.assert_array_equal(np.asarray(pw.to_dense()), np.asarray(w))
    np.testing.assert_array_equal(
        np.asarray(unpack_block(pw.active_groups, pw.values, pw.indices,
                                CFG, pw.dense_shape)),
        np.asarray(w))


def test_block_apply_parity_vs_ref_oracle():
    """pack_block -> apply matches the kernels/ref.block_spmm_ref oracle and
    the dense matmul, on the reference and (interpret) Pallas backends."""
    from repro.kernels.ref import block_spmm_ref

    w, pw = _block_pw()
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    want_oracle = np.asarray(block_spmm_ref(
        pw.active_groups, pw.values, pw.indices, x.T, CFG, 32).T)
    want_dense = np.asarray(x @ w.T)
    for backend in ("reference", "block_spmm_interpret"):
        y = sl.apply(pw, x, ExecPolicy(mode="packed", backend=backend))
        np.testing.assert_allclose(np.asarray(y), want_oracle,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y), want_dense,
                                   rtol=1e-4, atol=1e-4)


def test_block_matches_xwT_path_through_checkpoint():
    """Acceptance regression: a block-layout PackedWeight survives
    pack -> apply -> checkpoint -> elastic restore with outputs identical
    (within tolerance) to the xwT path."""
    import tempfile

    from repro.train import checkpoint as ckpt

    w, pw_block = _block_pw()
    pw_xwT = sl.pack_params({"w": w}, CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    pol = ExecPolicy(mode="packed")
    y_xwT = np.asarray(sl.apply(pw_xwT, x, pol))
    y_block = np.asarray(sl.apply(pw_block, x, pol))
    np.testing.assert_allclose(y_block, y_xwT, rtol=1e-5, atol=1e-5)

    with tempfile.TemporaryDirectory() as d:
        ckpt.save({"lin": pw_block}, d, 1)
        # elastic restore: fresh shape-only template (as a restarted process
        # would build), manifest is authoritative for the aux
        template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            {"lin": pw_block})
        restored = ckpt.restore(template, d, 1)["lin"]
    assert restored.layout == "block"
    assert restored.block_geom == pw_block.block_geom
    assert restored.cfg == CFG
    np.testing.assert_array_equal(np.asarray(restored.active_groups),
                                  np.asarray(pw_block.active_groups))
    np.testing.assert_array_equal(np.asarray(sl.apply(restored, x, pol)),
                                  y_block)


def test_block_param_specs_structural():
    from repro.launch.pack_tree import pack_tree
    from repro.sharding.plan import ShardingPlan

    cfg = SparsityConfig(2, 16)
    def lin(key):
        w = jax.random.normal(jax.random.PRNGKey(key), (32, 64))
        return {"w": w, "sparsity": Static(cfg)}
    tree = pack_tree({"mlp": {"gate": lin(0), "down": lin(1)}},
                     layout="block")
    assert tree["mlp"]["gate"].layout == "block"
    specs = ShardingPlan().param_specs(tree)
    # col-parallel shards the row-block axis of all three children
    assert specs["mlp"]["gate"].values == P("model", None, None, None)
    assert specs["mlp"]["gate"].active_groups == P("model", None)
    # row-parallel needs active-group renumbering -> replicated for now
    assert specs["mlp"]["down"].values == P(None, None, None, None)
    assert specs["mlp"]["down"].active_groups == P(None, None)


def test_pack_tree_block_stacked_scan_slices():
    """Stacked block packing shares a_max across the stack and scan-style
    layer slicing reproduces the per-layer packing."""
    from repro.core.sparsity import pack_block
    from repro.launch.pack_tree import pack_tree

    cfg = SparsityConfig(2, 16)
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 32))  # stacked L=3
    tree = pack_tree({"layers": {"w": w, "sparsity": Static(cfg)}},
                     layout="block")
    pw = tree["layers"]
    assert pw.layout == "block" and pw.stack_dims == (3,)
    assert pw.dense_shape == (8, 32)
    br, a_max = pw.block_geom
    assert pw.values.shape == (3, 8 // br, a_max, cfg.n_effective, br)
    # slicing the layer axis (what lax.scan does) == packing that slice with
    # the shared a_max
    sliced = jax.tree.map(lambda a: a[1], pw)
    per = pack_block(w[1], cfg, block_r=br, a_max=a_max)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32))
    pol = ExecPolicy(mode="packed")
    np.testing.assert_allclose(
        np.asarray(sl.apply(sliced, x, pol)),
        np.asarray(sl.apply(per, x, pol)), rtol=1e-5, atol=1e-5)
    # stacked to_dense restores the stack dims (regression: used to crash)
    np.testing.assert_allclose(np.asarray(pw.to_dense()[1]),
                               np.asarray(per.to_dense()),
                               rtol=1e-6, atol=1e-6)
    assert pw.to_dense().shape == (3, 8, 32)


def test_autotune_packed_tree_slices_stacked_block(tmp_path):
    """A scan-stacked block tree pre-tunes by slicing one layer off (the
    decode step applies 2-D slices), instead of erroring on 5-D operands."""
    from repro import tune
    from repro.core.sparsity import pack_block_stacked

    cfg = SparsityConfig(2, 16)
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 32))
    pw = pack_block_stacked(w, cfg)
    cache = tune.TuneCache(path=str(tmp_path / "cache.json"))
    results = tune.autotune_packed_tree(
        {"layers": pw}, 4, persist=False, cache=cache,
        max_measure=1, warmup=1, iters=1)
    (res,) = results.values()
    assert res.problem.op == "xwT_block"
    assert any(c.status == "measured" for c in res.candidates)


def test_pack_block_a_max_validation_and_padding():
    from repro.core.sparsity import (pack_block, pack_block_stacked,
                                     random_sparse_dense)

    w = jnp.asarray(random_sparse_dense(np.random.default_rng(0), 8, 32,
                                        CFG))                  # G = 2
    # a_max beyond the group count pads with inactive slots (useful when
    # matching an existing checkpoint's geometry) — still lossless
    pw = pack_block(w, CFG, block_r=8, a_max=5)
    assert pw.block_geom == (8, 5)
    assert pw.values.shape == (1, 5, CFG.n_effective, 8)
    np.testing.assert_array_equal(np.asarray(pw.to_dense()), np.asarray(w))
    # an undersized explicit a_max raises — including on the stacked path,
    # whose per-slice packers run under vmap and cannot check it themselves
    # (regression: used to silently drop weights from the densest slice)
    ws = jnp.zeros((2, 8, 32)).at[0, 0, 0].set(1.0).at[0, 0, 16].set(2.0)
    with pytest.raises(ValueError, match="active groups"):
        pack_block_stacked(ws, CFG, block_r=8, a_max=1)
    with pytest.raises(ValueError, match="active groups"):
        pack_block(ws[0], CFG, block_r=8, a_max=1)


def test_block_auto_dispatch_resolves_block_spmm(tmp_path):
    """backend='auto' can resolve a block-layout weight to the block_spmm
    kernel (its interpret-mode twin on CPU): forced cache entries dispatch
    it (numerics unchanged) and the autotuner measures it as a first-class,
    dispatchable candidate."""
    from repro import tune

    cache = tune.TuneCache(path=str(tmp_path / "cache.json"))
    tune.set_default_cache(cache)
    try:
        w, pw = _block_pw()
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
        p = tune.Problem.for_xwT_block(x.shape, pw, x.dtype)
        assert f"b{pw.block_geom[0]}x{pw.block_geom[1]}" in \
            tune.problem_key(p)
        cache.put(p, tune.TunedConfig(backend="block_spmm_interpret",
                                      params={"cd_block": 8}))
        y = jax.jit(lambda pw_, x_: sl.apply(
            pw_, x_, ExecPolicy(mode="packed", backend="auto")))(pw, x)
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(sl.apply(pw, x, ExecPolicy(mode="packed"))),
            rtol=1e-5, atol=1e-5)

        res = tune.autotune_xwT_block(x, pw, cache=cache, persist=False,
                                      max_measure=2, warmup=1, iters=1)
        measured = {c.backend for c in res.candidates
                    if c.status == "measured"}
        assert "block_spmm_interpret" in measured   # not measure-only
        assert res.best.backend in measured
    finally:
        tune.set_default_cache(None)


def test_autotune_packed_tree_handles_block_layout(tmp_path):
    from repro import tune

    w, pw = _block_pw()
    cache = tune.TuneCache(path=str(tmp_path / "cache.json"))
    results = tune.autotune_packed_tree(
        {"mlp": {"gate": pw, "up": pw}}, 4, persist=False, cache=cache,
        max_measure=1, warmup=1, iters=1)
    assert len(results) == 1   # deduped by (O, K, pattern, block geometry)
    (res,) = results.values()
    assert res.problem.op == "xwT_block"
    assert (res.problem.block_r, res.problem.a_max) == pw.block_geom


def test_unknown_layout_tag_rejected():
    """The constructor rejects unknown tags, and ops keeps a clear
    ValueError (not the old 'lands later' NotImplementedError) for a forged
    layout that slips past it."""
    from repro.kernels import ops

    _, pw = _pw()
    with pytest.raises(ValueError, match="unknown layout"):
        PackedWeight(pw.values, pw.indices, cfg=CFG, dense_shape=(16, 64),
                     layout="bogus")
    forged = object.__new__(PackedWeight)
    forged.values, forged.indices = pw.values, pw.indices
    forged.cfg, forged.dense_shape = CFG, (16, 64)
    forged.layout, forged.active_groups, forged.block_geom = \
        "bogus", None, None
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    with pytest.raises(ValueError, match="unknown PackedWeight layout"):
        ops.demm_matmul_packed(x, forged)


def test_autotune_packed_tree_keys_off_type(tmp_path):
    from repro import tune

    cfg = SparsityConfig(2, 16)
    params = sl.init_sparse(jax.random.PRNGKey(0), 32, 16, cfg)
    pw = sl.pack_params(params, cfg)
    cache = tune.TuneCache(path=str(tmp_path / "cache.json"))
    results = tune.autotune_packed_tree(
        {"mlp": {"gate": pw, "up": pw}}, 4, persist=False, cache=cache,
        max_measure=1, warmup=1, iters=1)
    assert len(results) == 1  # deduped by (O, K, pattern) from static aux
    (res,) = results.values()
    assert res.problem.sparsity == (cfg.n, cfg.m, cfg.k)
