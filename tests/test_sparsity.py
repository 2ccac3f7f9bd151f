"""Unit + property tests for the relaxed N:M sparsity format."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # bare env: deterministic example replay
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.sparsity import (
    PATTERNS,
    SparsityConfig,
    group_nonzero_counts,
    pack,
    prune,
    prune_mask,
    random_sparse_dense,
    reconfigure_k,
    satisfies_pattern,
    unpack_packed,
)

jax.config.update("jax_enable_x64", False)


def test_config_validation():
    with pytest.raises(ValueError):
        SparsityConfig(n=0, m=4)
    with pytest.raises(ValueError):
        SparsityConfig(n=4, m=4, k=2)  # kN > M
    cfg = SparsityConfig(8, 128, 1)
    assert cfg.density == pytest.approx(8 / 128)
    assert cfg.pattern_name() == "8:128"
    assert SparsityConfig(8, 128, 8).pattern_name() == "64:128 (as 8x8:128)"


def test_compression_ratio_8_128():
    cfg = PATTERNS["8:128"]
    # bf16 values + int8 indices: 128*2 / (8*3) ≈ 10.7x
    assert cfg.compression_ratio(2, 1) == pytest.approx(256 / 24)
    # with int32 indices it is 128*2/(8*6)
    assert cfg.compression_ratio(2, 4) == pytest.approx(256 / 48)


def test_prune_satisfies_pattern():
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((16, 256)).astype(np.float32))
    for name in ("1:2", "1:4", "1:8", "8:128", "4:64"):
        cfg = PATTERNS[name]
        pruned = prune(a, cfg)
        assert satisfies_pattern(pruned, cfg), name
        counts = group_nonzero_counts(pruned, cfg)
        # dense random input -> pruning keeps exactly n_effective per group
        assert int(counts.min()) == cfg.n_effective


def test_prune_keeps_largest_magnitudes():
    cfg = SparsityConfig(2, 4)
    a = jnp.asarray([[1.0, -5.0, 0.25, 3.0, 0.1, 0.2, -0.3, 0.05]])
    pruned = np.asarray(prune(a, cfg))
    np.testing.assert_allclose(pruned, [[0.0, -5.0, 0.0, 3.0, 0.0, 0.2, -0.3, 0.0]])


def test_prune_is_identity_on_underfull_groups():
    """Relaxed "at most N" groups with fewer than n_effective non-zeros must
    survive pruning untouched (regression: the tie-resolution used to count
    leading zeros against the 0-threshold and drop the real non-zeros)."""
    cfg = SparsityConfig(2, 16)
    a = np.zeros((2, 32), np.float32)
    a[0, 8] = -0.7          # 1 non-zero, late in the group
    a[1, 20] = 0.3          # 1 non-zero in the second group
    a[1, 30] = -0.2
    pruned = np.asarray(prune(jnp.asarray(a), cfg))
    np.testing.assert_array_equal(pruned, a)


def test_pack_unpack_roundtrip_exact():
    rng = np.random.default_rng(2)
    cfg = SparsityConfig(4, 32)
    a = random_sparse_dense(rng, 24, 128, cfg)
    p = pack(jnp.asarray(a), cfg)
    np.testing.assert_allclose(np.asarray(unpack_packed(p)), a, rtol=1e-6)


def test_pack_prunes_nonconforming():
    cfg = SparsityConfig(1, 4)
    a = jnp.asarray([[1.0, -2.0, 0.0, 0.0]])  # 2 nonzeros in a 1:4 group
    p = pack(a, cfg)
    got = np.asarray(unpack_packed(p))
    np.testing.assert_allclose(got, [[0.0, -2.0, 0.0, 0.0]])


def test_reconfigure_k_views():
    rng = np.random.default_rng(3)
    cfg = SparsityConfig(8, 64)  # 8:64 packed
    a = random_sparse_dense(rng, 8, 128, cfg)
    p = pack(jnp.asarray(a), cfg)
    split = reconfigure_k(p, k=4)  # view as 4 passes of 2:64
    assert split.values.shape == (2 * 4, 2, 8)
    assert split.cfg.n == 2 and split.cfg.k == 4
    # the multiset of (value) entries is preserved
    np.testing.assert_allclose(
        np.sort(np.asarray(split.values).ravel()),
        np.sort(np.asarray(p.values).ravel()),
    )


@settings(max_examples=25, deadline=None)
@given(
    n=st.sampled_from([1, 2, 4, 8]),
    m=st.sampled_from([8, 16, 32, 128]),
    rows=st.sampled_from([1, 4, 16]),
    groups=st.sampled_from([1, 2, 4]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_prune_pack_unpack(n, m, rows, groups, seed):
    """For any dense matrix: prune->pack->unpack is idempotent and satisfies
    the pattern; pack drops nothing that prune kept."""
    if n > m:
        return
    cfg = SparsityConfig(n, m)
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((rows, groups * m)).astype(np.float32))
    pruned = prune(a, cfg)
    assert satisfies_pattern(pruned, cfg)
    roundtrip = unpack_packed(pack(pruned, cfg))
    np.testing.assert_allclose(np.asarray(roundtrip), np.asarray(pruned), rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_mask_is_topk(seed):
    cfg = SparsityConfig(4, 16)
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((8, 64)).astype(np.float32)
    mask = np.asarray(prune_mask(jnp.asarray(a), cfg))
    grp = np.abs(a.reshape(8, 4, 16))
    kept = np.where(mask.reshape(8, 4, 16), grp, -1.0)
    dropped = np.where(mask.reshape(8, 4, 16), np.inf, grp)
    # min kept magnitude >= max dropped magnitude, per group
    assert np.all(
        np.min(np.where(kept < 0, np.inf, kept), axis=-1)
        >= np.max(np.where(np.isinf(dropped), -np.inf, dropped), axis=-1)
    )


# ---------------------------------------------------------------------------
# k-reconfigured tiers on block / q8 / stacked-scan layouts (the draft-tier
# correctness foundation, DESIGN.md §15 — only xwT was covered before)
# ---------------------------------------------------------------------------

def _topk_per_group(dense: np.ndarray, m: int, t: int) -> np.ndarray:
    """Keep the magnitude-top-``t`` entries of every 1×m group."""
    *lead, k = dense.shape
    g = dense.reshape(*lead, k // m, m)
    order = np.argsort(-np.abs(g), axis=-1, kind="stable")
    mask = np.zeros_like(g, dtype=bool)
    np.put_along_axis(mask, order[..., :t], True, axis=-1)
    return np.where(mask, g, 0.0).reshape(dense.shape)


def _check_tier_and_reconfig(pw, dense_pruned, t=4):
    from repro.core.sparse_linear import _reconfigure
    from repro.core.sparsity import narrow_tier, tier_sort_packed
    from repro.kernels import ops

    cfg = pw.cfg
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (3, pw.in_features)).astype(np.float32))
    y_full = ops.demm_matmul_packed(x, pw, backend="reference")

    # k-retag round-trip: kN:M <-> (N, M, k) views share buffers and output
    split = _reconfigure(pw, SparsityConfig(cfg.n_effective // 2, cfg.m, 2))
    assert split.values is pw.values and split.indices is pw.indices
    back = _reconfigure(split, cfg)
    assert back.cfg == cfg and back.values is pw.values
    for view in (split, back):
        np.testing.assert_allclose(
            np.asarray(ops.demm_matmul_packed(x, view, backend="reference")),
            np.asarray(y_full), rtol=1e-5, atol=1e-5)

    # tier view: sort once, then the tier_ne prefix IS the magnitude-top-t
    # sub-pattern — and sorting itself never changes full-tier results
    srt = tier_sort_packed(pw)
    np.testing.assert_allclose(np.asarray(srt.to_dense()),
                               np.asarray(pw.to_dense()), rtol=1e-6)
    draft = srt.replace(tier_ne=t)
    assert draft.values is srt.values  # view, not copy
    got = np.asarray(narrow_tier(draft).to_dense())
    want = _topk_per_group(np.asarray(dense_pruned), cfg.m, t)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_reconfigured_tier_block_layout():
    from repro.core.sparsity import LAYOUT_BLOCK, PackedWeight

    rng = np.random.default_rng(7)
    cfg = SparsityConfig(8, 16, 1)
    w = jnp.asarray(rng.standard_normal((16, 64)).astype(np.float32))
    pw = PackedWeight.from_dense(w, cfg, layout=LAYOUT_BLOCK)
    _check_tier_and_reconfig(pw, prune(w, cfg))


def test_reconfigured_tier_q8_layout():
    from repro.core.sparsity import PackedWeight
    from repro.quant import quantize_packed

    rng = np.random.default_rng(8)
    cfg = SparsityConfig(8, 16, 1)
    w = jnp.asarray(rng.standard_normal((16, 64)).astype(np.float32))
    q8 = quantize_packed(PackedWeight.from_dense(w, cfg))
    assert q8.qdtype is not None
    # the tier comparison target is the *dequantized* pruned weight: the
    # per-row scale is constant along Ne, so raw int magnitude order is
    # dequant magnitude order
    _check_tier_and_reconfig(q8, q8.to_dense())


def test_reconfigured_tier_stacked_scan():
    """Layer-stacked (scan) weights: both packed layouts keep the tier and
    k-retag semantics per layer."""
    from repro.core.sparsity import narrow_tier, tier_sort_packed
    from repro.launch.pack_tree import _pack_sparse_linear

    rng = np.random.default_rng(9)
    cfg = SparsityConfig(8, 16, 1)
    w = jnp.asarray(rng.standard_normal((3, 8, 64)).astype(np.float32))
    for layout in ("xwT", "block"):
        pw = _pack_sparse_linear({"w": w}, cfg, layout=layout)
        assert pw.stack_dims == (3,)
        srt = tier_sort_packed(pw)
        np.testing.assert_allclose(np.asarray(srt.to_dense()),
                                   np.asarray(pw.to_dense()), rtol=1e-6)
        got = np.asarray(narrow_tier(srt.replace(tier_ne=4)).to_dense())
        want = np.stack([_topk_per_group(np.asarray(prune(w[i], cfg)),
                                         cfg.m, 4) for i in range(3)])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
