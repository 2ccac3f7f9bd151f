"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles.

Kernels run in interpret mode (CPU container; TPU is the lowering target).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # bare env: deterministic example replay
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.sparsity import SparsityConfig, pack, random_sparse_dense
from repro.kernels import demm_q8 as q8_kernels
from repro.kernels import demm_spmm as spmm_kernels
from repro.kernels import ref as kref
from repro.kernels.demm_block_spmm import (
    demm_block_spmm_pallas,
    pack_block_sparse,
)
from repro.kernels.demm_q8 import demm_xwT_q8_pallas
from repro.kernels.demm_spmm import demm_spmm_pallas, demm_xwT_pallas
from repro.kernels.ops import demm_matmul_xwT, demm_spmm


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-5)


SWEEP = [
    # (n, m, rows, groups, cd, block_r, block_c, dtype)
    (1, 8, 16, 2, 32, 8, 16, jnp.float32),
    (2, 16, 32, 4, 64, 16, 32, jnp.float32),
    (4, 32, 64, 4, 128, 32, 64, jnp.float32),
    (8, 128, 128, 2, 128, 64, 128, jnp.float32),
    (4, 64, 64, 2, 64, 64, 64, jnp.bfloat16),
    (8, 128, 256, 1, 256, 128, 256, jnp.bfloat16),
    (1, 2, 16, 8, 32, 16, 32, jnp.float32),   # fine-grained 1:2
    (1, 4, 16, 4, 32, 16, 32, jnp.float32),   # fine-grained 1:4
    # several groups a grid step, Sᵀ built in slices of lcm(M, 8) rows
    (5, 80, 128, 16, 16, 128, 16, jnp.float32),   # 8 groups a step
    (3, 48, 128, 8, 16, 128, 16, jnp.bfloat16),   # 8 groups a step
    (2, 4, 128, 64, 16, 128, 16, jnp.float32),    # 2 groups a slice
    (8, 16, 128, 16, 16, 128, 16, jnp.float32),
]


@pytest.mark.parametrize("n,m,rows,groups,cd,br,bc,dtype", SWEEP)
def test_spmm_kernel_vs_oracle(n, m, rows, groups, cd, br, bc, dtype):
    rng = np.random.default_rng(n * 1000 + m)
    cfg = SparsityConfig(n, m)
    a = random_sparse_dense(rng, rows, groups * m, cfg).astype(np.float32)
    b = rng.standard_normal((groups * m, cd)).astype(np.float32)
    p = pack(jnp.asarray(a, dtype), cfg)
    bj = jnp.asarray(b, dtype)
    got = demm_spmm_pallas(p.values, p.indices, bj, cfg,
                           block_r=br, block_c=bc, interpret=True)
    want = kref.spmm_ref(p.values, p.indices, bj, cfg, (rows, groups * m))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("n,m,rows,groups,cd,br,bc,dtype", SWEEP)
def test_xwT_kernel_vs_oracle(n, m, rows, groups, cd, br, bc, dtype):
    rng = np.random.default_rng(n * 7000 + m)
    cfg = SparsityConfig(n, m)
    w = random_sparse_dense(rng, rows, groups * m, cfg).astype(np.float32)
    x = rng.standard_normal((cd, groups * m)).astype(np.float32)
    p = pack(jnp.asarray(w, dtype), cfg)
    xj = jnp.asarray(x, dtype)
    got = demm_xwT_pallas(xj, p.values, p.indices, cfg,
                          block_b=min(bc, cd), block_o=br, interpret=True)
    want = kref.xwT_ref(xj, p.values, p.indices, cfg, (rows, groups * m))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


def _full_tile_scatter(values_ref, indices_ref, m, scales=None):
    """The Sᵀ expansion as it was before slicing, kept as the oracle: every
    group's select passes sweep all ``chunk * M`` rows of the step."""
    chunk, n, cols = values_ref.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk * m, cols), 0)
    s = jnp.zeros((chunk * m, cols), jnp.float32)
    for g in range(chunk):
        for j in range(n):
            v = values_ref[g, j:j + 1, :].astype(jnp.float32)
            if scales is not None:
                v = v * scales[g]
            target = indices_ref[g, j:j + 1, :] + g * m
            s = s + jnp.where(rows == target, v, 0.0)
    return s


def _bits(a):
    return np.asarray(a).view(np.uint32)


# (n, m, groups): chunk 8 at 5:80, 3:48 and 8:16 (one group a slice),
# chunk 32 at 2:4 (two groups a slice), chunk 1 at 8:128 (unchanged)
SLICED = [(5, 80, 16), (3, 48, 8), (2, 4, 64), (8, 16, 16), (8, 128, 2)]


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("op", ["xwT", "xwT_q8"])
@pytest.mark.parametrize("n,m,groups", SLICED)
def test_sliced_scatter_bitwise_equals_full_tile(monkeypatch, n, m, groups,
                                                 op, x_dtype):
    """Building Sᵀ a slice at a time adds to each row exactly the terms the
    full-tile sweep adds, in the same order, less its ``+0.0`` terms: the
    kernels' outputs are bit for bit the full-tile expansion's.  Operands
    repeat indices inside a group; the int8 kernel takes per-group
    scales."""
    rng = np.random.default_rng(n * 100 + m)
    o, bx = 256, 8
    cfg = SparsityConfig(n, m)
    idx = rng.integers(0, m, (groups, n, o)).astype(np.int32)
    if n > 1:
        idx[:, 1, ::2] = idx[:, 0, ::2]            # repeated indices
    x = rng.standard_normal((bx, groups * m))
    if op == "xwT":
        vals = rng.standard_normal((groups, n, o)).astype(np.float32)
        vals[:, -1, ::3] = 0.0
        kernel, operands = demm_xwT_pallas, [vals, idx]
    else:
        vals = rng.integers(-127, 128, (groups, n, o)).astype(np.int8)
        scales = rng.uniform(0.001, 0.02, (groups, o)).astype(np.float32)
        kernel, operands = demm_xwT_q8_pallas, [vals, idx, scales]
    # The unjitted body, so each call traces the expansion patched in now.
    call = functools.partial(
        kernel.__wrapped__, jnp.asarray(x, x_dtype),
        *map(jnp.asarray, operands), cfg, block_b=8, block_o=128,
        interpret=True)
    got = call()
    monkeypatch.setattr(spmm_kernels, "_scatter_matrix", _full_tile_scatter)
    monkeypatch.setattr(q8_kernels, "_scatter_matrix", _full_tile_scatter)
    want = call()
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("block_r", [8, 16, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_spmm_kernel_vs_oracle(block_r, dtype):
    rng = np.random.default_rng(99)
    cfg = SparsityConfig(2, 16)
    a = random_sparse_dense(rng, 64, 128, cfg)
    # zero out some whole groups to exercise block skipping
    a = a.reshape(64, 8, 16)
    a[:, 3, :] = 0
    a[:32, 5, :] = 0
    a = a.reshape(64, 128)
    b = rng.standard_normal((128, 64)).astype(np.float32)
    ag, vals, idxs, a_max = pack_block_sparse(a, cfg, block_r=block_r)
    assert a_max < 8, "block skipping must actually skip groups"
    got = demm_block_spmm_pallas(
        jnp.asarray(ag), jnp.asarray(vals, dtype), jnp.asarray(idxs),
        jnp.asarray(b, dtype), cfg, r=64, cd_block=32, interpret=True)
    want = a.astype(np.float32) @ b
    np.testing.assert_allclose(np.asarray(got), want, **_tol(dtype))


def test_block_spmm_all_zero_rowblock():
    cfg = SparsityConfig(2, 16)
    a = np.zeros((32, 64), np.float32)
    b = np.ones((64, 32), np.float32)
    ag, vals, idxs, _ = pack_block_sparse(a, cfg, block_r=16)
    got = demm_block_spmm_pallas(
        jnp.asarray(ag), jnp.asarray(vals), jnp.asarray(idxs),
        jnp.asarray(b), cfg, r=32, cd_block=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), 0.0)


@settings(max_examples=12, deadline=None)
@given(
    n=st.sampled_from([1, 2, 4]),
    groups=st.sampled_from([1, 2, 3]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_kernel_matches_oracle(n, groups, seed):
    """Random patterns, random shapes: kernel == oracle."""
    m = 16
    cfg = SparsityConfig(n, m)
    rng = np.random.default_rng(seed)
    rows, cd = 32, 32
    a = random_sparse_dense(rng, rows, groups * m, cfg)
    b = rng.standard_normal((groups * m, cd)).astype(np.float32)
    p = pack(jnp.asarray(a), cfg)
    got = demm_spmm_pallas(p.values, p.indices, jnp.asarray(b), cfg,
                           block_r=16, block_c=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), a @ b, rtol=1e-4, atol=1e-5)


def test_ops_backend_dispatch_and_grads():
    rng = np.random.default_rng(5)
    cfg = SparsityConfig(4, 32)
    w = random_sparse_dense(rng, 64, 128, cfg)
    x = rng.standard_normal((16, 128)).astype(np.float32)
    p = pack(jnp.asarray(w), cfg)
    outs = {
        be: np.asarray(demm_matmul_xwT(jnp.asarray(x), p.values, p.indices,
                                       cfg, (64, 128), be))
        for be in ("reference", "pallas_interpret")
    }
    np.testing.assert_allclose(outs["reference"], outs["pallas_interpret"],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(outs["reference"], x @ w.T, rtol=1e-4, atol=1e-5)

    # gradient only lives on the non-zero coordinates
    def loss(v):
        return jnp.sum(
            demm_matmul_xwT(jnp.asarray(x), v, p.indices, cfg, (64, 128),
                            "reference") ** 2)
    gv = np.asarray(jax.grad(loss)(p.values))
    assert np.all((gv != 0) <= (np.asarray(p.values) != 0))

    with pytest.raises(ValueError):
        demm_matmul_xwT(jnp.asarray(x), p.values, p.indices, cfg, (64, 128),
                        "not_a_backend")


def test_spmm_op_backends_agree():
    rng = np.random.default_rng(6)
    cfg = SparsityConfig(2, 16)
    a = random_sparse_dense(rng, 32, 64, cfg)
    b = rng.standard_normal((64, 32)).astype(np.float32)
    p = pack(jnp.asarray(a), cfg)
    r1 = demm_spmm(p.values, p.indices, jnp.asarray(b), cfg, (32, 64),
                   "reference")
    r2 = demm_spmm(p.values, p.indices, jnp.asarray(b), cfg, (32, 64),
                   "pallas_interpret")
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-4,
                               atol=1e-5)
