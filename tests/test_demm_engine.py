"""Tests for the functional DeMM engine model + pruning schedules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.demm import (
    DeMMConfig,
    demm_spmm,
    demm_spmm_k_passes,
    multiply_reduce,
    read_ports,
)
from repro.core.pruning import (
    PruneSchedule,
    init_mask,
    masked_weight,
    maybe_update_mask,
    rigl_update_mask,
)
from repro.core.sparsity import (
    SparsityConfig,
    pack,
    random_sparse_dense,
    satisfies_pattern,
)

TOL = dict(rtol=1e-4, atol=1e-5)


def test_read_ports_select_rows():
    b = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    idx = jnp.asarray([[0, 3], [7, 7]], jnp.int32)
    rows = read_ports(b, idx)
    assert rows.shape == (2, 2, 4)
    np.testing.assert_allclose(rows[0, 1], np.asarray(b[3]))
    np.testing.assert_allclose(rows[1, 0], np.asarray(b[7]))


def test_multiply_reduce_adder_tree():
    rows = jnp.ones((2, 4, 8))
    vals = jnp.asarray([[1.0, 2.0, 3.0, 4.0], [0.0, 0.0, 0.0, 0.0]])
    out = multiply_reduce(rows, vals)
    np.testing.assert_allclose(out[0], 10.0 * np.ones(8))
    np.testing.assert_allclose(out[1], np.zeros(8))


@pytest.mark.parametrize("n,m,groups", [(1, 4, 2), (2, 16, 4), (8, 128, 2)])
def test_engine_matches_dense(n, m, groups):
    rng = np.random.default_rng(n + m)
    cfg = SparsityConfig(n, m)
    a = random_sparse_dense(rng, 32, groups * m, cfg)
    b = rng.standard_normal((groups * m, 48)).astype(np.float32)
    p = pack(jnp.asarray(a), cfg)
    np.testing.assert_allclose(np.asarray(demm_spmm(p, jnp.asarray(b))),
                               a @ b, **TOL)


@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_k_reconfiguration_equivalence(k):
    """Paper §II-B: a DeMM(N,M,·,k) engine computes the kN:M pattern in k
    passes with identical results."""
    rng = np.random.default_rng(k)
    cfg = SparsityConfig(8, 64)
    a = random_sparse_dense(rng, 16, 128, cfg)
    b = rng.standard_normal((128, 32)).astype(np.float32)
    p = pack(jnp.asarray(a), cfg)
    np.testing.assert_allclose(
        np.asarray(demm_spmm_k_passes(p, jnp.asarray(b), k=k)), a @ b, **TOL)


def test_demm_config_supports():
    eng = DeMMConfig(n=8, m=128, c=64, k=8)
    assert eng.multipliers == 512  # the paper's resource-equalized setup
    assert eng.supports(SparsityConfig(8, 128))
    assert eng.supports(SparsityConfig(16, 128))   # 16:128 == 2x8:128
    assert eng.supports(SparsityConfig(64, 128))   # 1:2-equivalent
    assert not eng.supports(SparsityConfig(8, 256))  # different M
    assert not eng.supports(SparsityConfig(65, 128))  # beyond k*N


def test_straight_through_gradients():
    cfg = SparsityConfig(1, 4)
    w = jnp.asarray([[1.0, 2.0, 0.5, 0.25]])

    def loss(w):
        return jnp.sum(masked_weight(w, cfg) * 3.0)

    g = np.asarray(jax.grad(loss)(w))
    # straight-through: gradient reaches masked-out weights too
    np.testing.assert_allclose(g, 3.0 * np.ones((1, 4)))
    # forward is masked
    np.testing.assert_allclose(np.asarray(masked_weight(w, cfg)),
                               [[0.0, 2.0, 0.0, 0.0]])


def test_rigl_update_keeps_pattern_and_regrows():
    cfg = SparsityConfig(2, 8)
    sched = PruneSchedule(cfg=cfg, update_every=1, regrow_fraction=0.5)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32))
    mask = init_mask(w, cfg)
    # gradient strongly favours position 0 of each group
    grad = jnp.zeros((4, 16)).at[:, 0].set(100.0).at[:, 8].set(100.0)
    new_mask = rigl_update_mask(w, mask, grad, sched)
    nm = np.asarray(new_mask).reshape(4, 2, 8)
    assert np.all(nm.sum(-1) == 2)           # exactly N per group
    assert np.all(nm[:, :, 0])               # regrown at max-gradient slot


def test_maybe_update_mask_schedule():
    cfg = SparsityConfig(1, 4)
    sched = PruneSchedule(cfg=cfg, update_every=10, stop_update_after=100)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((2, 8)),
                    jnp.float32)
    mask = init_mask(w, cfg)
    grad = jnp.ones_like(w)
    same = maybe_update_mask(jnp.asarray(7), w, mask, grad, sched)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(mask))
    frozen = maybe_update_mask(jnp.asarray(110), w, mask, grad, sched)
    np.testing.assert_array_equal(np.asarray(frozen), np.asarray(mask))


def test_sparse_linear_roundtrip_train_to_serve():
    from repro.core import sparse_linear as sl
    from repro.core.sparse_linear import ExecPolicy
    from repro.core.sparsity import PackedWeight

    cfg = SparsityConfig(2, 16)
    key = jax.random.PRNGKey(0)
    params = sl.init_sparse(key, 64, 32, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    y_masked = sl.apply_masked(params, x, cfg)
    packed = sl.pack_params(params, cfg)
    assert isinstance(packed, PackedWeight)
    for backend in ("reference", "pallas_interpret"):
        y_packed = sl.apply(packed, x, ExecPolicy(mode="packed",
                                                  backend=backend))
        np.testing.assert_allclose(np.asarray(y_masked), np.asarray(y_packed),
                                   rtol=1e-3, atol=1e-3)
    # the packed weight satisfies the pattern by construction
    assert satisfies_pattern(packed.to_dense(), cfg)


def test_sparse_linear_k_reconfiguration_survives_pack():
    """Regression: a k>1 SparsityConfig must survive pack -> apply (the old
    dict convention rebuilt SparsityConfig(n, m, 1), silently dropping the
    paper's k-reconfiguration)."""
    from repro.core import sparse_linear as sl
    from repro.core.sparse_linear import ExecPolicy

    cfg = SparsityConfig(2, 32, k=2)   # 4:32 served as 2 passes of 2:32
    params = sl.init_sparse(jax.random.PRNGKey(0), 64, 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    pw = sl.pack_params(params, cfg)
    assert pw.cfg == cfg and pw.cfg.k == 2
    assert pw.values.shape[-2] == cfg.n_effective == 4
    y_masked = sl.apply_masked(params, x, cfg)
    y_packed = sl.apply(pw, x, ExecPolicy(mode="packed"))
    np.testing.assert_allclose(np.asarray(y_masked), np.asarray(y_packed),
                               rtol=1e-3, atol=1e-3)
