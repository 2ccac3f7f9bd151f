"""repro.tune subsystem: registry dispatch, autotuner pruning, cache."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core.sparsity import SparsityConfig, pack, random_sparse_dense
from repro.kernels import ref as kref
from repro.kernels.ops import demm_matmul_xwT, demm_spmm

SP = SparsityConfig(2, 16)


def _xwT_problem(rows=8, o=32, k=64):
    return tune.Problem.for_xwT((rows, k), (o, k), SP, jnp.float32)


def _packed(rng, o=32, k=64):
    w = random_sparse_dense(rng, o, k, SP)
    return w, pack(jnp.asarray(w), SP)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_has_builtin_variants():
    assert set(tune.backend_names("xwT")) >= {
        "reference", "pallas", "pallas_interpret"}
    assert set(tune.backend_names("spmm")) >= {
        "reference", "pallas", "pallas_interpret", "block_spmm"}


def test_registry_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        tune.get_variant("xwT", "nope")
    with pytest.raises(ValueError, match="unknown op"):
        tune.Problem(op="nope", rows=1, out=1, k=16, dtype="float32",
                     sparsity=(2, 16, 1))


def test_registry_platform_filtering():
    p = _xwT_problem()
    names = {v.name for v in tune.variants_for("xwT", p)}
    # this suite runs on CPU: the real-hardware kernel must be filtered out
    if tune.current_platform() != "tpu":
        assert "pallas" not in names
    assert "reference" in names


def test_registry_dispatch_equivalence_xwT():
    """Every dispatchable registered variant agrees with the oracle."""
    rng = np.random.default_rng(0)
    w, p = _packed(rng)
    x = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
    want = kref.xwT_ref(x, p.values, p.indices, SP, (32, 64))
    prob = _xwT_problem()
    for v in tune.variants_for("xwT", prob):
        got = v.call(x, p.values, p.indices, SP, (32, 64),
                     **v.default_params(prob))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=v.name)


def test_registry_dispatch_equivalence_spmm():
    rng = np.random.default_rng(1)
    a = random_sparse_dense(rng, 32, 64, SP)
    pa = pack(jnp.asarray(a), SP)
    b = jnp.asarray(rng.standard_normal((64, 48)).astype(np.float32))
    want = kref.spmm_ref(pa.values, pa.indices, b, SP, (32, 64))
    prob = tune.Problem.for_spmm((32, 64), (64, 48), SP, jnp.float32)
    for v in tune.variants_for("spmm", prob, include_measure_only=True):
        got = v.call(pa.values, pa.indices, b, SP, (32, 64),
                     **v.default_params(prob))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=v.name)


def test_custom_variant_registration_and_dispatch():
    def doubled_ref(x, values, indices, cfg, w_shape, **_):
        return kref.xwT_ref(x, values, indices, cfg, w_shape)

    v = tune.KernelVariant(
        op="xwT", name="_test_variant", call=doubled_ref,
        param_space=lambda p: {}, default_params=lambda p: {},
        supported=lambda p: True)
    tune.register_variant(v)
    try:
        with pytest.raises(ValueError, match="already registered"):
            tune.register_variant(v)
        rng = np.random.default_rng(2)
        w, p = _packed(rng)
        x = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
        got = demm_matmul_xwT(x, p.values, p.indices, SP, (32, 64),
                              backend="_test_variant")
        want = kref.xwT_ref(x, p.values, p.indices, SP, (32, 64))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5)
    finally:
        from repro.tune.registry import _REGISTRY
        _REGISTRY.pop(("xwT", "_test_variant"), None)


# ---------------------------------------------------------------------------
# VMEM-budget pruning / candidate enumeration
# ---------------------------------------------------------------------------

def test_vmem_bytes_scales_with_tiles():
    p = _xwT_problem(rows=1024, o=1024, k=1024)
    small = tune.vmem_bytes(p, "pallas", {"block_b": 8, "block_o": 8})
    big = tune.vmem_bytes(p, "pallas", {"block_b": 512, "block_o": 512})
    assert 0 < small < big
    assert tune.vmem_bytes(p, "reference", {}) == 0


def test_prune_rejects_oversize_tiles():
    p = _xwT_problem(rows=512, o=512, k=64)
    cands = tune.enumerate_candidates(p)
    tiled = [c for c in cands if c.params]
    assert tiled, "expected tile candidates to enumerate"
    # a budget below every tiled candidate's working set rejects them all
    floor = min(tune.vmem_bytes(p, c.backend, c.params) for c in tiled)
    kept = tune.prune_candidates(p, cands, vmem_budget=floor - 1)
    assert all(not c.params for c in kept)
    assert all(c.status == "pruned_vmem" for c in tiled
               if c not in kept)


def test_prune_keeps_defaults_and_ranks_by_perfmodel():
    p = _xwT_problem(rows=64, o=64, k=64)
    cands = tune.enumerate_candidates(p)
    kept = tune.prune_candidates(p, cands, max_measure=3)
    names = {(c.backend, tuple(sorted(c.params.items()))) for c in kept}
    for v in tune.variants_for("xwT", p, include_measure_only=True):
        assert (v.name, tuple(sorted(v.default_params(p).items()))) in names
    assert all(c.est_cycles is not None for c in kept if c.params)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_invalidate(tmp_path):
    path = str(tmp_path / "tune_cache.json")
    cache = tune.TuneCache(path)
    p = _xwT_problem()
    cfg = tune.TunedConfig("reference", {}, measured_us=12.5, source="tuned")
    cache.put(p, cfg, persist=True)

    fresh = tune.TuneCache(path)
    assert fresh.load() == 1
    got = fresh.get(p)
    assert got == cfg

    # a different problem key misses
    assert fresh.get(_xwT_problem(rows=16)) is None

    fresh.invalidate(p)
    assert fresh.get(p) is None

    # schema-version bump invalidates stale files
    blob = json.load(open(path))
    blob["version"] = -1
    json.dump(blob, open(path, "w"))
    stale = tune.TuneCache(path)
    assert stale.load() == 0


def test_cache_resolve_falls_back_to_heuristic(tmp_path):
    cache = tune.TuneCache(str(tmp_path / "c.json"))
    p = _xwT_problem()
    got = cache.resolve(p)
    assert got.source == "heuristic"
    if tune.current_platform() != "tpu":
        assert got.backend == "reference"


def test_heuristic_prefers_pallas_on_tpu():
    p = tune.Problem(op="xwT", rows=256, out=256, k=256, dtype="bfloat16",
                     sparsity=(8, 128, 1), platform="tpu")
    got = tune.heuristic_default(p)
    assert got.backend == "pallas"
    assert got.params == {"block_b": 128, "block_o": 128}


# ---------------------------------------------------------------------------
# Autotune end-to-end + auto backend
# ---------------------------------------------------------------------------

def test_autotune_and_auto_backend_match_reference(tmp_path):
    cache = tune.TuneCache(str(tmp_path / "c.json"))
    tune.set_default_cache(cache)
    try:
        rng = np.random.default_rng(3)
        w, p = _packed(rng)
        x = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
        res = tune.autotune_xwT(x, p.values, p.indices, SP, (32, 64),
                                max_measure=3, warmup=1, iters=2,
                                cache=cache, persist=True)
        assert res.best.measured_us > 0
        assert res.best.source == "tuned"
        # the tuned choice is never slower than any measured default
        defaults = [c for c in res.candidates if c.status == "measured"]
        assert all(res.best.measured_us <= c.measured_s * 1e6 + 1e-9
                   for c in defaults if c.measured_s)

        # dispatch through backend="auto" resolves the tuned entry and
        # matches the oracle (inside jit: resolution is trace-safe)
        got = jax.jit(
            lambda xx, vv, ii: demm_matmul_xwT(
                xx, vv, ii, SP, (32, 64), backend="auto")
        )(x, p.values, p.indices)
        want = kref.xwT_ref(x, p.values, p.indices, SP, (32, 64))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
    finally:
        tune.set_default_cache(None)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_autotune_kernel_failing_at_default_tiles(platform):
    """On a TPU a Pallas kernel that fails at its own default tiles is a
    broken kernel: autotune raises instead of recording it and quietly
    serving the reference.  Elsewhere (and at non-default tiles) a failing
    candidate is recorded and skipped."""
    from repro.tune.autotune import _autotune

    p = tune.Problem(op="xwT", rows=8, out=256, k=256, dtype="bfloat16",
                     sparsity=(8, 128, 1), platform=platform)

    def make_thunk(c):
        def thunk():
            if c.backend != "reference":
                raise RuntimeError(f"{c.backend} refused by the compiler")
            return jnp.zeros(())
        return thunk

    def run():
        return _autotune(p, make_thunk, vmem_budget=2 ** 30, max_measure=4,
                         warmup=0, iters=1, cache=None, persist=False)

    if platform == "tpu":
        with pytest.raises(RuntimeError, match="pallas refused"):
            run()
    else:
        res = run()
        assert res.best.backend == "reference"
        assert all(c.status == "error" for c in res.candidates
                   if c.backend != "reference" and c.status != "pruned_rank")


def test_auto_backend_spmm_matches_reference():
    rng = np.random.default_rng(4)
    a = random_sparse_dense(rng, 16, 32, SP)
    pa = pack(jnp.asarray(a), SP)
    b = jnp.asarray(rng.standard_normal((32, 24)).astype(np.float32))
    got = demm_spmm(pa.values, pa.indices, b, SP, (16, 32), backend="auto")
    want = kref.spmm_ref(pa.values, pa.indices, b, SP, (16, 32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_xwT_grads_unaffected_by_auto_backend():
    rng = np.random.default_rng(5)
    w, p = _packed(rng, o=16, k=32)
    x = jnp.asarray(rng.standard_normal((4, 32)).astype(np.float32))

    def loss(xx, vv, backend):
        y = demm_matmul_xwT(xx, vv, p.indices, SP, (16, 32), backend=backend)
        return jnp.sum(y ** 2)

    gx_auto, gv_auto = jax.grad(loss, argnums=(0, 1))(x, p.values, "auto")
    gx_ref, gv_ref = jax.grad(loss, argnums=(0, 1))(x, p.values, "reference")
    np.testing.assert_allclose(np.asarray(gx_auto), np.asarray(gx_ref),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gv_auto), np.asarray(gv_ref),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# Ragged (non-divisible) shapes through the padded Pallas kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bx,o", [(10, 24), (1, 32), (17, 31)])
def test_xwT_pallas_ragged_shapes(bx, o):
    from repro.kernels.demm_spmm import demm_xwT_pallas

    rng = np.random.default_rng(6)
    w = random_sparse_dense(rng, o, 48, SP)
    pw = pack(jnp.asarray(w), SP)
    x = jnp.asarray(rng.standard_normal((bx, 48)).astype(np.float32))
    got = demm_xwT_pallas(x, pw.values, pw.indices, SP, block_b=16,
                          block_o=16, interpret=True)
    want = kref.xwT_ref(x, pw.values, pw.indices, SP, (o, 48))
    assert got.shape == (bx, o)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("r,cd", [(21, 37), (8, 100), (33, 16)])
def test_spmm_pallas_ragged_shapes(r, cd):
    from repro.kernels.demm_spmm import demm_spmm_pallas

    rng = np.random.default_rng(7)
    a = random_sparse_dense(rng, r, 32, SP)
    pa = pack(jnp.asarray(a), SP)
    b = jnp.asarray(rng.standard_normal((32, cd)).astype(np.float32))
    got = demm_spmm_pallas(pa.values, pa.indices, b, SP, block_r=16,
                           block_c=16, interpret=True)
    want = kref.spmm_ref(pa.values, pa.indices, b, SP, (r, 32))
    assert got.shape == (r, cd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
