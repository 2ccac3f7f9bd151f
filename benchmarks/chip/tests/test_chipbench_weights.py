"""The benchmark's weights: drawn from the seed, pruned to the stated
pattern, and kept exactly by the program's packing; the operation and
byte counts of the packed kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench import cell as cell_mod
from chipbench import costs, spec, weights

CFG = chipbench_tiny.CONFIG
BIG_SEED = 2**33 + 99
DENSE = spec.layer_of(CFG)


@pytest.fixture(scope="module")
def served():
    from repro.launch.pack_tree import pack_tree
    from repro.models.families import build_model

    model = build_model(cell_mod.arch_config(CFG, DENSE))
    return model, weights.build_served(model, CFG, DENSE, BIG_SEED,
                                       pack_tree)


def test_pattern_and_scale():
    w = weights.sparse_linear(jax.random.PRNGKey(1), 64, 256, 2, 16)
    groups = np.asarray(w).reshape(64, 16, 16)
    assert np.all((groups != 0).sum(-1) == 2)
    # rows have about unit squared norm
    assert abs(float(jnp.mean(jnp.sum(w * w, -1))) - 1.0) < 0.2


def test_topn_mask_breaks_ties_to_the_lowest_column():
    w = jnp.asarray([[1.0, 1.0, 1.0, 0.5]])
    assert np.asarray(weights.topn_mask(w, 2, 4)).tolist() == [
        [True, True, False, False]]


def test_program_packing_keeps_exactly_the_benchmarks_weights(served):
    from repro.core.sparsity import PackedWeight

    _, params = served
    dims, groups = weights.dims_of(CFG), weights.groups_of(CFG)
    key = weights.seed_key(BIG_SEED)
    for layer in range(dims["layers"]):
        want = weights.layer_weights(key, layer, DENSE.tree(dims), groups,
                                     2.0)
        for block, names in (("attn", ("wq", "wk", "wv", "wo")),
                             ("mlp", ("gate", "up", "down"))):
            for name in names:
                pw = params["layers"][block][name]
                assert isinstance(pw, PackedWeight)
                got = np.asarray(jax.tree.map(lambda a: a[layer],
                                              pw).to_dense())
                ref = np.asarray(want[block][name]["w"])
                # the same weights in the same places; the two compiled
                # draws may round the scaling differently in the last bit
                np.testing.assert_array_equal(got != 0, ref != 0)
                np.testing.assert_allclose(got, ref, rtol=1e-6, atol=0)
    top = weights.top_weights(key, dims, groups, 2.0)
    np.testing.assert_allclose(np.asarray(params["unembed"]["table"]),
                               np.asarray(top["unembed"]["table"]),
                               rtol=1e-6, atol=0)


def test_seed_changes_the_weights():
    dims, groups = weights.dims_of(CFG), weights.groups_of(CFG)
    tree = DENSE.tree(dims)
    a = weights.layer_weights(weights.seed_key(1), 0, tree, groups, 2.0)
    b = weights.layer_weights(weights.seed_key(2**32 + 1), 0, tree, groups,
                              2.0)
    assert not np.array_equal(np.asarray(a["mlp"]["up"]["w"]),
                              np.asarray(b["mlp"]["up"]["w"]))


def test_kept_weights_from_the_leaves(served):
    _, params = served
    dims = weights.dims_of(CFG)
    d, ff = dims["d"], dims["ff"]
    # (out, in, n, m) of every packed linear of one layer
    shapes = [(d, d, 1, 8)] * 4 + [(ff, d, 1, 8)] * 2 + [(d, ff, 2, 16)]
    assert costs.kept_weights(params) == dims["layers"] * sum(
        o * k * n // m for o, k, n, m in shapes)


def test_token_and_prompt_flops():
    dims = weights.dims_of(CFG)
    attn = 4 * dims["layers"] * dims["hq"] * dims["dh"]
    head = 2 * dims["vocab"] * dims["d"]
    f = costs.token_flops(1000, dims, np.array([10, 20]))
    assert f == pytest.approx(2 * (2 * 1000 + head) + attn * 30)
    # a 3-token prompt from position 0: spans 1 + 2 + 3, one head
    g = costs.prompt_flops(1000, dims, 3, 6, 1)
    assert g == pytest.approx(3 * 2 * 1000 + attn * 6 + head)
