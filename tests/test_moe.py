"""MoE dispatch invariants: token conservation, capacity drops, routing
determinism (property-based), and the dropless dispatch against a
per-token loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # bare env: deterministic example replay
    from _hypothesis_compat import given, settings, strategies as st

from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod


def _setup(e=8, k=2, d=32, f=16, seed=0, norm_topk_prob=True):
    cfg = MoEConfig(num_experts=e, experts_per_token=k, d_ff_expert=f,
                    norm_topk_prob=norm_topk_prob)
    params = moe_mod.init_moe(jax.random.PRNGKey(seed), d, cfg, sparse=None)
    return cfg, params


def test_identity_experts_preserve_token_mix():
    """With identity-like experts (w_down @ w_up ≈ scaled identity is hard;
    instead zero experts), the output is exactly zero — no token leaks."""
    cfg, params = _setup()
    params = dict(params, down={"w": jnp.zeros_like(params["down"]["w"])})
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    y, aux = moe_mod.apply_moe(params, x, cfg, capacity=64)
    np.testing.assert_allclose(np.asarray(y), 0.0)


def test_capacity_drops_are_passthrough_zero():
    """capacity=1 forces drops; dropped tokens contribute zero output (the
    residual connection outside the MoE carries them)."""
    cfg, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    y_full, _ = moe_mod.apply_moe(params, x, cfg, capacity=64)
    y_tight, _ = moe_mod.apply_moe(params, x, cfg, capacity=1)
    # tight capacity must zero *some* token outputs
    z_full = np.mean(np.all(np.asarray(y_full) == 0, axis=-1))
    z_tight = np.mean(np.all(np.asarray(y_tight) == 0, axis=-1))
    assert z_tight > z_full


def test_top1_routing_selects_argmax_expert():
    cfg, params = _setup(e=4, k=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 32))
    logits = x.reshape(-1, 32) @ params["router"]["w"].T
    top = np.argmax(np.asarray(logits), -1)
    # perturb one expert's weights to NaN; tokens routed there go NaN
    bad = int(top[0])
    wg = params["gate"]["w"].at[bad].set(jnp.nan)
    y, _ = moe_mod.apply_moe(dict(params, gate={"w": wg}), x, cfg,
                             capacity=8)
    yn = np.isnan(np.asarray(y)).any(-1)[0]
    assert yn[0]  # token 0 hit the poisoned expert
    for t in range(1, 4):
        assert yn[t] == (top[t] == bad)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       e=st.sampled_from([4, 8]),
       k=st.sampled_from([1, 2]))
def test_property_moe_finite_and_deterministic(seed, e, k):
    cfg, params = _setup(e=e, k=k, seed=seed % 1000)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 8, 32))
    y1, a1 = moe_mod.apply_moe(params, x, cfg, capacity=32)
    y2, a2 = moe_mod.apply_moe(params, x, cfg, capacity=32)
    assert np.all(np.isfinite(np.asarray(y1)))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert float(a1) == float(a2) >= 0.0


def test_aux_loss_penalizes_imbalance():
    cfg, params = _setup(e=4, k=1)
    # router forced to send everything to expert 0
    w = jnp.zeros_like(params["router"]["w"]).at[0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32))
    _, aux_skew = moe_mod.apply_moe(
        dict(params, router={"w": w}), x, cfg, capacity=64)
    _, aux_uniform = moe_mod.apply_moe(
        dict(params, router={"w": jnp.zeros_like(w)}), x, cfg, capacity=64)
    assert float(aux_skew) > float(aux_uniform)


def test_expert_stacks_are_out_by_in():
    cfg, params = _setup(e=4, d=32, f=16)
    assert params["gate"]["w"].shape == (4, 16, 32)
    assert params["up"]["w"].shape == (4, 16, 32)
    assert params["down"]["w"].shape == (4, 32, 16)
    assert params["router"]["w"].shape == (4, 32)


def _per_token(params, x, cfg):
    """Each token through its top-k experts, one at a time (float64)."""
    p = {k: np.asarray(v["w"], np.float64) for k, v in params.items()}
    out = np.zeros_like(x, np.float64)
    for t, v in enumerate(np.asarray(x, np.float64)):
        logits = p["router"] @ v
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs)[:cfg.experts_per_token]
        w = probs[top]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        for e, we in zip(top, w):
            g = p["gate"][e] @ v
            out[t] += we * (p["down"][e] @ (g / (1 + np.exp(-g))
                                            * (p["up"][e] @ v)))
    return out


@pytest.mark.parametrize("norm", [True, False])
def test_dropless_matches_a_per_token_loop(norm):
    cfg, params = _setup(e=8, k=2, norm_topk_prob=norm)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 32))
    y, aux, stats = moe_mod.apply_moe(params, x, cfg, stats=True)
    want = _per_token(params, x.reshape(-1, 32), cfg).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    assert float(aux) > 0
    _, top = jax.lax.top_k(jax.nn.softmax(
        x.reshape(-1, 32) @ params["router"]["w"].T), 2)
    assert stats.tolist() == [24 * 2, len(np.unique(np.asarray(top)))]


def test_dropless_equals_capacity_dispatch_without_drops():
    cfg, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 32))
    y_dropless, a1 = moe_mod.apply_moe(params, x, cfg)
    y_roomy, a2 = moe_mod.apply_moe(params, x, cfg, capacity=32)
    np.testing.assert_allclose(np.asarray(y_dropless), np.asarray(y_roomy),
                               rtol=1e-5, atol=1e-6)
    assert float(a1) == float(a2)


def test_capacity_by_factor_drops_where_dropless_does_not():
    """The training dispatch sizes C from capacity_factor; a router that
    sends every token to one expert overflows it, dropless serves all."""
    cfg, params = _setup(e=4, k=1)
    w = jnp.zeros_like(params["router"]["w"]).at[0].set(10.0)
    params = dict(params, router={"w": w})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (1, 16, 32)))
    y_factor, _ = moe_mod.apply_moe(params, x, cfg,
                                    capacity=moe_mod.BY_FACTOR)
    y_dropless, _ = moe_mod.apply_moe(params, x, cfg)
    dropped = np.all(np.asarray(y_factor) == 0, axis=-1)[0]
    # C = int(1.25 * 16 * 1 / 4) = 5 of the 16 tokens kept
    assert dropped.sum() == 11
    assert not np.any(np.all(np.asarray(y_dropless) == 0, axis=-1))
