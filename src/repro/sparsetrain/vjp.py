"""custom_vjp coverage for the packed ops beyond ``xwT``.

``kernels/ops.py`` has always carried a custom_vjp for the row-packed
``xwT`` op (dL/dvalues = gather of dyᵀx at the packed coordinates); the
``block`` and quantized ops were serving-only and raised inside ``jax.grad``.
This module closes that gap so ``ExecPolicy(mode="packed")`` is legal under
differentiation for every layout:

* ``xwT_block_grad``    — the two-level block layout.  Forward dispatches
  through the ``repro.tune`` registry (reference or Pallas ``block_spmm``);
  backward scatters through the :func:`~repro.core.sparsity.unpack_block`
  reference: dx = dy @ W_dense, and dvalues is the gather of dyᵀx at each
  slot's (row-block, active-group, local-index) coordinate.  Duplicate
  active-group ids accumulate in the forward scatter, so the per-slot
  gather *is* the exact vjp of that linear map.  ``indices`` and
  ``active_groups`` (the address streams) are non-differentiable.

* ``xwT_q8_grad`` / ``xwT_block_q8_grad`` — the int8 quantized twins
  (dequant-and-scatter backward).  The int8 ``values`` are not a
  differentiable parameterization (cotangent None, like the indices), but
  the op is no longer a wall: dx flows through the *dequantized* dense
  weight — so activations behind a quantized layer get exact gradients —
  and ``scales`` (a float leaf) receives its true gradient
  dL/ds = Σ_slots gather(dyᵀx) · int_value, which is what a
  learned-scale QAT variant would train.  Padded slots (value 0)
  contribute nothing to either.

All backward passes run through the ``kernels/ref.py`` / ``core.sparsity``
scatter references (pure jnp, fp32 accumulation); forwards reuse whatever
backend the policy picked, Pallas included.  The padding rule matches the
``xwT`` vjp: slots with value 0 receive zero gradient, so the packed
pattern can never densify during fine-tuning.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sparsity import (SparsityConfig, expand_scales, unpack,
                                 unpack_block)


def _variant_call(op: str, backend: str, params: tuple, *args):
    from repro import tune

    return tune.get_variant(op, backend).call(*args, **dict(params))


def _dw(dy: jax.Array, x: jax.Array) -> jax.Array:
    """dW = dyᵀ @ x in fp32 — the dense-weight cotangent every packed
    backward gathers from."""
    return jnp.dot(dy.T.astype(jnp.float32), x.astype(jnp.float32))


def gather_xwT_slots(dw: jax.Array, indices: jax.Array, m: int) -> jax.Array:
    """Gather the (O, K) dense cotangent at every xwT slot: result
    (G, Ne, O) aligned with the lane-major packed values."""
    o, k = dw.shape
    dw_g = dw.T.reshape(k // m, m, o)                          # (G, M, O)
    return jnp.take_along_axis(dw_g, indices, axis=1)          # (G, Ne, O)


def _gather_block_slots(dw: jax.Array, indices: jax.Array,
                        active_groups: jax.Array, m: int) -> jax.Array:
    """Gather the (O, K) dense cotangent at every block-layout slot:
    result (RB, A_max, Ne, block_r) aligned with the packed values."""
    rb, a_max, _ne, block_r = indices.shape
    o = rb * block_r
    g = dw.shape[1] // m
    assert dw.shape[0] == o, (dw.shape, indices.shape)
    dw_g = jnp.transpose(dw.reshape(rb, block_r, g, m),
                         (0, 2, 3, 1))                         # (RB,G,M,br)
    sel = jnp.take_along_axis(
        dw_g, active_groups[:, :, None, None].astype(jnp.int32), axis=1
    )                                                          # (RB,A,M,br)
    return jnp.take_along_axis(sel, indices, axis=2)           # (RB,A,Ne,br)


# ---------------------------------------------------------------------------
# float block layout
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def xwT_block_grad(x, values, indices, active_groups, cfg: SparsityConfig,
                   w_shape, backend: str = "reference", params: tuple = ()):
    """y = x @ W_blockᵀ, differentiable in x and values."""
    return _variant_call("xwT_block", backend, params, x, values, indices,
                         active_groups, cfg, tuple(w_shape))


def _block_fwd(x, values, indices, active_groups, cfg, w_shape, backend,
               params):
    y = xwT_block_grad(x, values, indices, active_groups, cfg, w_shape,
                       backend, params)
    return y, (x, values, indices, active_groups)


def _block_bwd(cfg, w_shape, backend, params, res, dy):
    x, values, indices, active_groups = res
    o, k = w_shape
    w = unpack_block(active_groups, values.astype(jnp.float32), indices,
                     cfg, (o, k))
    dx = jnp.dot(dy.astype(jnp.float32), w)
    dvalues = _gather_block_slots(_dw(dy, x), indices, active_groups,
                                  cfg.m).astype(values.dtype)
    # Padded / inactive slots (value 0, aliased at group 0 index 0) must not
    # accumulate gradient, or they would densify the pattern.
    dvalues = jnp.where(values != 0, dvalues, jnp.zeros((), values.dtype))
    return dx.astype(x.dtype), dvalues, None, None


xwT_block_grad.defvjp(_block_fwd, _block_bwd)


# ---------------------------------------------------------------------------
# int8 quantized xwT (w8a16)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def xwT_q8_grad(x, values, indices, scales, cfg: SparsityConfig, w_shape,
                backend: str = "reference", params: tuple = ()):
    """y = x @ W_q8ᵀ, differentiable in x and scales (values are int8)."""
    return _variant_call("xwT_q8", backend, params, x, values, indices,
                         scales, cfg, tuple(w_shape))


def _q8_fwd(x, values, indices, scales, cfg, w_shape, backend, params):
    y = xwT_q8_grad(x, values, indices, scales, cfg, w_shape, backend,
                    params)
    return y, (x, values, indices, scales)


def _q8_bwd(cfg, w_shape, backend, params, res, dy):
    x, values, indices, scales = res
    o, k = w_shape
    vals_f = values.astype(jnp.float32)
    deq = vals_f * expand_scales(scales, values)
    w = unpack(deq, indices, cfg, (o, k))
    dx = jnp.dot(dy.astype(jnp.float32), w)
    dslot = gather_xwT_slots(_dw(dy, x), indices, cfg.m)       # (G, Ne, O)
    # dL/ds = Σ over the slots sharing the scale of dW[slot] · int_value
    # (padded slots have int_value 0 and drop out automatically).
    axes = (-2,) if scales.ndim == values.ndim - 1 else (-3, -2)
    dscales = jnp.sum(dslot * vals_f, axis=axes).astype(scales.dtype)
    return dx.astype(x.dtype), None, None, dscales


xwT_q8_grad.defvjp(_q8_fwd, _q8_bwd)


# ---------------------------------------------------------------------------
# int8 quantized block layout
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def xwT_block_q8_grad(x, values, indices, active_groups, scales,
                      cfg: SparsityConfig, w_shape,
                      backend: str = "reference", params: tuple = ()):
    """y = x @ W_block_q8ᵀ, differentiable in x and scales."""
    return _variant_call("xwT_block_q8", backend, params, x, values, indices,
                         active_groups, scales, cfg, tuple(w_shape))


def _block_q8_fwd(x, values, indices, active_groups, scales, cfg, w_shape,
                  backend, params):
    y = xwT_block_q8_grad(x, values, indices, active_groups, scales, cfg,
                          w_shape, backend, params)
    return y, (x, values, indices, active_groups, scales)


def _block_q8_bwd(cfg, w_shape, backend, params, res, dy):
    x, values, indices, active_groups, scales = res
    o, k = w_shape
    vals_f = values.astype(jnp.float32)
    deq = vals_f * expand_scales(scales, values)
    w = unpack_block(active_groups, deq, indices, cfg, (o, k))
    dx = jnp.dot(dy.astype(jnp.float32), w)
    dslot = _gather_block_slots(_dw(dy, x), indices, active_groups, cfg.m)
    dscales = jnp.sum(dslot * vals_f, axis=-2).astype(scales.dtype)
    return dx.astype(x.dtype), None, None, None, dscales


xwT_block_q8_grad.defvjp(_block_q8_fwd, _block_q8_bwd)
