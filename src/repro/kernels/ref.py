"""Pure-jnp oracles for the DeMM kernels.

Every Pallas kernel in this package is validated with
``np.testing.assert_allclose`` against these references across shape/dtype
sweeps (see tests/test_demm_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sparsity import (SparsityConfig, expand_scales, unpack,
                                 unpack_block)


def spmm_ref(values: jax.Array, indices: jax.Array, b: jax.Array,
             cfg: SparsityConfig, a_shape) -> jax.Array:
    """C = A_sparse @ B via unpack-to-dense then dense matmul (fp32 accum)."""
    a = unpack(values, indices, cfg, tuple(a_shape))
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def xwT_ref(x: jax.Array, values: jax.Array, indices: jax.Array,
            cfg: SparsityConfig, w_shape) -> jax.Array:
    """y = x @ W_sparseᵀ via unpack-to-dense (fp32 accum)."""
    w = unpack(values, indices, cfg, tuple(w_shape))
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32).T,
                   preferred_element_type=jnp.float32)


def block_spmm_ref(active_groups, values, indices, b, cfg: SparsityConfig,
                   r: int) -> jax.Array:
    """Oracle for the two-level block-sparse format: scatter every active
    group back to dense (``core.sparsity.unpack_block`` — one home for the
    revisit-accumulate scatter semantics), then matmul."""
    k = b.shape[0]
    a = unpack_block(active_groups, values, indices, cfg, (r, k))
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# int8 quantized oracles (repro.quant): dequantize, then the float path.
# ---------------------------------------------------------------------------

def xwT_q8_ref(x: jax.Array, values: jax.Array, indices: jax.Array,
               scales: jax.Array, cfg: SparsityConfig, w_shape) -> jax.Array:
    """y = x @ W_q8ᵀ with per-output-row (O,) or per-group (G, O) scales:
    dequant + float ref."""
    vals = values.astype(jnp.float32) * expand_scales(scales, values)
    return xwT_ref(x, vals, indices, cfg, w_shape)


def block_spmm_q8_ref(active_groups, values, indices, scales, b,
                      cfg: SparsityConfig, r: int) -> jax.Array:
    """Two-level block oracle with per-(row-block, group, row) scales
    (RB, A_max, block_r): dequant + float ref."""
    vals = values.astype(jnp.float32) * expand_scales(scales, values)
    return block_spmm_ref(active_groups, vals, indices, b, cfg, r)
