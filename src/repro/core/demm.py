"""Functional model of the DeMM engine (paper §II).

This is the *architectural* model of the engine: it computes sparse×dense
products in exactly the decoupled, row-wise product-first order the hardware
uses, with the two stages made explicit:

  stage 1 (memory)   — the N read ports: ``col_idx`` addresses the
                       pre-loaded M×C block of B, returning N rows of C
                       elements each;
  stage 2 (compute)  — N×C multipliers scale each read row by its non-zero
                       value; C N-input adder trees reduce to one output row.

The Pallas kernels in ``repro.kernels`` are the TPU-performant versions; this
module is the semantics reference and the engine used by the perf model and
by small-scale (CPU) execution.  All functions are jit-able and
differentiable.

Engine configuration mirrors the paper's DeMM(N, M, C, k):
  N — read ports / multiplier rows (non-zeros processed per cycle)
  M — group width = rows of B pre-loaded per block
  C — columns of B processed in parallel (output lanes)
  k — reconfiguration factor: kN:M patterns run in k passes per row
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sparsity import PackedSparse, SparsityConfig


@dataclasses.dataclass(frozen=True)
class DeMMConfig:
    """DeMM(N, M, C, k) — paper §II-B."""

    n: int = 8
    m: int = 128
    c: int = 64
    k: int = 8

    @property
    def multipliers(self) -> int:
        # The paper equalizes designs by MAC count: N*C multipliers.
        return self.n * self.c

    @property
    def sparsity(self) -> SparsityConfig:
        return SparsityConfig(n=self.n, m=self.m, k=1)

    def supports(self, pat: SparsityConfig) -> bool:
        """A DeMM(N,M,·,k) engine serves any pattern n':M with n' <= k*N."""
        return pat.m == self.m and pat.n_effective <= self.n * self.k


# ---------------------------------------------------------------------------
# The two decoupled stages
# ---------------------------------------------------------------------------

def read_ports(b_block: jax.Array, col_idx: jax.Array) -> jax.Array:
    """Stage 1 — the N-read-port memory block.

    b_block : (M, C)  pre-loaded rows of B (the engine's memory contents)
    col_idx : (..., N) int32 addresses
    returns : (..., N, C) — each read port outputs one full row of B.
    """
    return jnp.take(b_block, col_idx, axis=0)


def multiply_reduce(read_rows: jax.Array, values: jax.Array) -> jax.Array:
    """Stage 2 — N×C multipliers + C N-input adder trees.

    read_rows : (..., N, C)
    values    : (..., N)
    returns   : (..., C)
    """
    acc_dtype = jnp.promote_types(values.dtype, jnp.float32)
    prods = read_rows.astype(acc_dtype) * values[..., None].astype(acc_dtype)
    return jnp.sum(prods, axis=-2)


# ---------------------------------------------------------------------------
# Whole-matrix products in row-wise product-first order
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("out_dtype",))
def demm_spmm(packed: PackedSparse, b: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """C = A_sparse @ B with A packed as {values, indices}.

    A is (R, K) packed to (G, Ne, R); B is (K, Cdim).  The product is formed
    group by group (each group = one pre-loaded M-row memory block of B),
    each group contributing via the two decoupled stages.  Padded slots carry
    value 0 and contribute nothing.
    """
    r, kdim = packed.shape
    g = packed.values.shape[0]
    m = packed.cfg.m
    assert b.shape[0] == kdim, (b.shape, kdim)
    cdim = b.shape[1]

    b_blocks = b.reshape(g, m, cdim)

    def per_group(vals_g, idx_g, b_block):
        # vals_g/idx_g: (Ne, R); b_block: (M, C)
        rows = read_ports(b_block, idx_g.T)          # (R, Ne, C)
        return multiply_reduce(rows, vals_g.T)        # (R, C)

    # vmap over groups, then reduce — the engine iterates groups serially in
    # hardware; the sum order is fixed (group-major) either way.
    contribs = jax.vmap(per_group)(
        packed.values, packed.indices, b_blocks
    )  # (G, R, C)
    return jnp.sum(contribs, axis=0).astype(out_dtype)


@partial(jax.jit, static_argnames=("out_dtype",))
def demm_spmm_dense_a(a: jax.Array, b: jax.Array, cfg: SparsityConfig,
                      out_dtype=jnp.float32) -> jax.Array:
    """Convenience: prune+pack a dense A on the fly, then demm_spmm."""
    from repro.core.sparsity import pack, prune

    return demm_spmm(pack(prune(a, cfg), cfg), b, out_dtype=out_dtype)


def demm_spmm_k_passes(packed: PackedSparse, b: jax.Array, k: int,
                       out_dtype=jnp.float32) -> jax.Array:
    """The k-reconfigured schedule (paper §II-B): a kN:M packed matrix is
    consumed in k sequential N:M passes that time-share the read ports.

    Numerically identical to ``demm_spmm(packed, b)``; exists to validate the
    reconfiguration semantics and to drive the perf model's cycle counts.
    """
    from repro.core.sparsity import reconfigure_k

    ne = packed.cfg.n_effective
    if ne % k:
        raise ValueError(f"k={k} does not divide n_effective={ne}")
    split = reconfigure_k(packed, k)
    r, kdim = packed.shape
    g = packed.values.shape[0]
    m = packed.cfg.m
    cdim = b.shape[1]
    b_blocks = b.reshape(g, m, cdim)

    vals = split.values.reshape(g, k, ne // k, r)
    idx = split.indices.reshape(g, k, ne // k, r)

    acc = jnp.zeros((r, cdim), jnp.float32)
    for pass_i in range(k):  # k is a static engine parameter (unrolled)
        def per_group(v, i, bb):
            return multiply_reduce(read_ports(bb, i.T), v.T)

        contribs = jax.vmap(per_group)(
            vals[:, pass_i], idx[:, pass_i], b_blocks
        )
        acc = acc + jnp.sum(contribs, axis=0)
    return acc.astype(out_dtype)
