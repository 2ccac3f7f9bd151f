"""Attention: GQA with full / sliding-window / local:global patterns, plus
cross-attention and decode (KV-cache) paths.

Training/prefill uses a chunked flash-style attention: an outer scan over Q
chunks and an inner scan over KV chunks with an online-softmax accumulator,
so activation memory is O(T · chunk) instead of O(T²) — required for the
32k-prefill dry-run cells to fit.

Decode computes one new token against a cache of S past tokens; for
long-context decode the KV cache may be *sequence-sharded* over the 'data'
mesh axis — the online-softmax combine is a (max, sum) reduction, which XLA
SPMD turns into the flash-decode all-reduce pattern automatically because we
express it with stable logsumexp accumulation over the (sharded) S axis.

Window semantics: ``window`` < 0 means unbounded (full causal); a positive
window w lets position t attend to [t-w+1, t].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _windowed(window) -> bool:
    """Static check: is a window mask needed?  ``window`` may be a python int
    (<=0 or None means unbounded) or a traced int32 (always masked; the
    FULL_WINDOW sentinel makes the mask a no-op for global layers)."""
    if window is None:
        return False
    if isinstance(window, (int, float)):
        return window > 0
    return True  # traced value: emit the mask


def _gqa_scores(q, k):
    """q: (B, Tq, Hq, Dh), k: (B, S, Hkv, Dh) -> (B, Hq, Tq, S)."""
    b, tq, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, tq, hkv, group, dh)
    s = jnp.einsum("bthgd,bshd->bhgts", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    return s.reshape(b, hkv * group, tq, k.shape[1])


def _gqa_out(p, v):
    """p: (B, Hq, Tq, S), v: (B, S, Hkv, Dh) -> (B, Tq, Hq, Dh)."""
    b, hq, tq, s = p.shape
    hkv = v.shape[2]
    group = hq // hkv
    pg = p.reshape(b, hkv, group, tq, s)
    o = jnp.einsum("bhgts,bshd->bthgd", pg, v.astype(jnp.float32))
    return o.reshape(b, tq, hq, v.shape[-1])


def flash_attention(
    q: jax.Array,              # (B, T, Hq, Dh)
    k: jax.Array,              # (B, S, Hkv, Dh)
    v: jax.Array,              # (B, S, Hkv, Dh)
    *,
    causal: bool = True,
    window: int = -1,
    static_window: Optional[int] = None,  # python int: banded inner scan
    q_offset: int = 0,         # absolute position of q[0] (prefill continuation)
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Chunked online-softmax attention.  Returns (B, T, Hq, Dh) in q.dtype.

    ``static_window``: when the window is known at trace time (SWA archs,
    gemma3 local layers), the inner KV scan only visits the
    ``ceil((W + qc)/kvc) + 1`` chunks that can intersect the band, instead
    of all S/kvc — an ~S/W cut in attention FLOPs, bytes, and (when K/V are
    head_dim-sharded) collectives (DESIGN.md §5)."""
    b, t, hq, dh = q.shape
    s = k.shape[1]
    scale = dh ** -0.5
    q_chunk = min(q_chunk, t)
    kv_chunk = min(kv_chunk, s)
    # pad T and S to chunk multiples
    tp = -(-t // q_chunk) * q_chunk
    sp = -(-s // kv_chunk) * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    n_q, n_kv = tp // q_chunk, sp // kv_chunk

    if static_window is not None and static_window > 0:
        window = static_window
        n_band = min(n_kv, (static_window + q_chunk - 2) // kv_chunk + 2)
    else:
        n_band = n_kv

    q_pos_base = jnp.arange(q_chunk)
    kv_pos_base = jnp.arange(kv_chunk)

    def q_step(_, qi):
        qc = jax.lax.dynamic_slice_in_dim(qp, qi * q_chunk, q_chunk, axis=1)
        q_pos = q_pos_base + qi * q_chunk + q_offset
        if n_band < n_kv:
            # first chunk that can contain position q0 - W + 1
            base = jnp.clip((qi * q_chunk + q_offset - window + 1)
                            // kv_chunk, 0, n_kv - n_band)
        else:
            base = 0

        def kv_step(carry, j):
            acc, m_run, l_run = carry
            ki = base + j
            kc = jax.lax.dynamic_slice_in_dim(kp, ki * kv_chunk, kv_chunk, 1)
            vc = jax.lax.dynamic_slice_in_dim(vp, ki * kv_chunk, kv_chunk, 1)
            kv_pos = kv_pos_base + ki * kv_chunk
            logits = _gqa_scores(qc, kc) * scale      # (B,Hq,qc,kc) fp32
            mask = kv_pos[None, :] < s                 # padding
            if causal:
                mask = mask & (kv_pos[None, :] <= q_pos[:, None])
            if _windowed(window):
                mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            m_new = jnp.maximum(m_run, logits.max(-1))          # (B,Hq,qc)
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            acc = acc * corr[..., None] + _gqa_out(p, vc).swapaxes(1, 2)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, hq, q_chunk, dh), jnp.float32)
        m0 = jnp.full((b, hq, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hq, q_chunk), jnp.float32)
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), jnp.arange(n_band))
        out = acc / jnp.maximum(l_run[..., None], 1e-30)        # (B,Hq,qc,Dh)
        return (), out.swapaxes(1, 2)                           # (B,qc,Hq,Dh)

    _, outs = jax.lax.scan(q_step, (), jnp.arange(n_q))         # (nq,B,qc,..)
    out = outs.swapaxes(0, 1).reshape(b, tp, hq, dh)[:, :t]
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,              # (B, 1, Hq, Dh)
    k_cache: jax.Array,        # (B, S, Hkv, Dh)
    v_cache: jax.Array,        # (B, S, Hkv, Dh)
    cache_len: jax.Array,      # (B,) valid lengths (new token already written)
    *,
    window: int = -1,
) -> jax.Array:
    """One-token attention against the cache.

    Expressed as a single stable-softmax reduction over S so that a
    sequence-sharded cache (long-context decode) lowers to the flash-decode
    partial-softmax + all-reduce combine under SPMD.
    """
    b, s, hkv, dh = k_cache.shape
    scale = dh ** -0.5
    logits = _gqa_scores(q, k_cache) * scale          # (B, Hq, 1, S)
    pos = jnp.arange(s)[None, :]                       # (1, S)
    valid = pos < cache_len[:, None]
    if _windowed(window):
        valid = valid & (pos > cache_len[:, None] - 1 - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = logits.max(-1, keepdims=True)
    p = jnp.exp(logits - m)
    out = _gqa_out(p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30), v_cache)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV cache indexing (DESIGN.md §13)
# ---------------------------------------------------------------------------
#
# The paper's decoupling idea applied to serving state: the KV *arena* is a
# flat pool of fixed-size pages shared by every sequence, and each sequence
# addresses it through a small block table — an indirection stream, exactly
# like the col_idx stream that lets the DeMM compute units read a packed
# weight buffer.  Page 0 is the reserved null/scratch page: block-table
# entries of inactive or not-yet-allocated positions point there, writes for
# masked lanes are redirected there, and it is never read un-masked.

NULL_PAGE = 0


def gather_pages(arena: jax.Array, block_table: jax.Array) -> jax.Array:
    """Materialize per-sequence caches from the shared arena.

    arena: (Np, P, Hkv, Dh); block_table: (B, NBLK) physical page ids in
    sequence order.  Returns (B, NBLK*P, Hkv, Dh) where gathered position
    ``s`` holds the KV of absolute token position ``s``.
    """
    b, nblk = block_table.shape
    p = arena.shape[1]
    return arena[block_table].reshape(b, nblk * p, *arena.shape[2:])


def scatter_token_pages(arena: jax.Array, block_table: jax.Array,
                        pos: jax.Array, new: jax.Array,
                        active: Optional[jax.Array] = None) -> jax.Array:
    """Write one token per sequence into its page (decode step).

    new: (B, 1, Hkv, Dh) written at absolute positions pos (B,).  Lanes with
    ``active`` False (empty slots, slots still prefilling) are redirected to
    the null page so a batched decode step cannot corrupt them.
    """
    p = arena.shape[1]
    page = jnp.take_along_axis(block_table, (pos // p)[:, None], axis=1)[:, 0]
    if active is not None:
        page = jnp.where(active, page, NULL_PAGE)
    return arena.at[page, pos % p].set(new[:, 0].astype(arena.dtype))


def scatter_chunk_pages(arena: jax.Array, row_table: jax.Array,
                        pos0: jax.Array, new: jax.Array,
                        n_valid: jax.Array) -> jax.Array:
    """Write a K-token prefill chunk of ONE sequence straight into its pages.

    new: (K, Hkv, Dh) for absolute positions pos0..pos0+K-1; rows >= n_valid
    (padding of the last partial chunk) go to the null page.  row_table:
    (NBLK,) — this sequence's block-table row.
    """
    k = new.shape[0]
    p = arena.shape[1]
    apos = pos0 + jnp.arange(k)
    page = jnp.where(jnp.arange(k) < n_valid, row_table[apos // p], NULL_PAGE)
    return arena.at[page, apos % p].set(new.astype(arena.dtype))


# ---------------------------------------------------------------------------
# Full attention block (init + train/prefill/decode apply)
# ---------------------------------------------------------------------------

from repro.models.layers import (apply_linear, apply_rmsnorm,  # noqa: E402
                                 apply_rope, init_linear, init_rmsnorm)


def init_attention(key, d: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, *, sparse=None, dtype=jnp.float32,
                   qk_norm: bool = False):
    """Q/K/V/O projections; ``qk_norm`` adds ``q_norm`` and ``k_norm``
    RMSNorm scales over the whole q (Hq·Dh) and k (Hkv·Dh) projections."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": init_linear(kq, d, num_heads * head_dim, sparse=sparse, dtype=dtype),
        "wk": init_linear(kk, d, num_kv_heads * head_dim, sparse=sparse, dtype=dtype),
        "wv": init_linear(kv, d, num_kv_heads * head_dim, sparse=sparse, dtype=dtype),
        "wo": init_linear(ko, num_heads * head_dim, d, sparse=sparse, dtype=dtype),
    }
    if qk_norm:
        p["q_norm"] = init_rmsnorm(num_heads * head_dim, dtype)
        p["k_norm"] = init_rmsnorm(num_kv_heads * head_dim, dtype)
    return p


def _constrain_heads(x, *, seq_sharded=False):
    """Pin (B, S, H, Dh) tensors to the TP layout (DESIGN.md §5):

    1. heads over 'model' when this tensor's head count divides TP;
    2. else, if the arch's *Q* head count divides TP, REPLICATE this (K/V)
       tensor — Q carries the sharding and the GQA einsums stay local (the
       per-chunk logits psum of head_dim sharding costs ~1000x more, see
       DESIGN.md §5);
    3. else REPLICATE q/k/v: attention runs replicated over 'model' (one
       gather per projection instead of a psum per flash chunk — §Perf
       iteration 4; these are small-head archs where attention is a minor
       FLOPs fraction, and ring attention is the noted future alternative);

    batch over the DP axes (or seq over 'data' for seq-sharded caches).
    Without an active mesh context this is the identity."""
    from repro.sharding import context as shctx

    ctx = shctx.get_context()
    if ctx is None:
        return x
    tp = ctx.tp
    h, dh = x.shape[2], x.shape[3]
    if h % tp == 0:
        mspec = ("model", None)
    else:
        mspec = (None, None)        # replicated (K/V of GQA, or all three)
    if seq_sharded:
        return shctx.constrain(x, None, "data", *mspec)
    batch = x.shape[0]
    bspec = "BATCH" if batch % ctx.dp_degree() == 0 else None
    return shctx.constrain(x, bspec, None, *mspec)


def _project_qkv(params, x, kv_x, num_heads, num_kv_heads, head_dim,
                 policy, norm_eps=1e-6):
    """q, k, v split into heads; where ``params`` hold ``q_norm`` and
    ``k_norm`` (QK-norm), the whole q and k projections are RMS-normalised
    first (eps ``norm_eps``), before the heads split and before RoPE."""
    b, t, _ = x.shape
    skv = kv_x.shape[1]
    q = apply_linear(params["wq"], x, policy=policy)
    k = apply_linear(params["wk"], kv_x, policy=policy)
    v = apply_linear(params["wv"], kv_x, policy=policy)
    if "q_norm" in params:
        q = apply_rmsnorm(params["q_norm"], q, norm_eps)
        k = apply_rmsnorm(params["k_norm"], k, norm_eps)
    return (_constrain_heads(q.reshape(b, t, num_heads, head_dim)),
            _constrain_heads(k.reshape(b, skv, num_kv_heads, head_dim)),
            _constrain_heads(v.reshape(b, skv, num_kv_heads, head_dim)))


def apply_attention(
    params, x, *, num_heads, num_kv_heads, head_dim, rope_theta,
    positions=None, causal=True, window=-1, static_window=None, kv_x=None,
    policy=None, q_chunk=512, kv_chunk=1024, norm_eps=1e-6,
):
    """Self- (kv_x=None) or cross- (kv_x=encoder out, causal=False) attention."""
    b, t, _ = x.shape
    cross = kv_x is not None
    kv_src = kv_x if cross else x
    q, k, v = _project_qkv(params, x, kv_src, num_heads, num_kv_heads,
                           head_dim, policy, norm_eps)
    if positions is None:
        positions = jnp.arange(t)
    if not cross:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    out = flash_attention(q, k, v, causal=causal and not cross, window=window,
                          static_window=static_window,
                          q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = out.reshape(b, t, num_heads * head_dim)
    return apply_linear(params["wo"], out, policy=policy)


def apply_attention_decode(
    params, x, cache, pos, *, num_heads, num_kv_heads, head_dim, rope_theta,
    window=-1, policy=None, norm_eps=1e-6,
):
    """One-token decode.  cache: {"k": (B,S,Hkv,Dh), "v": ...}; pos: (B,)
    index at which to write the new KV (== current length).  Returns
    (out (B,1,D), new_cache)."""
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(params, x, x, num_heads, num_kv_heads,
                                   head_dim, policy, norm_eps)
    q = apply_rope(q, pos[:, None], rope_theta)
    k_new = apply_rope(k_new, pos[:, None], rope_theta)
    onehot = jax.nn.one_hot(pos, cache["k"].shape[1],
                            dtype=cache["k"].dtype)    # (B, S)
    # replace (not accumulate) at pos: identical when the slot is zero, but
    # a speculative rollback (repro.spec) re-writes positions whose rejected
    # draft KV is still resident — the write must be idempotent.
    keep = (1.0 - onehot)[:, :, None, None]
    put = onehot[:, :, None, None]
    k_cache = cache["k"] * keep + put * k_new.astype(cache["k"].dtype)
    v_cache = cache["v"] * keep + put * v_new.astype(cache["v"].dtype)
    out = decode_attention(q, k_cache, v_cache, pos + 1, window=window)
    out = out.reshape(b, 1, num_heads * head_dim)
    out = apply_linear(params["wo"], out, policy=policy)
    return out, {"k": k_cache, "v": v_cache}


def apply_attention_decode_paged(
    params, x, arena_k, arena_v, block_table, active, pos, *, num_heads,
    num_kv_heads, head_dim, rope_theta, window=-1, policy=None,
    norm_eps=1e-6,
):
    """One-token decode against a paged KV arena (DESIGN.md §13).

    arena_k/arena_v: (Np, P, Hkv, Dh) shared pools; block_table (B, NBLK);
    active (B,) bool decode mask; pos (B,) absolute write position.  The new
    KV is scattered into the owning page (null-redirected for inactive
    lanes), then the per-sequence caches are gathered back and attention
    runs exactly as in the dense-cache path — same masks, same reduction —
    so paged and dense decode are token-identical.  Returns
    (out (B,1,D), (new_arena_k, new_arena_v)).
    """
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(params, x, x, num_heads, num_kv_heads,
                                   head_dim, policy, norm_eps)
    q = apply_rope(q, pos[:, None], rope_theta)
    k_new = apply_rope(k_new, pos[:, None], rope_theta)
    arena_k = scatter_token_pages(arena_k, block_table, pos, k_new, active)
    arena_v = scatter_token_pages(arena_v, block_table, pos, v_new, active)
    k_c = gather_pages(arena_k, block_table)
    v_c = gather_pages(arena_v, block_table)
    out = decode_attention(q, k_c, v_c, pos + 1, window=window)
    out = out.reshape(b, 1, num_heads * head_dim)
    out = apply_linear(params["wo"], out, policy=policy)
    return out, (arena_k, arena_v)


def apply_attention_prefill_paged(
    params, x, arena_k, arena_v, row_table, pos0, n_valid, *, num_heads,
    num_kv_heads, head_dim, rope_theta, policy=None, q_chunk=64,
    kv_chunk=128, norm_eps=1e-6,
):
    """One K-token prefill chunk of ONE sequence against the paged arena.

    x: (1, K, D) embedded chunk for absolute positions pos0..pos0+K-1 (the
    last chunk is padded; rows >= n_valid are masked to the null page).  The
    chunk's KV is scattered into the sequence's pages first, then flash
    attention runs the K queries against the gathered cache with
    ``q_offset=pos0`` — causal masking covers both the intra-chunk triangle
    and earlier chunks, and excludes unwritten (garbage) positions beyond
    pos0 + n_valid.  One call == one compiled dispatch for K tokens: the
    O(prompt_len) token-by-token ingest becomes O(prompt_len / K).
    """
    b, k_tok, _ = x.shape
    q, k_new, v_new = _project_qkv(params, x, x, num_heads, num_kv_heads,
                                   head_dim, policy, norm_eps)
    apos = pos0 + jnp.arange(k_tok)
    q = apply_rope(q, apos[None, :], rope_theta)
    k_new = apply_rope(k_new, apos[None, :], rope_theta)
    arena_k = scatter_chunk_pages(arena_k, row_table, pos0, k_new[0], n_valid)
    arena_v = scatter_chunk_pages(arena_v, row_table, pos0, v_new[0], n_valid)
    k_c = gather_pages(arena_k, row_table[None])
    v_c = gather_pages(arena_v, row_table[None])
    out = flash_attention(q, k_c, v_c, causal=True, q_offset=pos0,
                          q_chunk=min(q_chunk, k_tok), kv_chunk=kv_chunk)
    out = out.reshape(b, k_tok, num_heads * head_dim)
    return apply_linear(params["wo"], out, policy=policy), (arena_k, arena_v)


def init_kv_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
    }
