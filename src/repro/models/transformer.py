"""Model assembly for all assigned architecture families.

Four families share one functional interface:

    model = build_model(cfg)
    params = model.init(key)
    loss, metrics = model.train_loss(params, batch, policy=ExecPolicy(...))
    logits, state = model.prefill(params, inputs)
    logits, state = model.decode_step(params, state, tokens)

* ``DecoderLM``   — dense / moe / vlm (vision stub prepends patch embeddings)
* ``EncDecLM``    — seamless-m4t (audio-stub encoder + cross-attn decoder)
* ``HybridLM``    — zamba2 (Mamba2 backbone + shared attention block)
* ``XLSTMLM``     — xlstm (periodic sLSTM/mLSTM superblocks)

Layers are stacked and scanned (``jax.lax.scan``) with ``jax.checkpoint``
remat so the 81-layer/48-layer configs compile to compact HLO.  Layer-type
variation (gemma3 local:global, zamba shared-attn sites) is handled with
per-layer window values (train) and cond-free superblock scans (decode), so
every HLO while-loop carries an exact known_trip_count for the roofline.

Decode caches:
* full-attention layers — (B, S, Hkv, Dh) append caches;
* windowed layers — (B, W, Hkv, Dh) ring buffers with per-slot positions;
* SSM layers — O(1) recurrent states.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.sparse_linear import ExecPolicy, resolve_policy
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_embedding,
    apply_linear,
    apply_mlp,
    apply_rmsnorm,
    apply_rope,
    apply_unembedding,
    dtype_of,
    Static,
    init_embedding,
    init_linear,
    init_mlp,
    init_rmsnorm,
)

FULL_WINDOW = jnp.int32(2**30)  # "unbounded" window sentinel (traced-safe)


# ---------------------------------------------------------------------------
# Ring-buffer (windowed) KV cache
# ---------------------------------------------------------------------------

def init_ring_cache(batch, window, hkv, dh, dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, window, hkv, dh), dtype),
        "v": jnp.zeros((batch, window, hkv, dh), dtype),
        "slot_pos": jnp.full((batch, window), -1, jnp.int32),
    }


def ring_decode_attention(params_block, x, cache, pos, *, cfg: ArchConfig,
                          window, policy):
    """One-token attention against a ring-buffer cache (window W slots)."""
    b = x.shape[0]
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q, k_new, v_new = attn._project_qkv(params_block, x, x, hq, hkv, dh,
                                        policy)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)
    w = cache["k"].shape[1]
    slot = pos % w                                         # (B,)
    onehot = jax.nn.one_hot(slot, w, dtype=cache["k"].dtype)
    keepm = (1.0 - onehot)[:, :, None, None]
    k_c = cache["k"] * keepm + onehot[:, :, None, None] * k_new.astype(cache["k"].dtype)
    v_c = cache["v"] * keepm + onehot[:, :, None, None] * v_new.astype(cache["v"].dtype)
    slot_pos = jnp.where(jax.nn.one_hot(slot, w, dtype=jnp.int32) > 0,
                         pos[:, None], cache["slot_pos"])
    # mask directly from stored absolute positions
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None]) & \
        (slot_pos > pos[:, None] - window)
    logits = attn._gqa_scores(q, k_c) * dh ** -0.5
    logits = jnp.where(valid[:, None, None, :], logits, attn.NEG_INF)
    m = logits.max(-1, keepdims=True)
    p = jnp.exp(logits - m)
    out = attn._gqa_out(p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30), v_c)
    out = out.reshape(b, 1, hq * dh).astype(x.dtype)
    out = apply_linear(params_block["wo"], out, policy=policy)
    return out, {"k": k_c, "v": v_c, "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# Standard transformer block (attention + MLP/MoE)
# ---------------------------------------------------------------------------

def init_tblock(key, cfg: ArchConfig, *, cross=False, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    sp = cfg.sparsity
    blk = {
        "ln1": init_rmsnorm(d, dtype),
        "attn": attn.init_attention(
            ks[0], d, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
            sparse=sp if "attn_qkv" in cfg.sparse_scope else None, dtype=dtype),
        "ln2": init_rmsnorm(d, dtype),
    }
    if cross:
        blk["ln_x"] = init_rmsnorm(d, dtype)
        blk["xattn"] = attn.init_attention(
            ks[1], d, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
            sparse=None, dtype=dtype)
    if cfg.moe is not None:
        blk["moe"] = moe_mod.init_moe(
            ks[2], d, cfg.moe,
            sparse=sp if "mlp" in cfg.sparse_scope else None, dtype=dtype)
    else:
        blk["mlp"] = init_mlp(ks[3], d, cfg.d_ff,
                              sparse=sp if "mlp" in cfg.sparse_scope else None,
                              dtype=dtype)
    return blk


def apply_tblock_seq(blk, x, cfg: ArchConfig, *, window, positions=None,
                     enc_out=None, causal=True, static_window=None,
                     policy):
    h = apply_rmsnorm(blk["ln1"], x)
    h = attn.apply_attention(
        blk["attn"], h,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        positions=positions, causal=causal, window=window,
        static_window=static_window, policy=policy)
    x = x + h
    if "xattn" in blk and enc_out is not None:
        h = apply_rmsnorm(blk["ln_x"], x)
        h = attn.apply_attention(
            blk["xattn"], h,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            causal=False, window=-1, kv_x=enc_out, policy=policy)
        x = x + h
    h = apply_rmsnorm(blk["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in blk:
        h, aux = moe_mod.apply_moe(blk["moe"], h, cfg.moe, policy=policy)
    else:
        h = apply_mlp(blk["mlp"], h, policy=policy)
    return x + h, aux


# ---------------------------------------------------------------------------
# Per-layer window schedule (gemma3 local:global, h2o SWA, full)
# ---------------------------------------------------------------------------

def layer_windows(cfg: ArchConfig) -> jnp.ndarray:
    """int32 (L,): attention window per layer (FULL_WINDOW = unbounded)."""
    l = cfg.num_layers
    if cfg.attention == "swa":
        return jnp.full((l,), cfg.window, jnp.int32)
    if cfg.attention == "local_global":
        idx = jnp.arange(l)
        is_global = (idx % (cfg.local_global_ratio + 1)) == cfg.local_global_ratio
        return jnp.where(is_global, FULL_WINDOW, cfg.local_window)
    return jnp.full((l,), FULL_WINDOW, jnp.int32)


def _remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat == "dots" else None)
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# DecoderLM: dense / moe / vlm
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DecoderLM:
    cfg: ArchConfig

    def init(self, key, layer_fn=None):
        """Random params.  Layers are built one at a time (``lax.map``),
        so only one layer's dense temporaries are live; ``layer_fn``
        transforms each layer inside that loop (e.g. ``launch.pack_tree``
        packing it), so a full-width model can be built in its served form
        without its dense tree ever existing whole."""
        cfg = self.cfg
        dtype = dtype_of(cfg.param_dtype)
        layer_fn = layer_fn or (lambda p: p)
        k_e, k_u, k_l, k_p = jax.random.split(key, 4)
        layer_keys = jax.random.split(k_l, cfg.num_layers)
        layers = jax.lax.map(
            lambda k: layer_fn(init_tblock(k, cfg, dtype=dtype)), layer_keys)
        params = {
            "embed": init_embedding(k_e, cfg.padded_vocab, cfg.d_model, dtype),
            "unembed": init_embedding(k_u, cfg.padded_vocab, cfg.d_model, dtype),
            "final_norm": init_rmsnorm(cfg.d_model, dtype),
            "layers": layers,
        }
        if cfg.frontend == "vision":
            params["patch_proj"] = init_linear(k_p, cfg.d_model, cfg.d_model,
                                               sparse=None, dtype=dtype)
        return params

    # ---- full-sequence forward (train / prefill logits) ----
    def _backbone_seq(self, params, x, *, positions, policy):
        cfg = self.cfg

        if cfg.attention == "local_global":
            # cond-free superblocks with STATIC local windows: local layers
            # run banded flash (DESIGN.md §5).
            period, n_p, n_tail = self._lg_layout()
            stacked = jax.tree.map(
                lambda a: a[:n_p * period].reshape(n_p, period,
                                                   *a.shape[1:]),
                params["layers"])
            tail = jax.tree.map(lambda a: a[n_p * period:], params["layers"])

            def body(carry, blks):
                x, aux = carry
                for i in range(period - 1):
                    blk = jax.tree.map(lambda a: a[i], blks)
                    x, a = apply_tblock_seq(
                        blk, x, cfg, window=cfg.local_window,
                        static_window=cfg.local_window,
                        positions=positions, policy=policy)
                    aux = aux + a
                blk = jax.tree.map(lambda a: a[period - 1], blks)
                x, a = apply_tblock_seq(blk, x, cfg, window=-1,
                                        positions=positions, policy=policy)
                return (x, aux + a), None

            (x, aux), _ = jax.lax.scan(
                _remat(body, cfg), (x, jnp.zeros((), jnp.float32)), stacked)
            for i in range(n_tail):
                blk = jax.tree.map(lambda a: a[i], tail)
                x, a = apply_tblock_seq(
                    blk, x, cfg, window=cfg.local_window,
                    static_window=cfg.local_window, positions=positions,
                    policy=policy)
                aux = aux + a
            return apply_rmsnorm(params["final_norm"], x), aux

        static_window = cfg.window if cfg.attention == "swa" else None
        windows = layer_windows(cfg)

        def body(carry, layer):
            x, aux = carry
            blk, window = layer
            x, a = apply_tblock_seq(blk, x, cfg, window=window,
                                    static_window=static_window,
                                    positions=positions, policy=policy)
            return (x, aux + a), None

        body = _remat(body, cfg)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (params["layers"], windows))
        return apply_rmsnorm(params["final_norm"], x), aux

    def _embed_inputs(self, params, batch, dtype):
        cfg = self.cfg
        x = apply_embedding(params["embed"], batch["tokens"]).astype(dtype)
        if cfg.frontend == "vision":
            pe = apply_linear(params["patch_proj"],
                              batch["patch_embeds"].astype(dtype))
            x = jnp.concatenate([pe, x], axis=1)
        return x

    def train_loss(self, params, batch, *, policy=None,
                         mode=None, backend=None):
        policy = resolve_policy(policy, mode, backend)
        cfg = self.cfg
        dtype = dtype_of(cfg.compute_dtype)
        x = self._embed_inputs(params, batch, dtype)
        t = x.shape[1]
        x, aux = self._backbone_seq(params, x, positions=jnp.arange(t),
                                    policy=policy)
        if cfg.frontend == "vision":  # only text positions carry loss
            x = x[:, cfg.num_patches:]
        logits = apply_unembedding(params["unembed"], x, self.cfg.vocab_size)
        loss = softmax_xent(logits, batch["targets"])
        return loss + aux, {"xent": loss, "aux": aux}

    # ---- serving ----
    def prefill(self, params, batch, *, max_len=None, policy=None,
                      mode=None, backend=None):
        policy = resolve_policy(policy, mode, backend)
        cfg = self.cfg
        dtype = dtype_of(cfg.compute_dtype)
        x = self._embed_inputs(params, batch, dtype)
        b, t = x.shape[0], x.shape[1]
        x, _ = self._backbone_seq(params, x, positions=jnp.arange(t),
                                  policy=policy)
        logits = apply_unembedding(params["unembed"], x[:, -1:], self.cfg.vocab_size)
        state = self.init_decode_state(b, max_len or t + 1, dtype=dtype)
        # NOTE: serving fills the cache during prefill; for the dry-run cells
        # the decode state is initialized directly (decode-only lowering).
        return logits, state

    def _lg_layout(self):
        """local_global layout: (period, n_periods, n_tail)."""
        cfg = self.cfg
        period = cfg.local_global_ratio + 1
        n_p = cfg.num_layers // period
        return period, n_p, cfg.num_layers - n_p * period

    def init_decode_state(self, batch, max_len, dtype=jnp.bfloat16,
                          paged=None):
        """Decode-state pytree.  ``paged`` (a ``repro.paged.PagedLayout``)
        swaps the dense per-slot KV caches for one shared paged arena +
        per-sequence block tables (DESIGN.md §13); only full-attention
        caches are paged — windowed ring buffers are already O(window)."""
        cfg = self.cfg
        hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
        l = cfg.num_layers

        if paged is not None:
            if cfg.attention != "full":
                raise NotImplementedError(
                    f"paged KV cache needs attention='full' (got "
                    f"{cfg.attention!r}): windowed ring buffers are already "
                    f"O(window) per slot; paging the local_global global "
                    f"layers is future work (DESIGN.md §13)")
            return {
                "caches": {
                    "kind": Static("paged"),
                    "layout": Static(paged),
                    "k": jnp.zeros((l, paged.num_pages, paged.page_size,
                                    hkv, dh), dtype),
                    "v": jnp.zeros((l, paged.num_pages, paged.page_size,
                                    hkv, dh), dtype),
                    "block_table": jnp.zeros((batch, paged.max_blocks),
                                             jnp.int32),
                    "active": jnp.zeros((batch,), jnp.bool_),
                },
                "pos": jnp.zeros((batch,), jnp.int32),
            }

        def ring(*lead):
            w = int(cfg.local_window if cfg.attention == "local_global"
                    else cfg.window)
            return {
                "k": jnp.zeros((*lead, batch, w, hkv, dh), dtype),
                "v": jnp.zeros((*lead, batch, w, hkv, dh), dtype),
                "slot_pos": jnp.full((*lead, batch, w), -1, jnp.int32),
            }

        if cfg.attention == "full":
            caches = {
                "kind": Static("full"),
                "k": jnp.zeros((l, batch, max_len, hkv, dh), dtype),
                "v": jnp.zeros((l, batch, max_len, hkv, dh), dtype),
            }
        elif cfg.attention == "swa":
            caches = {"kind": Static("swa"), "ring": ring(l)}
        else:  # local_global: periods of (ratio local + 1 global) + tail
            period, n_p, n_tail = self._lg_layout()
            caches = {
                "kind": Static("local_global"),
                "local": ring(n_p, period - 1),
                "tail": ring(max(n_tail, 1)),
                "global_k": jnp.zeros((max(n_p, 1), batch, max_len, hkv, dh),
                                      dtype),
                "global_v": jnp.zeros((max(n_p, 1), batch, max_len, hkv, dh),
                                      dtype),
            }
        return {"caches": caches, "pos": jnp.zeros((batch,), jnp.int32)}

    def _decode_ffn(self, blk, x, policy):
        cfg = self.cfg
        h = apply_rmsnorm(blk["ln2"], x)
        if "moe" in blk:
            h, _ = moe_mod.apply_moe(blk["moe"], h, cfg.moe, policy=policy)
        else:
            h = apply_mlp(blk["mlp"], h, policy=policy)
        return x + h

    def _decode_full_layer(self, blk, x, cache, pos, window, policy):
        cfg = self.cfg
        h = apply_rmsnorm(blk["ln1"], x)
        h, nc = attn.apply_attention_decode(
            blk["attn"], h, cache, pos,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            window=window, policy=policy)
        return self._decode_ffn(blk, x + h, policy), nc

    def _decode_ring_layer(self, blk, x, cache, pos, window, policy):
        h = apply_rmsnorm(blk["ln1"], x)
        h, nc = ring_decode_attention(blk["attn"], h, cache, pos,
                                      cfg=self.cfg, window=window, policy=policy)
        return self._decode_ffn(blk, x + h, policy), nc

    def _decode_paged_layer(self, blk, x, arena_k, arena_v, bt, active, pos,
                            policy):
        cfg = self.cfg
        h = apply_rmsnorm(blk["ln1"], x)
        h, arenas = attn.apply_attention_decode_paged(
            blk["attn"], h, arena_k, arena_v, bt, active, pos,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            window=FULL_WINDOW, policy=policy)
        return self._decode_ffn(blk, x + h, policy), arenas

    def decode_step(self, params, state, tokens, *, policy=None,
                          mode=None, backend=None):
        policy = resolve_policy(policy, mode, backend)
        cfg = self.cfg
        dtype = dtype_of(cfg.compute_dtype)
        x = apply_embedding(params["embed"], tokens).astype(dtype)
        pos = state["pos"]
        caches = state["caches"]
        kind = caches["kind"].value

        if kind == "full":
            def body(x, layer):
                blk, kc, vc = layer
                x, nc = self._decode_full_layer(
                    blk, x, {"k": kc, "v": vc}, pos, FULL_WINDOW,
                    policy)
                return x, (nc["k"], nc["v"])

            x, (ks, vs) = jax.lax.scan(
                body, x, (params["layers"], caches["k"], caches["v"]))
            new_caches = {"kind": Static("full"), "k": ks, "v": vs}

        elif kind == "paged":
            bt, active = caches["block_table"], caches["active"]

            def body(x, layer):
                blk, ak, av = layer
                x, arenas = self._decode_paged_layer(
                    blk, x, ak, av, bt, active, pos, policy)
                return x, arenas

            x, (ks, vs) = jax.lax.scan(
                body, x, (params["layers"], caches["k"], caches["v"]))
            new_caches = {**caches, "k": ks, "v": vs}
            x = apply_rmsnorm(params["final_norm"], x)
            logits = apply_unembedding(params["unembed"], x,
                                       self.cfg.vocab_size)
            # only lanes decoding this tick advance; prefilling/empty slots
            # keep their position (their pages were null-redirected too)
            return logits, {"caches": new_caches,
                            "pos": pos + active.astype(jnp.int32)}

        elif kind == "swa":
            def body(x, layer):
                blk, ring = layer
                x, nc = self._decode_ring_layer(blk, x, ring, pos,
                                                cfg.window, policy)
                return x, nc

            x, rings = jax.lax.scan(body, x, (params["layers"],
                                              caches["ring"]))
            new_caches = {"kind": Static("swa"), "ring": rings}

        else:  # local_global periods + local tail (cond-free)
            period, n_p, n_tail = self._lg_layout()
            stacked = jax.tree.map(
                lambda a: a[:n_p * period].reshape(n_p, period,
                                                   *a.shape[1:]),
                params["layers"])
            tail = jax.tree.map(lambda a: a[n_p * period:], params["layers"])

            def body(x, per):
                blks, local, gk, gv = per
                new_local = []
                for i in range(period - 1):
                    blk = jax.tree.map(lambda a: a[i], blks)
                    ring = jax.tree.map(lambda a: a[i], local)
                    x, nc = self._decode_ring_layer(
                        blk, x, ring, pos, cfg.local_window, policy)
                    new_local.append(nc)
                # the global layer (full cache, unbounded window)
                blk = jax.tree.map(lambda a: a[period - 1], blks)
                x, nc = self._decode_full_layer(
                    blk, x, {"k": gk, "v": gv}, pos, FULL_WINDOW,
                    policy)
                stacked_local = jax.tree.map(lambda *a: jnp.stack(a),
                                             *new_local)
                return x, (stacked_local, nc["k"], nc["v"])

            x, (locals_, gks, gvs) = jax.lax.scan(
                body, x,
                (stacked, caches["local"], caches["global_k"],
                 caches["global_v"]))

            new_tail = []
            for i in range(n_tail):
                blk = jax.tree.map(lambda a: a[i], tail)
                ring = jax.tree.map(lambda a: a[i], caches["tail"])
                x, nc = self._decode_ring_layer(
                    blk, x, ring, pos, cfg.local_window, policy)
                new_tail.append(nc)
            tail_caches = (jax.tree.map(lambda *a: jnp.stack(a), *new_tail)
                           if new_tail else caches["tail"])
            new_caches = {"kind": Static("local_global"), "local": locals_,
                          "tail": tail_caches, "global_k": gks,
                          "global_v": gvs}

        x = apply_rmsnorm(params["final_norm"], x)
        logits = apply_unembedding(params["unembed"], x, self.cfg.vocab_size)
        return logits, {"caches": new_caches, "pos": pos + 1}

    def decode_step_pipelined(self, params, state, tokens, *, policy=None,
                              pp: int = 2, pp_axis: str = "pipe"):
        """Pipeline-parallel :meth:`decode_step` (full-attention caches).

        The layer stack is split into ``pp`` contiguous stage groups sharded
        over ``pp_axis``; the decode batch is split into ``pp`` slot
        microbatches streamed through the GPipe schedule
        (:func:`repro.sharding.pipeline.pipeline_apply_stateful`).  Each
        stage owns the KV caches of its layer group and updates only the
        slot rows of its live microbatch, so the result — logits *and* new
        caches — is bitwise what the sequential scan produces.

        Embedding and the final norm/unembed run replicated outside the
        pipeline.  Requires ``num_layers % pp == 0`` and
        ``batch % pp == 0``; without a matching mesh in the active
        sharding context it falls back to :meth:`decode_step` (identical
        math, no pipelining) so the engine keeps working on one device.
        """
        from repro.sharding import context as shctx
        from repro.sharding.pipeline import pipeline_apply_stateful

        policy = resolve_policy(policy, None, None)
        cfg = self.cfg
        caches = state["caches"]
        if caches["kind"].value != "full":
            raise NotImplementedError(
                "decode_step_pipelined supports the dense full-attention "
                "cache (windowed/paged layouts pipeline their stages with "
                "different per-stage state; DESIGN.md §14)")
        ctx = shctx.get_context()
        mesh = getattr(ctx, "mesh", None)
        if (mesh is None or pp_axis not in mesh.shape
                or mesh.shape[pp_axis] != pp):
            return self.decode_step(params, state, tokens, policy=policy)
        b = tokens.shape[0]
        l = cfg.num_layers
        if l % pp or b % pp:
            raise ValueError(
                f"decode_step_pipelined: num_layers ({l}) and batch ({b}) "
                f"must both divide pp ({pp})")
        l_loc, mb = l // pp, b // pp
        dtype = dtype_of(cfg.compute_dtype)
        pos = state["pos"]
        x = apply_embedding(params["embed"], tokens).astype(dtype)

        def split(a):      # leading dim L -> (pp, L/pp)
            return a.reshape(pp, l_loc, *a.shape[1:])

        stage_params = jax.tree.map(split, params["layers"])
        stage_state = {"k": split(caches["k"]), "v": split(caches["v"])}

        def stage_fn(layers, st, x_mb, pos_mb, mb_idx):
            start = mb_idx * mb

            def body(x, layer):
                blk, kc, vc = layer      # kc: (B, S, Hkv, Dh)
                k_mb = jax.lax.dynamic_slice_in_dim(kc, start, mb, axis=0)
                v_mb = jax.lax.dynamic_slice_in_dim(vc, start, mb, axis=0)
                x, nc = self._decode_full_layer(
                    blk, x, {"k": k_mb, "v": v_mb}, pos_mb, FULL_WINDOW,
                    policy)
                kc = jax.lax.dynamic_update_slice_in_dim(
                    kc, nc["k"], start, axis=0)
                vc = jax.lax.dynamic_update_slice_in_dim(
                    vc, nc["v"], start, axis=0)
                return x, (kc, vc)

            x_mb, (ks, vs) = jax.lax.scan(
                body, x_mb, (layers, st["k"], st["v"]))
            return x_mb, {"k": ks, "v": vs}

        x_mbs = x.reshape(pp, mb, *x.shape[1:])
        pos_mbs = pos.reshape(pp, mb)
        # shard_map makes every mesh axis manual, so the context's
        # activation constraints are illegal inside the stages — suspend it
        # for the pipeline trace (stage math is unaffected)
        with shctx.suspend():
            y, new_stage = pipeline_apply_stateful(
                stage_fn, stage_params, stage_state, x_mbs, mesh,
                axis=pp_axis, aux=pos_mbs)
        x = y.reshape(b, *y.shape[2:])
        new_caches = {
            "kind": Static("full"),
            "k": new_stage["k"].reshape(l, *caches["k"].shape[1:]),
            "v": new_stage["v"].reshape(l, *caches["v"].shape[1:]),
        }
        x = apply_rmsnorm(params["final_norm"], x)
        logits = apply_unembedding(params["unembed"], x, self.cfg.vocab_size)
        return logits, {"caches": new_caches, "pos": pos + 1}

    def prefill_chunk(self, params, state, tokens, slot, n_valid, *,
                      policy=None, mode=None, backend=None):
        """Ingest one K-token chunk of a single sequence into its pages.

        ``tokens`` is a fixed-size ``(K,)`` int32 chunk (padded past
        ``n_valid``); ``slot`` and ``n_valid`` are traced scalars, so one
        compiled program serves every chunk of every request —
        O(prompt_len / K) dispatches instead of O(prompt_len).  Returns the
        logits at the last *valid* position (shape ``(1, 1, V)``) so the
        final chunk yields the first sampled token for free.
        """
        policy = resolve_policy(policy, mode, backend)
        cfg = self.cfg
        caches = state["caches"]
        if caches["kind"].value != "paged":
            raise NotImplementedError(
                "prefill_chunk requires a paged decode state "
                "(init_decode_state(..., paged=PagedLayout))")
        dtype = dtype_of(cfg.compute_dtype)
        slot = jnp.asarray(slot, jnp.int32)
        n_valid = jnp.asarray(n_valid, jnp.int32)
        pos0 = state["pos"][slot]
        row = caches["block_table"][slot]
        x = apply_embedding(params["embed"], tokens[None]).astype(dtype)

        def body(x, layer):
            blk, ak, av = layer
            h = apply_rmsnorm(blk["ln1"], x)
            h, arenas = attn.apply_attention_prefill_paged(
                blk["attn"], h, ak, av, row, pos0, n_valid,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                policy=policy)
            return self._decode_ffn(blk, x + h, policy), arenas

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], caches["k"], caches["v"]))
        x = apply_rmsnorm(params["final_norm"], x)
        last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = apply_unembedding(params["unembed"], last, cfg.vocab_size)
        return logits, {"caches": {**caches, "k": ks, "v": vs},
                        "pos": state["pos"].at[slot].add(n_valid)}


# ---------------------------------------------------------------------------
# Cross-entropy (vocab-sharded logits friendly)
# ---------------------------------------------------------------------------

def softmax_xent(logits, targets):
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
