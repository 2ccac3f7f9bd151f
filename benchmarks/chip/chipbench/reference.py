"""The plain reference that decides ``correct``.

A decoder forward pass in straightforward ``jax.numpy``, float32 at
``Precision.HIGHEST``, with no kernels, cache or batching: embedding,
then each layer as the configuration's layer kind computes it (its
``forward``, in ``layers/<kind>.py``, built from this module's helpers),
then the final norm and the head.  It imports nothing of the program and
takes nothing the program made: it draws the same weights from the seed
with :mod:`chipbench.weights`, one layer at a time, so it runs in the
memory the program's state leaves free.

It is run over each sampled request's prompt and served tokens at once;
the number compared is the widest gap by which a served token's logit lies
below the reference's best at that position.  The control is the same
pass with every matmul's operands (the linear layers and the head) rounded
to float8 e4m3, the step below the configuration's bfloat16: at each
position it reads the gap of the token the float8 pass puts first.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 256


@dataclasses.dataclass
class Served:
    """One finished request: its prompt and the tokens the program served."""

    uid: int
    prompt: np.ndarray
    served: np.ndarray

    @property
    def length(self) -> int:
        return len(self.prompt) + len(self.served)


def round_fp8(a: jax.Array) -> jax.Array:
    """Round to float8 e4m3 (3 mantissa bits, per-tensor scale to its
    largest finite value 448), computed in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    y = a / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = 2.0 ** (e - 3.0)
    return jnp.round(y / step) * step * s


def _mm(x, w, low: bool):
    """x (T, K) @ w (O, K)^T."""
    if low:
        x, w = round_fp8(x), round_fp8(w)
    return jnp.einsum("tk,ok->to", x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (T, H, Dh) at positions 0..T-1; halves rotated together."""
    t, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal softmax attention, Q_CHUNK queries at a time.  q (T, Hq, Dh),
    k/v (T, Hkv, Dh); query head h reads key/value head h // (Hq/Hkv)."""
    t, hq, dh = q.shape
    group = hq // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(t)

    def chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_CHUNK, Q_CHUNK)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) * dh ** -0.5
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(chunk, jnp.arange(t // Q_CHUNK))
    return out.reshape(t, hq * dh)


@functools.partial(jax.jit, static_argnames=("forward", "dims", "low"))
def _run_layer(w, h, forward, dims, low):
    """A layer kind's ``forward``, compiled once per kind, sizes and
    precision (``dims`` as sorted items)."""
    return forward(w, h, dict(dims), low)


@functools.partial(jax.jit, static_argnames=("dims", "low"))
def _head(top, h, rows, dims, low):
    dims = dict(dims)
    x = _rms(h[rows], top["final_norm"]["scale"], dims["eps"])
    return _mm(x, top["unembed"]["table"][:dims["vocab"]], low)


def _bucket(length: int, buckets: Sequence[int]) -> int:
    for b in sorted(buckets):
        if length <= b:
            return b
    raise ValueError(f"a sequence of {length} tokens exceeds every "
                     f"reference bucket {sorted(buckets)}")


@dataclasses.dataclass
class Gaps:
    """Per request, the gap of each served token (and, for the control, of
    each float8 argmax) below the reference's best logit."""

    served: List[np.ndarray]
    control: Optional[List[np.ndarray]] = None

    @staticmethod
    def widest(per_request: List[np.ndarray]) -> float:
        return float(max(float(np.max(g)) for g in per_request))


def gaps(config: dict, layer, seed: int, samples: Sequence[Served],
         buckets: Sequence[int], control: bool = False) -> Gaps:
    """Run the reference (and with ``control`` the float8 pass beside it)
    over every sample, layer by layer; ``layer`` is the configuration's
    layer kind (:func:`chipbench.spec.layer_of`)."""
    dims = weights.layer_dims(config, layer)
    groups = weights.groups_of(config)
    logit_std = float(config["logit_std"])
    key = weights.seed_key(seed)
    frozen = tuple(sorted(dims.items()))
    tree = layer.tree(dims)
    top = jax.jit(lambda k: weights.top_weights(k, dims, groups,
                                                logit_std))(key)
    gen = jax.jit(lambda k, i: weights.layer_weights(k, i, tree, groups,
                                                     logit_std))
    seqs, rows = [], []
    for s in samples:
        # the reference reads prompt + every served token but the last;
        # position p predicts token p + 1
        tokens = np.concatenate([s.prompt, s.served[:-1]]).astype(np.int32)
        padded = np.zeros(_bucket(len(tokens), buckets), np.int32)
        padded[:len(tokens)] = tokens
        seqs.append(jnp.asarray(padded))
        # logits rows padded to a multiple of 128 (by repeating the last)
        # so the head compiles for a few shapes only
        want = np.arange(len(s.prompt) - 1, len(tokens))
        pad = -len(want) % 128
        rows.append(np.concatenate([want, np.full(pad, want[-1])]))
    hs = [top["embed"]["table"][t] for t in seqs]
    lows = list(hs) if control else []
    for i in range(dims["layers"]):
        w = gen(key, np.uint32(i))
        hs = [_run_layer(w, h, layer.forward, frozen, False) for h in hs]
        lows = [_run_layer(w, h, layer.forward, frozen, True) for h in lows]
        del w
    served, ctrl = [], []
    for i, s in enumerate(samples):
        n = len(s.served)
        ref = np.asarray(_head(top, hs[i], jnp.asarray(rows[i]), frozen,
                               False))[:n]
        best = ref.max(-1)
        served.append(best - ref[np.arange(len(s.served)), s.served])
        if control:
            low = np.asarray(_head(top, lows[i], jnp.asarray(rows[i]),
                                   frozen, True))[:n]
            ctrl.append(best - ref[np.arange(len(s.served)),
                                   low.argmax(-1)])
    return Gaps(served=served, control=ctrl if control else None)
