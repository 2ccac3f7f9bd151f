"""Symmetric int8 quantization of packed relaxed-N:M sparse weights.

Granularity follows the packed layout (DESIGN.md §10):

* ``xwT``    — default one scale per output row: ``scales (*stack, O)``.
  The row is the reduction unit of the serving matmul ``y = x @ Wᵀ``, so a
  per-row scale folds into the kernel as a single multiply on the (rows, M)
  scatter matrix.  ``granularity="per_group"`` refines this to one scale
  per (M-group, row): ``scales (*stack, G, O)`` — each group's Ne values
  share one exponent, which matters exactly when a row mixes large and
  small groups (the kernel cost is unchanged: group ``g`` of a grid step
  scales by row ``g`` of the scales operand instead of the single row).
* ``block``  — one scale per (row-block, active-group slot, row):
  ``scales (*stack, RB, A_max, block_r)``.  Per-group scales are finer than
  per-row (each group's Ne values share one exponent) and line up with the
  block kernel's (Ne, block_r) value tiles.

Quantization is symmetric round-to-nearest: ``q = clip(round(v / s), ±127)``
with ``s = amax / 127`` (data-free) or an observer-provided scale.  Padded
slots (value 0) quantize to 0 and keep contributing nothing; a genuine
weight that rounds to 0 merely drops below the quantization floor.

The optional activation-calibration hook searches a small clip grid per
scale unit, weighting each packed slot's quantization error by the RMS of
the calibration activations at the slot's *global* column (the diagonal /
OBS approximation of the output MSE).  It never needs labels or a backward
pass — a handful of activation rows from the serving distribution is
enough.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.sparsity import (
    LAYOUT_BLOCK,
    QDTYPE_INT8,
    QDTYPES,
    PackedWeight,
    expand_scales,
)

QMAX = 127.0
# Clip ratios searched by the activation observer (1.0 = plain amax).
CLIP_GRID = (1.0, 0.95, 0.9, 0.85, 0.8)

_EPS = 1e-12

GRANULARITIES = ("per_row", "per_group")


def _check_granularity(pw: PackedWeight, granularity: str):
    if granularity not in GRANULARITIES:
        raise ValueError(f"unknown granularity {granularity!r}; expected "
                         f"one of {GRANULARITIES}")
    if granularity == "per_group" and pw.layout == LAYOUT_BLOCK:
        raise ValueError(
            "granularity only applies to the xwT layout; block scales are "
            "already per (row-block, group, row)")


def _reduce_axes(pw: PackedWeight, granularity: str = "per_row"):
    """Packed axes reduced away by one scale unit: the Ne axis, plus the
    group axis in front of it for per-row xwT scales."""
    if pw.layout == LAYOUT_BLOCK or granularity == "per_group":
        return (-2,)
    return (-3, -2)


def amax_scales(pw: PackedWeight,
                granularity: str = "per_row") -> jax.Array:
    """Data-free calibration: ``amax / 127`` per scale unit (float32).

    Zero rows (fully padded slots) get a scale of ``1/127`` so the divide
    stays finite; their values are all 0 and quantize to 0 regardless.
    """
    _check_granularity(pw, granularity)
    amax = jnp.max(jnp.abs(pw.values.astype(jnp.float32)),
                   axis=_reduce_axes(pw, granularity))
    return jnp.where(amax > _EPS, amax, 1.0) / QMAX


def _quantize_values(pw: PackedWeight, scales: jax.Array) -> jax.Array:
    q = jnp.round(pw.values.astype(jnp.float32)
                  / expand_scales(scales, pw.values))
    return jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


def quantize_packed(pw: PackedWeight, qdtype: str = QDTYPE_INT8, *,
                    observer: Optional[Callable] = None,
                    granularity: str = "per_row") -> PackedWeight:
    """Quantize a float packed weight to ``qdtype`` (int8 today).

    ``observer`` maps the float ``PackedWeight`` to per-unit scales (see
    :func:`activation_calibration`); by default the cheap data-free
    :func:`amax_scales` pass is used.  ``granularity`` picks the scale unit
    for the xwT layout — ``per_row`` (``scales (*stack, O)``, the default)
    or ``per_group`` (``(*stack, G, O)``); an observer's output shape wins
    over ``granularity``.  Returns a new ``PackedWeight`` with int8
    ``values``, a float32 ``scales`` child, and the ``qdtype`` aux tag;
    ``indices``/``active_groups`` and all static aux are shared unchanged.
    """
    if qdtype not in QDTYPES:
        raise ValueError(f"unknown qdtype {qdtype!r}; expected {QDTYPES}")
    if pw.qdtype is not None:
        raise ValueError(f"weight is already quantized ({pw.qdtype!r}); "
                         "dequantize_packed first to re-calibrate")
    _check_granularity(pw, granularity)
    scales = (observer(pw) if observer is not None
              else amax_scales(pw, granularity)).astype(jnp.float32)
    return pw.replace(values=_quantize_values(pw, scales), scales=scales,
                      qdtype=qdtype)


def dequantize_packed(pw: PackedWeight) -> PackedWeight:
    """Back to the float packed form (float32 values, no scales child)."""
    if pw.qdtype is None:
        return pw
    return pw.replace(values=pw.dequantized_values(), scales=None,
                      qdtype=None)


def quantize_tree(params, qdtype: str = QDTYPE_INT8, *,
                  observer: Optional[Callable] = None,
                  granularity: str = "per_row"):
    """Quantize every :class:`PackedWeight` node of a params pytree
    (as produced by ``launch.pack_tree``); everything else passes through.
    Already-quantized nodes are left untouched.  ``granularity`` applies to
    xwT-layout nodes (block nodes are inherently per-group)."""
    if isinstance(params, PackedWeight):
        if params.qdtype is not None:
            return params
        gran = ("per_row" if params.layout == LAYOUT_BLOCK else granularity)
        return quantize_packed(params, qdtype, observer=observer,
                               granularity=gran)
    if isinstance(params, dict):
        return {k: quantize_tree(v, qdtype, observer=observer,
                                 granularity=granularity)
                for k, v in params.items()}
    return params


# ---------------------------------------------------------------------------
# Activation calibration
# ---------------------------------------------------------------------------

def _slot_columns(pw: PackedWeight) -> jax.Array:
    """Global contraction-dim column of every packed slot (same shape as
    ``indices``): ``group_id * M + local_index``."""
    m = pw.cfg.m
    if pw.layout == LAYOUT_BLOCK:
        # active_groups (*stack, RB, A_max) carries the group ids.
        return (pw.active_groups[..., None, None] * m
                + pw.indices).astype(jnp.int32)
    g = pw.groups
    gids = jnp.arange(g, dtype=jnp.int32)[:, None, None]  # (G, 1, 1)
    return (gids * m + pw.indices).astype(jnp.int32)


def activation_calibration(x: jax.Array,
                           grid: Sequence[float] = CLIP_GRID,
                           granularity: str = "per_row") -> Callable:
    """Observer factory: pick per-unit clip ratios from sample activations.

    ``x`` is a small ``(B, K)`` batch drawn from the serving distribution.
    For every scale unit the observer evaluates each clip ratio ``c`` in
    ``grid`` on the weighted quantization error

        err(c) = Σ_slots ( (deq_c(v) - v) · act_rms[column(slot)] )²

    — the diagonal approximation of the output MSE ``‖x (W - Ŵ)ᵀ‖²`` — and
    keeps the best ``c * amax_scale``.  Clipping below amax trades a few
    saturated outliers for a finer grid on the bulk, which wins exactly when
    the activation mass says the bulk matters more.
    """
    act_sq = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=0)   # (K,)

    def observer(pw: PackedWeight) -> jax.Array:
        base = amax_scales(pw, granularity)
        axes = _reduce_axes(pw, granularity)
        v = pw.values.astype(jnp.float32)
        w = act_sq[_slot_columns(pw)]                  # per-slot weight
        errs = []
        for c in grid:
            s = expand_scales(base * c, pw.values)
            deq = jnp.clip(jnp.round(v / s), -QMAX, QMAX) * s
            errs.append(jnp.sum(jnp.square(deq - v) * w, axis=axes))
        errs = jnp.stack(errs)                         # (|grid|, *units)
        best = jnp.argmin(errs, axis=0)
        ratios = jnp.asarray(grid, jnp.float32)[best]
        return base * ratios

    return observer
