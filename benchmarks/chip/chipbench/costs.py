"""Operations and bytes, counted by the benchmark.

* A packed kernel call ``y = x @ W^T`` reads ``x`` and the packed stream
  and writes ``y``.  Its bytes are taken from the shapes and dtypes of the
  operands the compiled call was actually passed, read from its HLO text in
  the trace (``values`` and ``indices`` of the packed weight among them),
  so a change of storage dtype changes them and every share stays a share.
  Its operations count stored weights only: ``2 * rows * G * Ne * O``,
  which is ``2 * rows * O * K * N / M``, the work the algorithm needs.
* The least time of a call is the larger of operations over the chip's
  peak rate and bytes over its peak bandwidth.
* A served token costs 2 operations per kept packed weight it multiplies
  (all of them in a dense layer; a layer kind counts its own with
  ``active_weights``), 2 per weight of the head over the true vocabulary,
  and ``4 * layers * Hq * Dh * ctx`` for attention over its ``ctx``
  positions.

:func:`kernel_call` counts a call of any kernel family that brings no
``kernels/<family>.py`` of its own (:func:`chipbench.spec.load_kernel_call`).
"""

from __future__ import annotations

import dataclasses
import re
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Call:
    flops: float
    bytes: float

    def least_s(self, peak: dict) -> float:
        return max(self.flops / peak["bf16_flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}
_SHAPE = re.compile(r"\b(pred|[fsu]\d+|bf16)\[([\d,]*)\]")


def _shapes(text: str) -> List[tuple]:
    return [(dt, [int(d) for d in dims.split(",") if d])
            for dt, dims in _SHAPE.findall(text)]


def kernel_call(hlo: str) -> Call:
    """The operations and bytes of one packed kernel call, from its HLO
    text in the trace (``%demm_xwT.46 = f32[256,6912]{...}
    custom-call(bf16[256,2560]{...} %x, f32[32,5,6912]{...} %values,
    s32[32,5,6912]{...} %indices), ...``): bytes are every operand and
    the output at their dtypes, operations 2 per row of ``x`` and stored
    weight value."""
    head, _, rest = hlo.partition(" custom-call(")
    operands = rest.split("custom_call_target", 1)[0]
    outs, ins = _shapes(head.split(" = ", 1)[-1]), _shapes(operands)
    if len(ins) < 2:
        raise ValueError(f"not a packed kernel call: {hlo[:120]}")

    def nbytes(shape):
        return DTYPE_BYTES[shape[0]] * int(np.prod(shape[1], dtype=np.int64))

    rows = ins[0][1][0]
    values = int(np.prod(ins[1][1], dtype=np.int64))
    return Call(flops=2.0 * rows * values,
                bytes=float(sum(nbytes(x) for x in ins + outs)))


def _packed_leaves(params) -> list:
    """Every packed weight in the served tree: nodes that carry a
    ``values``/``indices`` stream and the dense shape they stand for."""
    import jax

    return [x for x in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: hasattr(x, "dense_shape"))
        if hasattr(x, "dense_shape")]


def kept_weights(params) -> int:
    """Kept weights of every packed linear (the per-token matmul work)."""
    total = 0
    for pw in _packed_leaves(params):
        o, k = pw.dense_shape
        lead = int(np.prod(pw.values.shape[:-3], dtype=np.int64))
        total += lead * o * k * pw.values.shape[-2] // pw.cfg.m
    return int(total)


def _attn(dims: dict) -> float:
    return 4.0 * dims["layers"] * dims["hq"] * dims["dh"]


def _head(dims: dict) -> float:
    return 2.0 * dims["vocab"] * dims["d"]


def token_flops(kept: int, dims: dict, ctx: np.ndarray) -> float:
    """Operations to decode tokens whose attention spans ``ctx`` positions
    each (one entry per token), the head included for each; ``kept`` is
    the kept weights one token multiplies."""
    ctx = np.asarray(ctx, np.float64)
    return float((2.0 * kept + _head(dims)) * len(ctx)
                 + _attn(dims) * ctx.sum())


def prompt_flops(kept: int, dims: dict, tokens: int, ctx_sum: int,
                 prefills: int) -> float:
    """Operations to ingest ``tokens`` prompt tokens whose attention spans
    sum to ``ctx_sum``, with the head once for each of ``prefills``
    finished prompts (only the last position's logits are needed)."""
    return (2.0 * kept * tokens + _attn(dims) * ctx_sum
            + _head(dims) * prefills)
