"""Chunked prefill: the engine's second compiled program.

The legacy serve loop prefills token-by-token through the decode step —
O(prompt_len) compiled-step dispatches per request.  :class:`ChunkedPrefill`
wraps the model's ``prefill_chunk`` in ONE jit with a fixed chunk width K:
every chunk of every request of every length reuses the same compiled
program (``slot``, ``n_valid``, and the block-table contents are traced
values), so ingest costs O(prompt_len / K) dispatches and the engine runs
exactly two compiled programs total — prefill-chunk and decode-step.

The model is injected by the caller (the engine / launch driver):
``repro.paged`` never imports ``repro.models``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class ChunkedPrefill:
    """Feeds a prompt into a paged decode state K tokens per dispatch.

    ``model`` needs a ``prefill_chunk(params, state, tokens, slot, n_valid,
    policy=...)`` method (DecoderLM / EncDecLM).  ``step`` runs one chunk —
    the unit the scheduler interleaves with decode ticks; ``ingest`` loops a
    whole prompt (benchmarks, tests).  Where the program also returns MoE
    routing counts (a model that ``routes_experts``), each chunk's are kept
    (on the device, not read) in ``last_counts``.
    """

    def __init__(self, model, *, chunk: int = 32, policy=None):
        if chunk < 1:
            raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
        if not hasattr(model, "prefill_chunk"):
            raise NotImplementedError(
                f"{type(model).__name__} has no prefill_chunk (chunked "
                "paged prefill needs an attention-cache family)")
        self.chunk = int(chunk)

        def prefill_chunk(p, s, t, slot, n):
            return model.prefill_chunk(p, s, t, slot, n, policy=policy)

        self._fn = jax.jit(prefill_chunk)
        self.last_counts = None       # the last chunk's routing counts
        self.dispatches = 0           # compiled-program invocations issued

    def num_chunks(self, prompt_len: int) -> int:
        return -(-int(prompt_len) // self.chunk)

    def step(self, params, state, prompt, fed: int, slot: int):
        """Feed ONE chunk of ``prompt`` starting at token ``fed`` into
        ``slot``.  Returns ``(logits, state, fed')`` where ``logits`` is the
        last *valid* position's (1, 1, V) logits — meaningful when
        ``fed' == len(prompt)`` (the first sampled token for free)."""
        part = np.asarray(prompt[fed:fed + self.chunk], np.int32)
        buf = np.zeros((self.chunk,), np.int32)
        buf[:len(part)] = part
        logits, state, *counts = self._fn(params, state, jnp.asarray(buf),
                                          jnp.int32(slot),
                                          jnp.int32(len(part)))
        self.last_counts = counts[0] if counts else None
        self.dispatches += 1
        return logits, state, fed + len(part)

    def ingest(self, params, state, prompt, slot: int):
        """Feed a whole prompt; returns ``(last_logits, state)``."""
        fed, logits = 0, None
        while fed < len(prompt):
            logits, state, fed = self.step(params, state, prompt, fed, slot)
        return logits, state
