"""Draft → verify decode: the speculative window over one packed tree.

One speculation window per engine tick (both engines share this module):

1. **draft** — γ sequential decode steps run with the *draft-tier* params
   view (``spec.tiers.derive_draft_tier``; same buffers, narrower address
   stream), each proposing the next token with the replay-safe coupled
   sampler.  Draft steps write draft-quality KV into the shared cache —
   deliberately: there is no second decode state, no draft re-prefill
   after preemption, zero extra KV memory.
2. **verify** — ONE batched full-tier dispatch re-feeds the whole window
   (:func:`make_multistep`: a ``lax.scan`` over the γ+1 token columns
   inside a single jitted program), rewriting every window position's KV
   with full-tier values and producing exact logits for each.
3. **accept** — per lane, the committed tokens are the full-tier coupled
   samples; a drafted token survives iff it equals that sample, and the
   first mismatch truncates the window (the mismatching position still
   commits its full-tier token, so every window commits ≥ 1 token and an
   all-accepted window commits γ+1 — the bonus token).  The engine then
   rolls each lane's ``pos`` back to its last *valid* input, so stale
   draft KV beyond it is invisible (attention masks by ``pos``) and is
   overwritten by the next window.

Because the committed token at every position is exactly what the
non-speculative engine would emit (greedy argmax at temperature 0, the
Gumbel-max coupled sample otherwise), speculation changes dispatch count
and latency — never the token stream.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knobs.

    * ``draft`` — the sparser tier pattern, e.g. ``"8:128"`` to draft at
      8:128 from a tree packed at 16:128 (k-reconfigured).  Every packed
      node sharing the pattern's M and denser than its N drafts at the
      tier; the rest fall back to the full tier.
    * ``gamma`` — tokens drafted per window; a window verifies γ+1
      positions in one full-tier dispatch.
    """

    draft: str = "8:128"
    gamma: int = 4

    def __post_init__(self):
        from repro.spec.tiers import parse_tier

        parse_tier(self.draft)          # validate eagerly
        if self.gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {self.gamma}")


def guard_cache_kinds(state, allowed=("full", "paged")):
    """Refuse decode states speculation cannot roll back.

    The accept step undoes rejected draft writes by resetting ``pos``:
    that only works when history is *position-addressable* — full and paged
    attention caches mask reads by ``pos`` and rewrite any position.  Ring
    buffers (swa / local_global) lose the entries a rejected write
    overwrote, and O(1) recurrent states (SSM / mLSTM) fold every input in
    irreversibly; both would silently diverge from the non-speculative
    stream.  Walks the state pytree's ``{"kind": Static(...)}`` cache tags.
    """
    kinds = set()

    def walk(x):
        if isinstance(x, dict):
            k = x.get("kind")
            if hasattr(k, "value"):
                kinds.add(k.value)
            for v in x.values():
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)

    walk(state)
    bad = sorted(kinds - set(allowed))
    if bad or not kinds:
        raise NotImplementedError(
            f"speculative decoding requires position-addressable KV caches "
            f"(kinds {sorted(allowed)}); this decode state has "
            f"{bad or 'no tagged caches'} — ring buffers and recurrent "
            f"states cannot roll back rejected draft writes")
    return kinds


def make_multistep(model, policy):
    """The batched verify program: ``(params, state, tokens (B, W)) ->
    (logits (B, W, V), state)`` — W decode steps fused into one jitted
    dispatch via ``lax.scan`` over the token columns.

    Built on ``model.decode_step``, so every cache kind the engines serve
    (dense, ring, paged — with its active-mask/null-page redirection)
    verifies through its ordinary decode path; ``W`` is only a trace-time
    shape, so one program handles every window width the engine clamps to.
    """

    def multistep(params, state, tokens):
        def body(st, tok_col):
            # a mixture-of-experts model's routing counts are not kept here
            logits, st, *_ = model.decode_step(params, st, tok_col[:, None],
                                               policy=policy)
            return st, logits[:, 0]

        state_out, logits = jax.lax.scan(body, state,
                                         jnp.swapaxes(tokens, 0, 1))
        return jnp.swapaxes(logits, 0, 1), state_out

    return jax.jit(multistep)


class SpecMetrics:
    """The obs families of the speculative decoder (DESIGN.md §15)."""

    def __init__(self, registry):
        m = registry
        self.drafted = m.counter(
            "spec_draft_tokens_total",
            help="draft-tier proposals fed to verification")
        self.accepted = m.counter(
            "spec_accepted_tokens_total",
            help="drafted tokens that matched the full-tier sample")
        self.rejected = m.counter(
            "spec_rejected_tokens_total",
            help="drafted tokens replaced by the full-tier sample")
        self.acceptance = m.histogram(
            "spec_acceptance_ratio",
            help="per-window accepted/drafted ratio",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0))
        self.tokens_per_dispatch = m.gauge(
            "spec_tokens_per_dispatch",
            help="committed tokens per full-tier (verify) dispatch, "
                 "running mean")
        # goodput accounting (repro.obs.slo): every drafted-but-uncommitted
        # proposal is draft-tier work thrown away.  Distinct from
        # spec_rejected_tokens_total, which counts only *examined* drafts —
        # drafts past a window truncation point are wasted too.
        self.wasted = m.counter(
            "serve_wasted_tokens_total",
            help="tokens of work the engine re-did or discarded, by cause",
            cause="spec_reject")
        self._committed_total = 0
        self._verify_dispatches = 0

    def observe_wasted(self, n: int):
        """Account ``n`` draft proposals discarded without commit."""
        self.wasted.inc(n)

    def observe_window(self, drafted: int, accepted: int, committed: int):
        """Account one speculation window (one verify dispatch)."""
        self.drafted.inc(drafted)
        self.accepted.inc(accepted)
        self.rejected.inc(drafted - accepted)
        if drafted:
            self.acceptance.observe(accepted / drafted)
        self._committed_total += committed
        self._verify_dispatches += 1
        self.tokens_per_dispatch.set(
            self._committed_total / self._verify_dispatches)
