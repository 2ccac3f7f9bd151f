"""PagedServeEngine: scheduled serving over a paged KV arena.

The rewritten engine tick is admit → prefill → decode:

1. **admit** — the scheduler hands over queued requests in policy order; a
   free slot is claimed and pages for the prompt are allocated (admission
   may preempt a strictly lower-priority running request under the
   ``priority`` policy).
2. **prefill** — up to ``prefill_chunks_per_tick`` chunk dispatches are
   spent round-robin over prefilling slots (``repro.paged.prefill``); the
   final chunk's logits yield the request's first generated token for free.
3. **decode** — one batched decode step over every decode-ready slot; lanes
   still prefilling (or empty) are masked out via the ``active`` mask and
   null-page write redirection, so the two compiled programs interleave
   freely within a tick.

Page exhaustion preempts: the victim's pages are freed, the request is
requeued with its prompt + generated-so-far output, and a later admission
re-prefills it — the preempt/resume cycle is token-identical to an
uninterrupted run at any temperature, because sampling randomness is keyed
on (request, position), not on a sequential stream (DESIGN.md §13/§15;
``repro.spec.sampling``).

Speculative decoding (``spec=SpecConfig(...)``): the decode phase drafts γ
tokens per tick with the draft-tier view of the same packed buffers, grows
each lane's pages to cover the window, verifies in ONE batched full-tier
multistep dispatch, then trims pages beyond the committed tokens in the
same tick — drafted-but-rejected tokens never hold arena capacity across
ticks.

Control state (positions, block tables, the decode mask) is mirrored on the
host and pushed to the device pytree before each program call — value-only
updates, never a retrace.  Layering: this module never imports
``repro.models``; the model (and its two compiled entry points) is injected
by the caller.

Every tick runs as a numbered ``serve.tick`` step span, and each of its
phases under an ``obs.phase`` span (``serve.admit``, ``serve.control``,
``serve.prefill.dispatch``/``.wait``, ``serve.decode.dispatch``/``.wait``,
``serve.sample``, ``serve.pages``): an attached profiler records them on the
device trace's clock, and their host seconds add up in
``serve_phase_seconds_total{phase=<name without serve., dots as _>}``.  The
two compiled programs are named ``decode_step`` and ``prefill_chunk``
(``jit_decode_step``/``jit_prefill_chunk`` in a trace), and
``serve_compiles_total{program}`` counts each new executable they build.

A mixture-of-experts model (``model.routes_experts``) has both programs
return their routing counts beside their outputs; the engine reads them
with the device reads it already makes (a decode step's logits, a
prompt's last chunk) into ``serve_moe_rows_total{program}`` (assignments
routed) and ``serve_moe_experts_touched_total{program}`` (experts that
received a row, summed over layers).  A dense model counts nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.paged.kv_cache import PagedKVCache, PagedLayout
from repro.paged.prefill import ChunkedPrefill
from repro.paged.scheduler import SchedConfig, Scheduler, Stage
from repro.serve.protocol import EngineBase
from repro.serve.serve_loop import Request

# tick phases, each the ``serve.<name>`` span and the counter label
PHASES = ("tick", "admit", "control", "prefill.dispatch", "prefill.wait",
          "decode.dispatch", "decode.wait", "sample", "pages")


@dataclasses.dataclass
class PagedServeConfig:
    num_slots: int = 4
    max_len: int = 256
    page_size: int = 16
    num_pages: Optional[int] = None   # None: fully provisioned (no sharing)
    prefill_chunk: int = 32
    greedy: bool = True         # legacy alias; temperature == 0 means greedy
    temperature: float = 0.0
    top_k: int = 0              # 0 = full vocab
    seed: int = 0               # sampling seed (keys the per-position RNG)
    sched: SchedConfig = dataclasses.field(default_factory=SchedConfig)


class PagedServeEngine(EngineBase):
    """Slot-batched serving with a shared paged KV arena.

    Same surface as the legacy :class:`~repro.serve.serve_loop.ServeEngine`
    (``submit`` / ``step`` / ``run_until_drained`` / ``completed``) plus the
    paged internals: ``kv`` (arena bookkeeping), ``sched`` (admission /
    preemption policy), and ``prefill`` (the chunked-ingest program).
    """

    def __init__(self, model, params, cfg: PagedServeConfig, *, policy=None,
                 autotune=False, metrics=None, spec=None, recorder=None):
        from repro.core.sparse_linear import resolve_policy
        from repro.spec.sampling import ReplaySafeSampler

        policy = resolve_policy(policy, None, None)
        self.model = model
        if spec is not None:
            # magnitude-descending per-group order BEFORE sharding so the
            # draft tier's prefix-read is exact magnitude pruning
            from repro.spec.tiers import tier_sort_tree
            params = tier_sort_tree(params)
        # policy.plan (ShardingPlan): renumber row-parallel packed weights
        # and place everything — the shared KV arena included — on the
        # plan's mesh before either program compiles
        params = self._setup_plan(policy, params)
        self.params = params
        self.cfg = cfg
        self.policy = policy
        if autotune and policy.mode == "packed":
            from repro import tune
            tune.autotune_packed_tree(params, cfg.num_slots)
        self.layout = PagedLayout.for_serve(
            cfg.max_len, page_size=cfg.page_size, num_pages=cfg.num_pages,
            num_slots=cfg.num_slots)
        self.kv = PagedKVCache(self.layout, cfg.num_slots)
        self.state = self._place_state(model.init_decode_state(
            cfg.num_slots, cfg.max_len, dtype=jnp.float32,
            paged=self.layout))

        self._moe = bool(getattr(model, "routes_experts", False))

        def decode_step(p, s, t):
            return model.decode_step(p, s, t, policy=policy)

        decode_jit = jax.jit(decode_step)
        self._decode = self._wrap_step(decode_jit)
        self.prefill = ChunkedPrefill(model, chunk=cfg.prefill_chunk,
                                      policy=policy)
        self._prefill_step = self._wrap_step(self.prefill.step)
        self._jits = {"decode": decode_jit, "prefill": self.prefill._fn}
        self.sched = Scheduler(cfg.sched)
        # host mirrors of the control leaves (pushed before each program)
        self._pos = np.zeros((cfg.num_slots,), np.int32)
        self._decode_mask = np.zeros((cfg.num_slots,), bool)
        self._next_tok = np.zeros((cfg.num_slots, 1), np.int32)
        self.active: List[Optional[Request]] = [None] * cfg.num_slots
        self._work: List[Optional[np.ndarray]] = [None] * cfg.num_slots
        self._fed = [0] * cfg.num_slots       # work tokens ingested
        self.completed: List[Request] = []
        self.tick_count = 0
        self.sampler = ReplaySafeSampler(temperature=cfg.temperature,
                                         top_k=cfg.top_k, seed=cfg.seed)
        # -- observability (legacy names + paged families) ------------------
        self.metrics = metrics if metrics is not None else obs.metrics()
        m = self.metrics
        self.trace = m.trace
        self._spans = {}
        self._m_submitted = m.counter(
            "serve_requests_submitted_total", help="requests accepted")
        self._m_completed = m.counter(
            "serve_requests_completed_total", help="requests fully decoded")
        self._m_tokens = m.counter(
            "serve_tokens_total", help="generated (decode) tokens")
        self._m_prefill_tok = m.counter(
            "serve_prefill_tokens_total", help="prompt tokens prefilled")
        self._m_preempt = m.counter(
            "serve_preempt_total",
            help="requests preempted by page eviction")
        self._m_disp_prefill = m.counter(
            "serve_step_dispatch_total",
            help="compiled-program invocations per program",
            program="prefill")
        self._m_disp_decode = m.counter(
            "serve_step_dispatch_total",
            help="compiled-program invocations per program",
            program="decode")
        self._m_queue_wait = m.histogram(
            "serve_queue_wait_seconds", help="submit -> first slot claim")
        self._m_ttft = m.histogram(
            "serve_time_to_first_token_seconds",
            help="submit -> first generated token")
        self._m_tok_lat = m.histogram(
            "serve_decode_token_seconds",
            help="decode-step latency per generated token")
        self._m_phase = {p: m.counter(
            "serve_phase_seconds_total",
            help="host seconds in each engine tick phase",
            phase=p.replace(".", "_")) for p in PHASES}
        self._m_ticks = m.counter("serve_ticks_total", help="engine ticks")
        self._m_compiles = {p: m.counter(
            "serve_compiles_total",
            help="executables a dispatch of the program traced and compiled",
            program=p) for p in self._jits}
        # routing counts still on the device: (program, int32 [rows, experts])
        self._moe_pending = []
        if self._moe:
            self._m_moe_rows = {p: m.counter(
                "serve_moe_rows_total",
                help="expert assignments routed (tokens x top-k, summed "
                     "over layers)", program=p) for p in self._jits}
            self._m_moe_touched = {p: m.counter(
                "serve_moe_experts_touched_total",
                help="experts that received at least one row, summed over "
                     "layers", program=p) for p in self._jits}
        self._m_slots = m.gauge(
            "serve_slots_active", help="occupied decode slots")
        self._m_queue_depth = m.gauge(
            "serve_queue_depth", help="requests waiting for a slot/pages")
        self._m_pages_free = m.gauge(
            "kv_pages_free", help="unallocated KV arena pages")
        self._m_occupancy = m.gauge(
            "kv_arena_occupancy",
            help="fraction of usable arena pages allocated")
        self._m_frag = m.gauge(
            "kv_page_fragmentation",
            help="allocated-but-empty token-slot fraction (last-page slack)")
        self._m_tps = m.gauge(
            "serve_tokens_per_second",
            help="decode throughput of the last run_until_drained window")
        # goodput accounting: tokens whose KV a preemption evicted — the
        # resume re-ingests them, so they are work done twice
        self._m_wasted_preempt = m.counter(
            "serve_wasted_tokens_total",
            help="tokens of work the engine re-did or discarded, by cause",
            cause="preempt")
        # sketch-backed latency percentiles (mergeable across DP replicas)
        self._sk_ttft = m.sketch(
            "serve_ttft_seconds_sketch",
            help="submit -> first token (quantile sketch)")
        self._sk_tok = m.sketch(
            "serve_decode_token_seconds_sketch",
            help="per-generated-token decode latency (quantile sketch)")
        self._sk_e2e = m.sketch(
            "serve_e2e_seconds_sketch",
            help="submit -> completion (quantile sketch)")
        self._m_pages_free.set(self.kv.pages_free)
        self._setup_recorder(recorder)
        # -- speculative decoding (DESIGN.md §15) ---------------------------
        self._spec = spec
        if spec is not None:
            from repro.spec.decode import (SpecMetrics, guard_cache_kinds,
                                           make_multistep)
            from repro.spec.tiers import derive_draft_tier
            guard_cache_kinds(self.state)
            # derive AFTER _setup_plan so the draft view aliases the
            # placed/renumbered buffers (draft.values IS full.values)
            self._draft_params, self.tier_report = derive_draft_tier(
                self.params, spec.draft)
            self._verify = self._wrap_step(make_multistep(model, policy))
            self._spec_metrics = SpecMetrics(self.metrics)
            self._m_disp_draft = m.counter(
                "serve_step_dispatch_total",
                help="compiled-program invocations per program",
                program="draft")
            self._m_disp_verify = m.counter(
                "serve_step_dispatch_total",
                help="compiled-program invocations per program",
                program="verify")

    # -- submission ---------------------------------------------------------

    def submit(self, req: Request):
        if len(req.prompt) < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if len(req.prompt) > self.cfg.max_len - 1:
            raise ValueError(
                f"request {req.uid}: prompt of {len(req.prompt)} tokens "
                f"exceeds max_len-1 = {self.cfg.max_len - 1}")
        peak = min(len(req.prompt) + req.max_new_tokens, self.cfg.max_len)
        need = self.layout.pages_for(peak)
        if need > min(self.layout.usable_pages, self.layout.max_blocks):
            raise RuntimeError(
                f"request {req.uid} needs {need} pages at peak ({peak} "
                f"tokens) but the arena has only "
                f"{self.layout.usable_pages} usable pages "
                f"(max_blocks={self.layout.max_blocks}) — it could never "
                f"complete even with every other sequence evicted; raise "
                f"--max-pages or --page-size")
        req.output = []
        req.submit_ts = time.monotonic()
        ctx = self._request_context(req)   # mints req.trace_id
        self.sched.submit(req)
        self._m_submitted.inc()
        self._m_queue_depth.set(len(self.sched))
        with obs.use_context(ctx):
            self._spans[req.uid] = self.trace.span("request", uid=req.uid)
            self.trace.event("request_submit", uid=req.uid,
                             prompt_len=len(req.prompt),
                             priority=req.priority)

    # -- device-control sync ------------------------------------------------

    def _sync_control(self):
        """Push the host-side control mirrors (positions, block tables,
        decode mask) into the device pytree.  Value-only: shapes and the
        Static kind/layout leaves never change, so no retrace.  The mirrors
        are COPIED before upload — jax's CPU client may zero-copy-alias an
        aligned numpy buffer, and these arrays keep mutating in place."""
        with self._phase("control"):
            c = self.state["caches"]
            self.state = {
                **self.state,
                "pos": jnp.asarray(np.array(self._pos)),
                "caches": {**c,
                           "block_table": jnp.asarray(np.array(self.kv.table)),
                           "active": jnp.asarray(np.array(self._decode_mask))},
            }

    def _phase(self, name: str, **meta):
        """``obs.phase`` span ``serve.<name>`` into its phase counter."""
        return obs.phase("serve." + name, self._m_phase[name], **meta)

    @contextlib.contextmanager
    def _dispatch(self, program: str, **meta):
        """The ``serve.<program>.dispatch`` span around one call of a
        compiled program; a call that grew the program's jit cache traced
        and compiled a new executable."""
        fn = self._jits[program]
        before = fn._cache_size()
        with self._phase(program + ".dispatch", **meta):
            yield
        grown = fn._cache_size() - before
        if grown > 0:
            self._m_compiles[program].inc(grown)

    def _decode_call(self, *args):
        """One decode-step dispatch: (logits, state), its routing counts
        queued for the next device read."""
        out = self._decode(*args)
        if not self._moe:
            return out
        self._moe_pending.append(("decode", out[2]))
        return out[0], out[1]

    def _read_moe(self):
        """Add the queued routing counts to their counters (the programs
        that made them have finished: this follows a device read)."""
        if not self._moe_pending:
            return
        got = jax.device_get([c for _, c in self._moe_pending])
        for (program, _), (rows, touched) in zip(self._moe_pending, got):
            self._m_moe_rows[program].inc(int(rows))
            self._m_moe_touched[program].inc(int(touched))
        self._moe_pending = []

    def _page_gauges(self):
        self._m_pages_free.set(self.kv.pages_free)
        self._m_occupancy.set(self.kv.occupancy())
        self._m_frag.set(self.kv.fragmentation())

    # -- lifecycle transitions ----------------------------------------------

    def _claim(self, slot: int, req: Request):
        work = (np.concatenate([np.asarray(req.prompt, np.int32),
                                np.asarray(req.output, np.int32)])
                if req.output else np.asarray(req.prompt, np.int32))
        self.active[slot] = req
        self._work[slot] = work
        self._fed[slot] = 0
        self._pos[slot] = 0
        self._decode_mask[slot] = False
        self.kv.note_tokens(slot, 0)
        now = time.monotonic()
        if req.claim_ts is None:
            self._m_queue_wait.observe(now - req.submit_ts)
        req.claim_ts = now
        self.sched.stage[req.uid] = Stage.SCHEDULED
        self.trace.event("request_schedule", uid=req.uid, slot=slot,
                         resume_tokens=len(req.output),
                         trace_id=req.trace_id)
        if req.preempts > 0:
            # a preempt-resume: the whole work buffer is a re-ingest
            self.trace.event("request_resume", uid=req.uid, slot=slot,
                             resume_tokens=len(work),
                             trace_id=req.trace_id)

    def _preempt(self, slot: int):
        req = self.active[slot]
        freed = self.kv.release(slot)
        # every token already ingested into the evicted pages is work the
        # resume must redo — charge it to the preempt waste cause now,
        # while the ingest depth is still known
        evicted_tokens = int(self._pos[slot])
        req.preempts += 1
        req.preempt_ts = time.monotonic()
        if evicted_tokens > 0:
            req.wasted_prefill_tokens += evicted_tokens
            self._m_wasted_preempt.inc(evicted_tokens)
        self.active[slot] = None
        self._work[slot] = None
        self._decode_mask[slot] = False
        self._pos[slot] = 0
        self.sched.stage[req.uid] = Stage.PREEMPTED
        self.sched.requeue(req)
        self._m_preempt.inc()
        self._m_queue_depth.set(len(self.sched))
        self._page_gauges()
        self.trace.event("request_preempt", uid=req.uid, slot=slot,
                         pages_freed=freed, tokens_done=len(req.output),
                         tokens_evicted=evicted_tokens,
                         trace_id=req.trace_id)

    def _complete(self, slot: int, req: Request, now: float):
        req.complete_ts = now
        self.completed.append(req)
        self.kv.release(slot)
        self.active[slot] = None
        self._work[slot] = None
        self._decode_mask[slot] = False
        self._pos[slot] = 0
        self._m_completed.inc()
        self._sk_e2e.observe(now - req.submit_ts)
        self._page_gauges()
        self.sched.stage[req.uid] = Stage.COMPLETE
        self.trace.event("request_complete", uid=req.uid,
                         tokens=len(req.output),
                         preempts=self.sched.preempts_of[req.uid],
                         trace_id=req.trace_id)
        span = self._spans.pop(req.uid, None)
        if span is not None:
            span.end(tokens=len(req.output))

    # -- tick phases --------------------------------------------------------

    def _admit(self):
        while len(self.sched):
            free = next((i for i in range(self.cfg.num_slots)
                         if self.active[i] is None), None)
            if free is None:
                # priority admission: preempt a strictly worse running req
                if not self.cfg.sched.preempt:
                    break
                incoming = self.sched.peek()
                victim = self.sched.victim(
                    [(s, r) for s, r in enumerate(self.active)
                     if r is not None], incoming=incoming)
                if victim is None:
                    break
                self._preempt(victim)
                continue
            req = self.sched.peek()
            work_len = len(req.prompt) + len(req.output or ())
            if not self.kv.ensure_capacity(free, work_len):
                if not self.cfg.sched.preempt:
                    break
                victim = self.sched.victim(
                    [(s, r) for s, r in enumerate(self.active)
                     if r is not None], incoming=req)
                if victim is None:
                    break
                self._preempt(victim)
                continue
            self._claim(free, self.sched.pop())
            self._m_queue_depth.set(len(self.sched))
            self._page_gauges()

    def _finish_prefill(self, slot: int, req: Request, logits: np.ndarray,
                        now: float):
        """Final chunk done: sample the next token from its host logits row
        (first generated token for a fresh request; the continuation token
        for a preempt-resume).  The sampler key is the token's absolute
        sequence index (= the work length), so a resume re-draws the
        identical token the uninterrupted run committed there."""
        tok = self.sampler.sample(logits, req.uid, int(self._pos[slot]))
        req.output.append(tok)
        self._next_tok[slot, 0] = tok
        self._m_tokens.inc()
        if req.preempt_ts is not None:
            # the eviction round trip (requeue -> re-claim -> re-prefill)
            # ends here; attribute it for the slo phase breakdown
            req.preempt_overhead_s += now - req.preempt_ts
            req.preempt_ts = None
        if len(req.output) == 1:
            req.first_token_ts = now
            self._m_ttft.observe(now - req.submit_ts)
            self._sk_ttft.observe(now - req.submit_ts)
            self.trace.event("request_first_token", uid=req.uid,
                             trace_id=req.trace_id)
        if (len(req.output) >= req.max_new_tokens or
                (req.eos_id is not None and tok == req.eos_id)):
            self._complete(slot, req, now)
            return
        self._decode_mask[slot] = True
        self.sched.stage[req.uid] = Stage.DECODE

    def _run_prefill(self):
        budget = self.cfg.sched.prefill_chunks_per_tick
        while budget > 0:
            slots = [i for i in range(self.cfg.num_slots)
                     if self.active[i] is not None
                     and not self._decode_mask[i]]
            if not slots:
                return
            for i in slots:
                if budget <= 0:
                    return
                req = self.active[i]
                if self._fed[i] == 0:
                    self.sched.stage[req.uid] = Stage.PREFILL
                    self.trace.event("request_prefill", uid=req.uid, slot=i,
                                     trace_id=req.trace_id,
                                     tokens=len(self._work[i]),
                                     chunks=self.prefill.num_chunks(
                                         len(self._work[i])))
                self._sync_control()
                was = self._fed[i]
                # chunk dispatch under the owning request's context: the
                # prefill_chunk event (and any compile-time kernel_dispatch
                # events) carry its trace_id
                with obs.use_context(self._request_context(req)):
                    with self._dispatch("prefill", uid=req.uid):
                        logits, self.state, fed = self._prefill_step(
                            self.params, self.state, self._work[i], was, i)
                        if self._moe:
                            self._moe_pending.append(
                                ("prefill", self.prefill.last_counts))
                    self.trace.event("prefill_chunk", uid=req.uid, slot=i,
                                     fed_from=was, fed_to=fed)
                self._fed[i] = fed
                self._pos[i] = fed
                self.kv.note_tokens(i, fed)
                self._m_disp_prefill.inc()
                self._m_prefill_tok.inc(fed - was)
                budget -= 1
                if fed == len(self._work[i]):
                    with self._phase("prefill.wait", uid=req.uid):
                        row = np.asarray(logits[0, 0], np.float32)
                        self._read_moe()
                    with self._phase("sample"):
                        self._finish_prefill(i, req, row, time.monotonic())
            with self._phase("pages"):
                self._page_gauges()

    def _grow_or_preempt(self, tokens_for):
        """Grow every decoding slot's pages to hold ``tokens_for(i)``
        tokens; exhaustion preempts the policy's victim (possibly the
        grower, which drops out of the decode mask)."""
        for i in range(self.cfg.num_slots):
            while (self._decode_mask[i]
                   and not self.kv.ensure_capacity(i, tokens_for(i))):
                if not self.cfg.sched.preempt:
                    raise RuntimeError(
                        "KV arena exhausted with preemption disabled "
                        "(sched.preempt=False); raise --max-pages")
                victim = self.sched.victim(
                    [(s, r) for s, r in enumerate(self.active)
                     if r is not None])
                self._preempt(victim)

    def _run_decode(self) -> int:
        if self._spec is not None and self._decode_mask.any():
            g_eff = min(self._spec.gamma,
                        self.cfg.max_len - 1
                        - max(int(self._pos[i])
                              for i in range(self.cfg.num_slots)
                              if self._decode_mask[i]))
            if g_eff >= 1:
                return self._run_decode_spec(g_eff)
            # a lane is one token from max_len: fall back to a plain step
        return self._run_decode_plain()

    def _run_decode_plain(self) -> int:
        with self._phase("pages"):
            self._grow_or_preempt(lambda i: int(self._pos[i]) + 1)
        if not self._decode_mask.any():
            return 0
        self._sync_control()
        t0 = time.perf_counter()
        first = next(i for i in range(self.cfg.num_slots)
                     if self._decode_mask[i])
        # batched dispatch: attributed to the first decode-ready lane
        with obs.use_context(self._request_context(self.active[first])), \
                self._dispatch("decode"):
            logits, self.state = self._decode_call(
                self.params, self.state,
                jnp.asarray(np.array(self._next_tok)))
        with self._phase("decode.wait"):
            logits = np.asarray(logits[:, 0], np.float32)   # device sync
            self._read_moe()
        step_dt = time.perf_counter() - t0
        self._m_disp_decode.inc()
        now = time.monotonic()
        n = 0
        with self._phase("sample"):
            for i in range(self.cfg.num_slots):
                if not self._decode_mask[i]:
                    continue
                n += 1
                req = self.active[i]
                self._pos[i] += 1
                self.kv.note_tokens(i, int(self._pos[i]))
                tok = self.sampler.sample(logits[i], req.uid,
                                          int(self._pos[i]))
                req.output.append(tok)
                self._next_tok[i, 0] = tok
                self._m_tokens.inc()
                self._m_tok_lat.observe(step_dt)
                self._sk_tok.observe(step_dt)
                if (len(req.output) >= req.max_new_tokens or
                        (req.eos_id is not None and tok == req.eos_id) or
                        int(self._pos[i]) >= self.cfg.max_len - 1):
                    self._complete(i, req, now)
        with self._phase("pages"):
            self._page_gauges()
        return n

    def _run_decode_spec(self, g_eff: int) -> int:
        """One speculation window over the decode-ready lanes: grow pages
        for the whole window, draft γ_eff tokens with the draft-tier params,
        verify in ONE batched full-tier multistep dispatch, commit each
        lane's accepted prefix + correcting/bonus token, then trim the
        pages beyond the committed tokens (same tick — rejected drafts
        never hold arena capacity across ticks)."""
        # positions pos .. pos+g_eff are written -> pos+g_eff+1 tokens
        with self._phase("pages"):
            self._grow_or_preempt(lambda i: int(self._pos[i]) + g_eff + 1)
        lanes = [i for i in range(self.cfg.num_slots) if self._decode_mask[i]]
        if not lanes:
            return 0
        self._sync_control()
        pos0 = self._pos.copy()
        t0 = time.perf_counter()
        W = g_eff + 1
        window = np.zeros((self.cfg.num_slots, W), np.int32)
        window[:, 0] = self._next_tok[:, 0]
        d_state = self.state                # self.state stays pre-draft
        window_ctx = self._request_context(self.active[lanes[0]])
        for j in range(g_eff):
            with obs.use_context(window_ctx), self._dispatch("decode"):
                d_logits, d_state = self._decode_call(
                    self._draft_params, d_state,
                    jnp.asarray(window[:, j:j + 1]))
            with self._phase("decode.wait"):
                d_logits = np.asarray(d_logits[:, 0], np.float32)
                self._read_moe()
            self._m_disp_draft.inc()
            with self._phase("sample"):
                for i in lanes:
                    window[i, j + 1] = self.sampler.sample(
                        d_logits[i], self.active[i].uid,
                        int(pos0[i]) + j + 1)
        with obs.use_context(window_ctx), self._phase("decode.dispatch"):
            f_logits, new_state = self._verify(self.params, self.state,
                                               jnp.asarray(window))
        with self._phase("decode.wait"):
            f_logits = np.asarray(f_logits, np.float32)
        self._m_disp_verify.inc()
        self.state = new_state
        window_dt = time.perf_counter() - t0
        now = time.monotonic()
        with self._phase("sample"):
            self._commit_window(lanes, pos0, window, f_logits, g_eff,
                                window_dt, now)
        with self._phase("pages"):
            self._page_gauges()
        return len(lanes)

    def _commit_window(self, lanes, pos0, window, f_logits, g_eff: int,
                       window_dt: float, now: float):
        """Commit each lane's accepted prefix + correcting/bonus token from
        the verify logits, trim the pages past it, and record the window."""
        W = g_eff + 1
        drafted = accepted = committed = 0
        for i in lanes:
            req = self.active[i]
            p = int(pos0[i])
            valid = W                   # window inputs this lane keeps
            finished = False
            lane_accepted = lane_committed = 0
            for j in range(W):
                tok = self.sampler.sample(f_logits[i, j], req.uid, p + j + 1)
                if j < g_eff:
                    drafted += 1
                    ok = int(window[i, j + 1]) == tok
                    accepted += ok
                    lane_accepted += ok
                req.output.append(tok)
                committed += 1
                lane_committed += 1
                self._m_tokens.inc()
                if (len(req.output) >= req.max_new_tokens or
                        (req.eos_id is not None and tok == req.eos_id) or
                        p + j + 1 >= self.cfg.max_len - 1):
                    valid = j + 1
                    finished = True
                    self._complete(i, req, now)
                    break
                if j < g_eff and int(window[i, j + 1]) != tok:
                    valid = j + 1       # first mismatch truncates
                    self._next_tok[i, 0] = tok
                    break
                if j == g_eff:
                    self._next_tok[i, 0] = tok   # bonus token
            if not finished:
                # roll back to the last valid input and free the tail pages
                self._pos[i] = p + valid
                self.kv.note_tokens(i, p + valid)
                self.kv.trim(i, p + valid)
            # every draft lane proposed g_eff tokens; the uncommitted ones
            # (incl. drafts past a truncation point) are discarded work
            lane_rejected = g_eff - lane_accepted
            if lane_rejected > 0:
                req.rejected_draft_tokens += lane_rejected
                self._spec_metrics.observe_wasted(lane_rejected)
            if lane_committed:
                self.trace.event("spec_commit", uid=req.uid,
                                 trace_id=req.trace_id,
                                 committed=lane_committed,
                                 accepted=lane_accepted,
                                 rejected=lane_rejected)
        if committed:
            per_tok = window_dt / committed
            for _ in range(committed):
                self._m_tok_lat.observe(per_tok)
                self._sk_tok.observe(per_tok)
        self._spec_metrics.observe_window(drafted, accepted, committed)

    # -- public loop --------------------------------------------------------

    def step(self) -> int:
        """One engine tick (admit → prefill → decode).  Returns the number
        of occupied slots after the tick."""
        self.tick_count += 1
        with obs.phase("serve.tick", self._m_phase["tick"],
                       step_num=self.tick_count):
            self._beat()
            with self._phase("admit"):
                self._admit()
            self._run_prefill()
            self._run_decode()
            n_active = sum(r is not None for r in self.active)
            self._m_slots.set(n_active)
            self._m_queue_depth.set(len(self.sched))
        self._m_ticks.inc()
        return n_active

    def run_until_drained(self, max_ticks: int = 10000):
        ticks = 0
        t0 = time.perf_counter()
        tok0 = self._m_tokens.value
        while (len(self.sched) or any(r is not None for r in self.active)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        dt = time.perf_counter() - t0
        if dt > 0:
            self._m_tps.set((self._m_tokens.value - tok0) / dt)
        return ticks
