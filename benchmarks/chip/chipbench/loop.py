"""Drive the program's engine on the wall clock.

The window calls ``PagedServeEngine.submit`` and ``step`` directly; the
program's own loops (tick replay, ``run_until_drained``) are not used.
After every ``step`` the runner stamps each new token of each request with
the time ``step`` returned, which is when a caller of this API sees it.

* Open loop: request ``i`` is due at ``t0 + offset_i`` whether or not
  earlier ones finished; it is submitted at the first chance after that,
  and its latency counts from when it was due.
* Closed loop: each client submits its next request the moment its
  previous one completes.

Every call into the engine and the runner's own bookkeeping runs under a
``bench.*`` ``TraceAnnotation``, so a traced run can say what the host was
doing in each idle gap of the device.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench.traffic import Planned, make_prompt


@dataclasses.dataclass
class Tracked:
    """What the runner knows of one request."""

    plan: Planned
    req: object                    # the program's Request
    due: float
    submitted: float = 0.0
    stamps: List[float] = dataclasses.field(default_factory=list)
    depth: int = 0                 # prompt tokens ingested at least once
    done: bool = False

    @property
    def first_token(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


class Runner:
    """Feeds one engine from a pool of planned requests."""

    def __init__(self, engine, pool: List[Planned], mix: dict, seed: int,
                 vocab: int, make_request: Callable, *,
                 clock=time.monotonic, sleep=time.sleep, span=None):
        self.engine, self.mix, self.seed, self.vocab = (engine, mix, seed,
                                                        vocab)
        self.make_request = make_request
        self.clock, self.sleep = clock, sleep
        self.span = span or _no_span
        self.pool = list(pool)
        self.next = 0                  # next pool entry to offer
        self.live: List[Tracked] = []  # submitted, not yet complete
        self.all: List[Tracked] = []
        self.t0 = 0.0
        self.window_end = 0.0
        # first-time prompt tokens ingested in the window, the sum of their
        # attention spans, and the prefills that finished in it
        self.prompt_tokens_window = 0
        self.prompt_ctx_window = 0
        self.prefills_window = 0
        self.steps = 0

    # -- offering -----------------------------------------------------------

    def _submit(self, plan: Planned, due: float):
        prompt = make_prompt(self.seed, plan.uid, plan.prompt_len, self.vocab)
        req = self.make_request(plan.uid, prompt, plan.max_new)
        with self.span("bench.submit"):
            self.engine.submit(req)
        tr = Tracked(plan=plan, req=req, due=due, submitted=self.clock())
        self.live.append(tr)
        self.all.append(tr)

    def _offer(self, now: float, until: float):
        """Submit what the loop offers by ``now`` (nothing due at or past
        ``until``)."""
        if self.mix["loop"] == "open":
            while self.next < len(self.pool):
                due = self.t0 + self.pool[self.next].offset
                if due > now or due >= until:
                    break
                self._submit(self.pool[self.next], due)
                self.next += 1
        else:
            while (len(self.live) < self.mix["clients"]
                   and self.next < len(self.pool) and now < until):
                self._submit(self.pool[self.next], now)
                self.next += 1

    def _next_due(self) -> Optional[float]:
        if self.mix["loop"] == "open" and self.next < len(self.pool):
            return self.t0 + self.pool[self.next].offset
        return None

    # -- stepping -----------------------------------------------------------

    def _busy(self) -> bool:
        return bool(len(self.engine.sched)) or any(
            r is not None for r in self.engine.active)

    def _stamp(self, now: float):
        in_window = self.t0 <= now <= self.window_end
        slot_of = {id(r): i for i, r in enumerate(self.engine.active)
                   if r is not None}
        tokens = self.engine.kv.tokens
        still = []
        for tr in self.live:
            n = len(tr.req.output or ())
            if n > len(tr.stamps):
                tr.stamps.extend([now] * (n - len(tr.stamps)))
            if tr.req.complete_ts is not None or n > 0:
                depth = tr.plan.prompt_len      # prefill finished
            elif id(tr.req) in slot_of:
                depth = min(int(tokens[slot_of[id(tr.req)]]),
                            tr.plan.prompt_len)
            else:
                depth = tr.depth                # queued or preempted
            if depth > tr.depth:
                if in_window:
                    a, b = tr.depth, depth
                    self.prompt_tokens_window += b - a
                    # token at position p attends over p + 1 positions
                    self.prompt_ctx_window += (b * (b + 1) - a * (a + 1)) // 2
                    if b == tr.plan.prompt_len:
                        self.prefills_window += 1
                tr.depth = depth
            if tr.req.complete_ts is not None:
                tr.done = True
            else:
                still.append(tr)
        self.live = still

    def step(self):
        with self.span("bench.step"):
            self.engine.step()
        self.steps += 1
        now = self.clock()
        with self.span("bench.stamp"):
            self._stamp(now)

    def run(self, until: float, offer_until: float,
            stop: Optional[Callable[[], bool]] = None):
        """Step until ``until`` (or ``stop()``), offering requests due
        before ``offer_until``."""
        while True:
            now = self.clock()
            if now >= until or (stop is not None and stop()):
                return
            self._offer(now, offer_until)
            if self._busy():
                self.step()
                continue
            nxt = self._next_due()
            wake = min(until, nxt if nxt is not None else until)
            with self.span("bench.wait"):
                self.sleep(max(0.0, min(wake - now, 0.05)))

    # -- phases -------------------------------------------------------------

    def start(self, preroll_s: float) -> None:
        """Begin offering load; the window opens ``preroll_s`` later."""
        self.t0 = self.clock() + preroll_s
        self.window_end = self.t0

    def preroll(self, until: Optional[float] = None):
        """Offer load up to ``until`` (default: the window's opening)."""
        self.run(until=self.t0 if until is None else min(until, self.t0),
                 offer_until=float("inf"))

    def window(self, seconds: float):
        self.window_end = self.t0 + seconds
        with self.span("bench.window"):
            self.run(until=self.window_end, offer_until=float("inf"))

    def drain(self, limit_s: float):
        """After the window: keep the load on until every request due in
        the window has its first token, for at most ``limit_s``."""
        if limit_s <= 0:
            return
        waiting = [t for t in self.counted() if t.first_token is None]
        self.run(until=self.window_end + limit_s, offer_until=float("inf"),
                 stop=lambda: all(t.first_token is not None
                                  for t in waiting))

    # -- what the window saw ------------------------------------------------

    def counted(self) -> List[Tracked]:
        """Requests the window is judged on: for an open loop those due in
        it; for a closed loop those in flight at any time in it."""
        if self.mix["loop"] == "open":
            return [t for t in self.all
                    if self.t0 <= t.due < self.window_end]
        return [t for t in self.all
                if t.due < self.window_end
                and (not t.done or t.req.complete_ts >= self.t0)]

    def record(self) -> Dict[str, object]:
        counted = self.counted()
        gaps, gen_ctx = [], []
        for tr in self.all:
            for j in range(1, len(tr.stamps)):
                if self.t0 <= tr.stamps[j] <= self.window_end:
                    gaps.append(tr.stamps[j] - tr.stamps[j - 1])
            for j in range(1, len(tr.stamps)):
                if self.t0 <= tr.stamps[j] <= self.window_end:
                    # output token j comes from a decode step whose input
                    # sits at position prompt_len + j - 1
                    gen_ctx.append(tr.plan.prompt_len + j)
        # an open loop owes every request due in the window an answer; a
        # closed loop's requests still in flight at the close are not due
        failed = (sum(t.first_token is None for t in counted)
                  if self.mix["loop"] == "open" else 0)
        return {
            "t0": self.t0,
            "attempted": len(counted),
            "failed": failed,
            "window_s": self.window_end - self.t0,
            "requests": [{
                "uid": t.plan.uid, "prompt_len": t.plan.prompt_len,
                "max_new": t.plan.max_new, "due": t.due,
                "claim": t.req.claim_ts, "first_token": t.first_token,
                "submitted": t.submitted,
                "tokens": len(t.stamps), "done": t.done,
                "preempts": int(getattr(t.req, "preempts", 0)),
            } for t in counted],
            "token_gaps_s": gaps,
            "gen_tokens_window": len(gen_ctx),
            "gen_ctx": np.asarray(gen_ctx, np.int64),
            "prompt_tokens_window": self.prompt_tokens_window,
            "prompt_ctx_window": self.prompt_ctx_window,
            "prefills_window": self.prefills_window,
            "steps": self.steps,
        }


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _no_span(name):
    return _NoSpan()
