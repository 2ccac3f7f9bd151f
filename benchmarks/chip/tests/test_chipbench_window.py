"""Percentiles and rates are taken over every sample and the whole
window, and a stall moves them.  A fake engine with a fake clock stands in
for the program, so the runner's own bookkeeping is what is tested."""

import dataclasses
from types import SimpleNamespace

import numpy as np
import pytest

import chipbench_tiny
from chipbench import spec, stats, traffic
from chipbench.loop import Runner


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


@dataclasses.dataclass
class Req:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    output: list = None
    claim_ts: float = None
    complete_ts: float = None
    preempts: int = 0


class FakeEngine:
    """Admits into free slots, prefills ``chunk`` tokens a step, then
    decodes one token a step; every ``stall_every``-th step takes
    ``stall_s`` instead of ``step_s``."""

    def __init__(self, clock, slots=16, chunk=16, step_s=0.05,
                 stall_every=0, stall_s=0.0):
        self.clock, self.chunk, self.step_s = clock, chunk, step_s
        self.stall_every, self.stall_s = stall_every, stall_s
        self.sched = []
        self.active = [None] * slots
        self.kv = SimpleNamespace(tokens=np.zeros(slots, np.int64))
        self.n = 0

    def submit(self, req):
        req.output = []
        self.sched.append(req)

    def step(self):
        self.n += 1
        stall = self.stall_every and self.n % self.stall_every == 0
        self.clock.t += self.stall_s if stall else self.step_s
        for i, r in enumerate(self.active):
            if r is None and self.sched:
                self.active[i] = r = self.sched.pop(0)
                r.claim_ts = self.clock()
                self.kv.tokens[i] = 0
        for i, r in enumerate(self.active):
            if r is None:
                continue
            p = len(r.prompt)
            if self.kv.tokens[i] < p:
                self.kv.tokens[i] = min(p, self.kv.tokens[i] + self.chunk)
                if self.kv.tokens[i] < p:
                    continue
            r.output.append(1)
            self.kv.tokens[i] += 1
            if len(r.output) >= r.max_new_tokens:
                r.complete_ts = self.clock()
                self.active[i] = None


MIX = dict(chipbench_tiny.MIX, rate_per_s=6.0, preroll_s=2.0,
           drain_limit_s=20.0)


def _window(loop="open", **engine_kw):
    clock = Clock()
    eng = FakeEngine(clock, **engine_kw)
    mix = dict(MIX, loop=loop, clients=6, pool_size=400)
    pool = traffic.build_pool(mix, 20.0, 3)
    runner = Runner(eng, pool, mix, 3, 512,
                    lambda uid, prompt, n: Req(uid, prompt, n),
                    clock=clock, sleep=clock.sleep)
    runner.start(mix["preroll_s"])
    runner.preroll()
    runner.window(20.0)
    runner.drain(mix["drain_limit_s"])
    rec = runner.record()
    rec.update(setup_s=1.0)
    return runner, rec


def _read(name, rec):
    return spec.load_reader(chipbench_tiny.BENCH_DIR, name)(rec)


def test_percentiles_cover_every_sample():
    runner, rec = _window()
    t0, t1 = runner.t0, runner.window_end
    gaps = [b - a for tr in runner.all
            for a, b in zip(tr.stamps, tr.stamps[1:]) if t0 <= b <= t1]
    assert len(gaps) == len(rec["token_gaps_s"]) > 100
    assert _read("token_gap_p95_ms", rec) == pytest.approx(
        1000 * np.percentile(gaps, 95))
    assert _read("itl_mean_ms", rec) == pytest.approx(1000 * np.mean(gaps))
    due = [t for t in runner.all if t0 <= t.due < t1]
    assert rec["attempted"] == len(due) and rec["failed"] == 0
    ttft = [t.first_token - t.due for t in due]
    assert _read("ttft_p50_ms", rec) == pytest.approx(
        1000 * np.percentile(ttft, 50))
    wait = [t.req.claim_ts - t.due for t in due]
    assert _read("queue_wait_p50_ms", rec) == pytest.approx(
        1000 * np.percentile(wait, 50))


def test_rate_is_over_the_whole_window():
    runner, rec = _window(loop="closed")
    assert rec["window_s"] == pytest.approx(20.0)
    assert _read("prompt_tokens_per_s", rec) == pytest.approx(
        rec["prompt_tokens_window"] / 20.0)
    # every prompt token ingested in the window is counted once
    assert rec["prompt_tokens_window"] > 0


@pytest.mark.parametrize("metric,loop,worse", [
    ("ttft_p50_ms", "open", "higher"),
    ("itl_mean_ms", "open", "higher"),
    ("token_gap_p95_ms", "open", "higher"),
    ("queue_wait_p50_ms", "open", "higher"),
    ("prompt_tokens_per_s", "closed", "lower"),
])
def test_injected_stall_moves_metric(metric, loop, worse):
    _, base = _window(loop=loop)
    _, stalled = _window(loop=loop, stall_every=8, stall_s=0.3)
    a, b = _read(metric, base), _read(metric, stalled)
    assert (b > 1.2 * a) if worse == "higher" else (b < a / 1.2), (a, b)


def test_percentile_matches_numpy_and_sorts_missing_last():
    xs = list(np.random.default_rng(0).random(101))
    for q in (0, 5, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([1.0, 2.0, float("inf")], 50) == 2.0
    assert stats.percentile([1.0, float("inf"), float("inf")], 50) == \
        float("inf")
    assert stats.percentile([], 50) is None


def test_sketch_window_counts_only_the_window():
    from repro.obs.sketch import QuantileSketch

    sk = QuantileSketch()
    for v in (0.5, 0.5, 0.5):
        sk.observe(v)
    start = sk.to_entry()
    window = [0.01 * (i + 1) for i in range(99)]
    for v in window:
        sk.observe(v)
    w = stats.sketch_window(start, sk.to_entry())
    assert w["count"] == 99
    got = stats.sketch_quantile(w, 0.5)
    assert got == pytest.approx(np.percentile(window, 50), rel=sk.alpha)
