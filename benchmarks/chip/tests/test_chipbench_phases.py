"""The phase reduction: idle gaps under the innermost of nested spans, the
compiled programs' device time, the device clock's lead, and the readers
of the engine's phase and compile counters."""

import json
import os

import pytest

import chipbench_tiny
from chipbench import phasetrace, spec, tracefile
from chipbench.phasetrace import ProgramTrace

FIXTURES = os.path.join(chipbench_tiny.HERE, "fixtures")
US = 1000          # nanoseconds
MS = 1000 * US
DEV = "/device:TPU:0"


def _nested():
    # window 0..100 us: a step holding an admission (1..2) and a decode
    # wait (8..90); the device is idle at 0..10, 50..60 and 92..98
    ops = [("fusion.1", 10 * US, 50 * US), ("fusion.2", 60 * US, 92 * US),
           ("fusion.3", 98 * US, 100 * US)]
    spans = [("bench.window", 0, 100 * US), ("bench.step", 0, 100 * US),
             ("serve.admit", 1 * US, 2 * US),
             ("serve.tick", 2 * US, 99 * US),
             ("serve.decode.wait", 8 * US, 90 * US)]
    return ProgramTrace(devices={DEV: ops}, spans=spans)


def test_a_gap_goes_to_the_innermost_covering_span():
    r = phasetrace.reduce(_nested())
    idle = dict(r["idle_gaps"])
    # middles 5 (tick), 55 (decode wait) and 95 (tick, after the wait)
    assert idle == pytest.approx({"serve.tick": 16e-6,
                                  "serve.decode.wait": 10e-6})
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # cut at every span boundary: 0..10 spans the step, the admission, the
    # tick and the wait's start
    assert dict(r["idle_split"]) == pytest.approx({
        "bench.step": 1e-6, "serve.admit": 1e-6, "serve.tick": 12e-6,
        "serve.decode.wait": 12e-6})


def test_a_gap_after_a_nested_span_stays_under_its_parent():
    t = _nested()
    t.spans = [sp for sp in t.spans if sp[0] != "serve.tick"]
    idle = dict(phasetrace.reduce(t)["idle_gaps"])
    assert idle == pytest.approx({"bench.step": 16e-6,
                                  "serve.decode.wait": 10e-6})
    assert tracefile.NO_SPAN not in idle


def test_a_serve_span_goes_ahead_of_a_bench_span_of_the_same_start():
    spans = sorted([("bench.step", 0, 50), ("serve.tick", 0, 50),
                    ("bench.wait", 50, 60)], key=lambda sp: sp[1])
    starts = [s for _, s, _ in spans]
    assert phasetrace.span_at(spans, starts, 50, 10) == "serve.tick"
    assert phasetrace.span_at(spans, starts, 50, 55) == "bench.wait"
    assert phasetrace.span_at(spans, starts, 50, 70) == tracefile.NO_SPAN


def test_programs_count_time_and_clip_their_executions():
    t = _nested()
    t.modules = {DEV: [("jit_decode_step", 10 * US, 50 * US),
                       ("jit_decode_step", 60 * US, 80 * US),
                       ("jit_prefill_chunk", 80 * US, 110 * US),
                       ("jit_prefill_chunk", 120 * US, 130 * US)]}
    progs = phasetrace.reduce(t)["programs"]
    assert progs["jit_decode_step"] == pytest.approx(
        {"count": 2, "median_ms": 0.03, "total_s": 60e-6})
    # one execution overlaps the window, by 20 of its 30 us
    assert progs["jit_prefill_chunk"] == pytest.approx(
        {"count": 1, "median_ms": 0.03, "total_s": 20e-6})


def _skewed(skew_ns: int, late: int = 3):
    """Ticks of a burst of three prefill chunks, then a decode step, on a
    device whose clock runs ``skew_ns`` behind the host's; the device
    tracer misses the first ``late`` executions."""
    spans, execs, device_free = [("bench.window", 0, 400 * MS)], [], 0
    for tick in range(12):
        t = tick * 30 * MS
        spans.append(("serve.tick", t, t + 29 * MS))
        for k in range(3):
            d = t + k * 2 * MS                  # 2 ms a dispatch
            spans.append(("serve.prefill.dispatch", d, d + 2 * MS))
            start = max(d + 40 * US * (k + 1), device_free)
            device_free = start + 5 * MS
            execs.append(("jit_prefill_chunk", start, device_free))
        d = t + 15 * MS                         # as the burst drains
        spans.append(("serve.decode.dispatch", d, d + 1 * MS))
        start = max(d + 60 * US, device_free)
        device_free = start + 4 * MS
        execs.append(("jit_decode_step", start, device_free))
    execs = [(n, s - skew_ns, e - skew_ns) for n, s, e in execs[late:]]
    ops = [(f"fusion.{i}", s, e) for i, (_, s, e) in enumerate(execs)]
    return ProgramTrace(devices={DEV: ops}, spans=spans,
                        modules={DEV: execs})


def test_clock_skew_is_recovered_from_dispatch_pairs():
    for late in (0, 3, 5):
        skew = phasetrace.clock_skew_ns(_skewed(1 * MS, late))
        assert skew == pytest.approx(1 * MS, abs=0.1 * MS)
    assert phasetrace.clock_skew_ns(_skewed(0)) == 0
    assert phasetrace.clock_skew_ns(ProgramTrace(devices={}, spans=[])) \
        is None


def test_idle_is_attributed_on_the_device_clock():
    # the 20 us gap between the prefill burst and each decode execution
    # falls inside the decode's dispatch; left on the host's clock, the
    # spans would put it 1 ms earlier, under the tick
    t = _skewed(1 * MS, late=0)
    r = phasetrace.reduce(t)
    assert r["clock_skew_ms"] == pytest.approx(1.0, abs=0.1)
    assert r["busy_s"] == tracefile.reduce(t)["busy_s"]
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert idle["serve.decode.dispatch"] == pytest.approx(12 * 20e-6)


def _recorded():
    path = os.path.join(FIXTURES, "kernel_trace.xplane.pb")
    return tracefile.load(path), phasetrace.load(path)


def test_recorded_trace_keeps_every_number_of_the_reduction():
    base, ext = _recorded()
    peak = chipbench_tiny.PEAK
    before, after = tracefile.reduce(base, peak), phasetrace.reduce(ext, peak)
    assert set(before) <= set(after)
    for key in before:
        if key != "idle_gaps":
            assert after[key] == before[key], key
    assert sum(v for _, v in after["idle_gaps"]) == pytest.approx(
        sum(v for _, v in before["idle_gaps"]))
    # the recording predates the engine's spans: the bench spans alone
    # name its gaps, as before
    assert dict(after["idle_gaps"]) == pytest.approx(
        dict(before["idle_gaps"]))


def _engine_fixture():
    with open(os.path.join(FIXTURES, "engine_trace.json")) as f:
        meta = json.load(f)
    trace = phasetrace.load(os.path.join(FIXTURES,
                                         "engine_trace.xplane.pb"))
    return meta, trace


def test_recorded_engine_trace_names_programs_and_phases():
    meta, trace = _engine_fixture()
    r = phasetrace.reduce(trace, chipbench_tiny.PEAK)
    progs = r["programs"]
    assert progs["jit_decode_step"]["count"] == meta["dispatched"]["decode"]
    assert progs["jit_prefill_chunk"]["count"] == \
        meta["dispatched"]["prefill"]
    assert sum(1 for name, _, _ in trace.spans if name == "serve.tick") == \
        meta["ticks"]
    idle = dict(r["idle_gaps"])
    assert any(name.startswith("serve.") for name in idle)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["clock_skew_ms"] is not None and r["clock_skew_ms"] >= 0
    # as reduced when it was recorded, key for key; the reduction has
    # since added the kernel families, which add up to the totals
    got = json.loads(json.dumps(r))
    assert {k: got[k] for k in meta["reduced"]} == meta["reduced"]
    assert set(got) - set(meta["reduced"]) == {"kernels"}
    assert sum(f["s"] for f in got["kernels"].values()) == pytest.approx(
        got["kernel_s"])


def _reader(name):
    return spec.load_reader(chipbench_tiny.BENCH_DIR, name)


def test_host_ms_per_tick_reads_the_phase_counters():
    read = _reader("host_ms_per_tick")
    counters = {"serve_ticks_total": 4,
                "serve_phase_seconds_total{phase=tick}": 1.0,
                "serve_phase_seconds_total{phase=decode_wait}": 0.7,
                "serve_phase_seconds_total{phase=prefill_wait}": 0.1,
                "serve_phase_seconds_total{phase=sample}": 0.05}
    assert read({"counters_window": counters}) == pytest.approx(50.0)
    no_prefill = dict(counters)
    del no_prefill["serve_phase_seconds_total{phase=prefill_wait}"]
    assert read({"counters_window": no_prefill}) == pytest.approx(75.0)
    assert read({"counters_window": {}}) is None
    assert read({"counters_window": {**counters,
                                     "serve_ticks_total": 0}}) is None


def test_compiles_in_window_reads_both_programs():
    read = _reader("compiles_in_window")
    assert read({"counters_window": {
        "serve_compiles_total{program=decode}": 0,
        "serve_compiles_total{program=prefill}": 0}}) == 0
    assert read({"counters_window": {
        "serve_compiles_total{program=decode}": 1,
        "serve_compiles_total{program=prefill}": 2}}) == 3
    assert read({"counters_window": {"serve_ticks_total": 3}}) is None
