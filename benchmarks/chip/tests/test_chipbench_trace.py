"""The trace reduction: busy time, kernel time, idle gaps by host span,
and the roofline share, on a synthetic trace and on a small trace
recorded on the chip."""

import json
import os

import numpy as np
import pytest

import chipbench_tiny
from chipbench import costs, tracefile
from chipbench.tracefile import Trace

FIXTURES = os.path.join(chipbench_tiny.HERE, "fixtures")
US = 1000  # nanoseconds


def _synthetic():
    # window 0..100 us; two demm kernels; a copy overlaps the second
    ops = [("fusion.1", 10 * US, 20 * US), ("demm_xwT", 20 * US, 40 * US),
           ("demm_xwT", 60 * US, 70 * US), ("copy.2", 65 * US, 80 * US),
           ("copy.3", 95 * US, 100 * US)]
    spans = [("bench.window", 0, 100 * US), ("bench.step", 5 * US, 45 * US),
             ("bench.wait", 45 * US, 58 * US),
             ("bench.step", 58 * US, 85 * US)]
    return Trace(devices={"/device:TPU:0": ops}, spans=spans)


def test_synthetic_reduction_by_hand():
    r = tracefile.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(100e-6)
    # busy: 10..40, 60..80 and 95..100
    assert r["busy_s"] == pytest.approx(55e-6)
    assert r["kernel_s"] == pytest.approx(30e-6)
    assert r["kernel_events"] == 2
    # gaps: 0..10 (middle 5 us, where the first step starts), 40..60
    # (middle 50: the wait), 80..95 (middle 87.5: after the last step)
    idle = dict(r["idle_gaps"])
    assert idle["bench.step"] == pytest.approx(10e-6)
    assert idle["bench.wait"] == pytest.approx(20e-6)
    assert idle[tracefile.NO_SPAN] == pytest.approx(15e-6)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    ops = dict(r["device_ops"])
    assert ops["demm_xwT"] == pytest.approx(30e-6)
    assert ops["fusion.1"] == pytest.approx(10e-6)
    assert ops["copy.2"] == pytest.approx(15e-6)


def test_a_trace_that_stops_recording_ends_the_window():
    t = _synthetic()
    t.devices["/device:TPU:0"] = [op for op in t.devices["/device:TPU:0"]
                                  if op[2] <= 40 * US]
    r = tracefile.reduce(t)
    assert r["window_s"] == pytest.approx(40e-6)
    assert r["busy_s"] == pytest.approx(30e-6)


def test_ops_are_clipped_to_the_window():
    t = _synthetic()
    r = tracefile.reduce(t, window=(25 * US, 65 * US))
    assert r["window_s"] == pytest.approx(40e-6)
    assert r["busy_s"] == pytest.approx(20e-6)      # 25..40, 60..65
    assert r["kernel_s"] == pytest.approx(20e-6)


def test_self_time_takes_nested_ops_out():
    ops = [("while.1", 0, 100), ("demm_xwT.2", 10, 40), ("fusion.3", 50, 60),
           ("copy.4", 120, 130)]
    assert tracefile.self_times(ops) == {"while.1": 60, "demm_xwT.2": 30,
                                         "fusion.3": 10, "copy.4": 10}


def test_op_name_is_the_ops_own():
    text = ("%fusion.7 = f32[16,6912]{1,0} fusion(f32[16,6912]{1,0} "
            "%demm_xwT.81), kind=kLoop")
    assert tracefile.op_name(text) == "fusion.7"
    assert not tracefile.is_kernel(tracefile.op_name(text))
    assert tracefile.is_kernel(tracefile.op_name(
        "%demm_xwT.81 = f32[16,6912]{1,0} custom-call(bf16[16,2560])"))


def test_async_copies_count_as_busy():
    t = _synthetic()
    t.copies = {"/device:TPU:0": [("copy-start.1", 40 * US, 50 * US)]}
    r = tracefile.reduce(t)
    assert r["busy_s"] == pytest.approx(65e-6)
    assert r["kernel_s"] == pytest.approx(30e-6)


def test_union_merges_overlaps():
    assert tracefile.union([(5, 9), (1, 3), (2, 4), (9, 10), (7, 7)]) == [
        (1, 4), (5, 10)]


def _fixture():
    with open(os.path.join(FIXTURES, "kernel_trace.json")) as f:
        meta = json.load(f)
    trace = tracefile.load(os.path.join(FIXTURES,
                                        "kernel_trace.xplane.pb"))
    return meta, trace


def test_recorded_trace_reduces_as_computed_by_hand():
    meta, trace = _fixture()
    r = tracefile.reduce(trace)
    lo, hi = tracefile.window_of(trace)
    (ops,) = trace.devices.values()
    hi = min(hi, max(e for _, _, e in ops))   # the last recorded op
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
              if min(e, hi) > max(s, lo)]
    # busy by a 1 ns timeline, independent of the interval union:
    # operations and asynchronous copies
    line = np.zeros(hi - lo, bool)
    for _, s, e in inside + [c for cs in trace.copies.values() for c in cs]:
        line[max(s, lo) - lo:max(min(e, hi) - lo, 0)] = True
    assert r["busy_s"] == pytest.approx(line.sum() / 1e9)
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    kernels = [(n, s, e) for n, s, e in inside if n.startswith("demm")]
    assert len(kernels) == r["kernel_events"] == meta["kernel_calls"]
    assert r["kernel_s"] == pytest.approx(sum(e - s for _, s, e in kernels)
                                          / 1e9)
    idle = 1 - r["busy_s"] / r["window_s"]
    assert 0 < idle < 1
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # the three 20 ms sleeps before the last step are idle time under
    # their span (the window ends with the last operation)
    assert dict(r["idle_gaps"]).get("bench.wait", 0) > 3 * 0.02 * 0.9


def test_recorded_kernel_roofline_share_is_a_share():
    meta, trace = _fixture()
    peak = chipbench_tiny.PEAK
    r = tracefile.reduce(trace, peak)
    rows, o, k, n, m = (meta[x] for x in ("rows", "out", "in", "n", "m"))
    # by hand, from the shapes the recording passed
    stream = o * k * n // m * (meta["value_bytes"] + meta["index_bytes"])
    flops = 2.0 * rows * o * k * n / m
    nbytes = stream + rows * k * meta["x_bytes"] + rows * o * meta["y_bytes"]
    least = r["kernel_events"] * max(flops / peak["bf16_flops_per_s"],
                                     nbytes / peak["hbm_bytes_per_s"])
    assert r["kernel_least_s"] == pytest.approx(least)
    share = r["kernel_least_s"] / r["kernel_s"]
    assert 0 < share <= 1
    # as recorded on the chip: one 16-row call at 5:80 takes about 0.7 ms
    # against a least time of about 12 us (bytes-bound)
    assert r["kernel_s"] / r["kernel_events"] == pytest.approx(
        meta["reduced"]["kernel_s"] / meta["reduced"]["kernel_events"])


def test_kernel_call_reads_the_operands_of_the_hlo_text():
    text = ("%demm_xwT.46 = f32[256,6912]{1,0:T(8,128)} custom-call("
            "bf16[256,2560]{1,0:T(8,128)(2,1)} %fusion.92, "
            "f32[32,5,6912]{2,1,0:T(8,128)} %copy_bitcast_fusion.36, "
            "s32[32,5,6912]{2,1,0:T(8,128)} %copy_bitcast_fusion.37), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_"
            "constraints={bf16[256,2560]{1,0}, f32[32,5,6912]{2,1,0}}")
    call = costs.kernel_call(text)
    assert call.flops == 2.0 * 256 * 32 * 5 * 6912
    assert call.bytes == (256 * 2560 * 2 + 2 * 32 * 5 * 6912 * 4
                          + 256 * 6912 * 4)
    with pytest.raises(ValueError):
        costs.kernel_call("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)")
