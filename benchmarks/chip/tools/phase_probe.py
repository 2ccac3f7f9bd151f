"""One traced run of a cell, read by phase and by compiled program.

    python3 benchmarks/chip/tools/phase_probe.py --workload stablelm_3b.chat \\
        --seed 5 --seconds 51 --out phase/chat.json

On the chip: first the cost of one ``obs.phase`` span with no profiler
attached and with one recording, then one run of the cell exactly as
``run.py --trace 1`` makes it, with the trace kept until
:func:`chipbench.phasetrace.reduce` has read it.  Writes to ``--out`` and
prints as JSON: the run's result line, the reduction (``programs``,
``clock_skew_ms``, ``idle_gaps`` by the innermost ``serve.*`` or
``bench.*`` span), the longest idle gaps with the spans around them, each
compiled program's median device time, the share of idle time under
``serve.*`` spans, and three consistency checks:
executions of ``jit_decode_step`` against the window's decode dispatches,
the two programs' device time against busy time, and the decode program's
device time against ``decode_step_ms``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path[:0] = [BENCH_DIR, os.path.join(REPO_ROOT, "src")]

COST_CALLS = 100_000


def phase_cost_us(calls: int = COST_CALLS) -> dict:
    """Microseconds per ``obs.phase`` span with a counter, with no
    profiler attached and with one recording."""
    import jax

    from repro import obs

    counter = obs.MetricsRegistry().counter("phase_cost_seconds_total")

    def per_call():
        t = time.perf_counter()
        for _ in range(calls):
            with obs.phase("serve.cost", counter):
                pass
        return 1e6 * (time.perf_counter() - t) / calls

    per_call()
    off = per_call()
    tmp = tempfile.mkdtemp(prefix="chipbench-cost-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    try:
        on = per_call()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(tmp, ignore_errors=True)
    return {"profiler_off_us": off, "profiler_on_us": on, "calls": calls}


def longest_gaps(trace, skew_ms, top: int = 8) -> list:
    """The window's longest idle gaps, each with the compiled programs on
    either side and every span that overlaps it (start from the gap's
    start, and length, in ms), the spans moved onto the device's clock."""
    from chipbench import phasetrace, tracefile

    lo, hi = phasetrace.window(trace)
    shift = int((skew_ms or 0) * 1e6)
    runs = sorted((ev for evs in trace.modules.values() for ev in evs),
                  key=lambda ev: ev[1])
    out = []
    for s, e in sorted(phasetrace.device_gaps(trace, lo, hi),
                       key=lambda g: g[0] - g[1])[:top]:
        out.append({
            "gap_ms": (e - s) / 1e6,
            "after": next((n for n, _, end in reversed(runs) if end <= s),
                          None),
            "before": next((n for n, start, _ in runs if start >= e), None),
            "spans": [[n, (a - shift - s) / 1e6, (b - a) / 1e6]
                      for n, a, b in sorted(trace.spans, key=lambda x: x[1])
                      if a - shift < e and b - shift > s
                      and n != tracefile.WINDOW_SPAN]})
    return out


def checks(rec: dict, reduced: dict, step_ms) -> dict:
    from chipbench import stats

    programs = reduced["programs"]
    decode = programs.get("jit_decode_step")
    prefill = programs.get("jit_prefill_chunk")
    idle = dict(reduced["idle_gaps"])
    idle_s = sum(idle.values())
    named = sum(v for k, v in idle.items() if k.startswith("serve."))
    out = {
        "decode_device_ms": decode and decode["median_ms"],
        "prefill_chunk_device_ms": prefill and prefill["median_ms"],
        "idle_s": idle_s,
        "idle_share_under_serve_spans": stats.percent(named, idle_s),
        "decode_executions": decode and decode["count"],
        "decode_dispatches": rec["dispatched"]["decode"],
        "programs_over_busy": stats.percent(
            sum(p["total_s"] for p in (decode, prefill) if p),
            reduced["busy_s"]),
        "decode_step_ms": step_ms,
    }
    out["decode_device_within_decode_step"] = (
        None if decode is None or step_ms is None
        else decode["median_ms"] <= step_ms)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    import run as run_mod
    from chipbench import cell as cell_mod
    from chipbench import device, phasetrace, spec, tracefile
    from repro.launch.compile_cache import use_compile_cache

    cell = spec.resolve(args.workload)
    devices = jax.devices()
    peak = device.check(devices, cell.chips, device.load_peaks())
    devices = devices[:cell.chips]
    use_compile_cache()
    cost = phase_cost_us()
    cell_mod.log(f"obs.phase cost: {cost}")
    trace_dir = tempfile.mkdtemp(prefix="chipbench-phase-")
    try:
        rec = cell_mod.run(cell, args.seed, args.seconds, True,
                           t_start=T_START, devices=devices, peak=peak,
                           trace_dir=trace_dir)
        t = time.monotonic()
        trace = phasetrace.load(tracefile.find_xplane(trace_dir))
        reduced = phasetrace.reduce(trace, peak)
        gaps = longest_gaps(trace, reduced["clock_skew_ms"])
        del trace
        cell_mod.log(f"phase reduction in {time.monotonic() - t:.3f}s")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    line = run_mod.result_line(cell, rec, True, device.describe(devices))
    step_ms = line["metrics"].get("decode_step_ms", {}).get("value")
    summary = {"workload": cell.name, "seed": args.seed,
               "phase_cost": cost, "line": line, "reduced": reduced,
               "longest_gaps": gaps,
               "checks": checks(rec, reduced, step_ms),
               "phase_seconds_window": {
                   k: v for k, v in rec["counters_window"].items()
                   if k.startswith(("serve_phase_seconds_total",
                                    "serve_ticks_total",
                                    "serve_compiles_total"))}}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
