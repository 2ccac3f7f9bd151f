"""Show what a cell's profiler trace holds, for whoever changes the trace
reduction.

    python3 benchmarks/chip/tools/trace_probe.py --workload stablelm_3b.chat \\
        --seed 5 --seconds 5 [--out <dir>]

Runs the cell once with ``--trace 1`` on the chip, keeps the trace under
``--out`` (default: a temporary directory, removed after), and prints as
JSON: every plane with its lines and event counts, the first and last
timestamp of each line (host and device share one clock when these
overlap), the device operations with the most self time, the longest idle
gaps with the operations around them and the host events at their middle,
and the reduction's own result.
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


def describe(path: str, peak: dict, top: int = 40) -> dict:
    import jax

    from chipbench import tracefile

    data = jax.profiler.ProfileData.from_file(path)
    planes, host = [], []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            n, first, last = 0, None, None
            for e in line.events:
                n += 1
                end = e.start_ns + e.duration_ns
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = end if last is None else max(last, end)
                if plane.name.startswith("/host:"):
                    host.append((e.name, int(e.start_ns), int(end),
                                 line.name))
            lines.append({"line": line.name, "events": n, "first_ns": first,
                          "last_ns": last})
        planes.append({"plane": plane.name, "lines": lines})
    trace = tracefile.load(path)
    lo, hi = tracefile.window_of(trace)
    (ops,) = trace.devices.values()
    inside = sorted((n, max(s, lo), min(e, hi)) for n, s, e in ops
                    if min(e, hi) > max(s, lo))
    inside.sort(key=lambda x: x[1])
    # the longest idle gaps, with the operations on either side and the
    # innermost host events (any thread) at their middle
    gaps, edge, before = [], lo, "window start"
    for name, s, e in inside:
        if s > edge:
            gaps.append((s - edge, edge, s, before, name))
        if e > edge:
            edge, before = e, name
    gaps.sort(reverse=True)
    longest = []
    for length, s, e, before, after in gaps[:20]:
        mid = (s + e) / 2
        at = sorted((h for h in host if h[1] <= mid < h[2]),
                    key=lambda h: -h[1])[:4]
        longest.append({"gap_s": length / 1e9, "after_op": before,
                        "before_op": after,
                        "host": [f"{h[3]}: {h[0][:80]}" for h in at]})
    by_op = tracefile.self_times(inside)
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {"planes": planes,
            "top_ops_self_s": [[k, v / 1e9] for k, v in ranked],
            "longest_gaps": longest,
            "spans": {name: sum(1 for s in trace.spans if s[0] == name)
                      for name in sorted({s[0] for s in trace.spans})},
            "reduced": tracefile.reduce(trace, peak)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from chipbench import cell as cell_mod
    from chipbench import device, spec, tracefile

    cell = spec.resolve(args.workload)
    devices = jax.devices()
    peak = device.check(devices, cell.chips, device.load_peaks())
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    out = args.out or tempfile.mkdtemp(prefix="chipbench-probe-")
    try:
        rec = cell_mod.run(cell, args.seed, args.seconds, True,
                           t_start=T_START, devices=devices[:cell.chips],
                           peak=peak, trace_dir=out)
        path = tracefile.find_xplane(out)
        summary = describe(path, peak)
        summary["xplane_bytes"] = os.path.getsize(path)
        summary["dispatched"] = rec["dispatched"]
        print(json.dumps(summary, indent=1, default=str), flush=True)
    finally:
        if args.out is None:
            shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
