"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload stablelm_3b.chat \\
        --seed 1234 --seconds 51 --trace 0

Runs from the root of a checkout on a machine that holds the chips the cell
asks for (``BENCHMARK.json``).  It refuses to run (exit 2, no result) unless
JAX's default backend is a TPU whose ``device_kind`` is in ``peaks.json``.
It keeps JAX's compilation cache at ``<checkout>/.jax_cache`` (or where
``JAX_COMPILATION_CACHE_DIR`` says), builds the weights from ``--seed``,
warms both compiled programs, offers the cell's traffic for
``--seconds``, and prints one JSON line last on standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``check``: each
number compared with its limit, which also close standard error.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def _paths():
    for p in (BENCH_DIR, os.path.join(REPO_ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def result_line(cell, rec: dict, trace: bool, devices_desc: dict) -> dict:
    from chipbench import cell as cell_mod

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(rec)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    line = {
        "correct": cell_mod.correct(rec),
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": metrics,
        "device": {**devices_desc,
                   "memory_peak_bytes": rec["memory_peak_bytes"]},
    }
    if trace and rec["trace"] is not None:
        line["device"]["busy_s"] = rec["trace"]["busy_s"]
        line["device"]["window_s"] = rec["trace"]["window_s"]
        line["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                             "idle_gaps": rec["trace"]["idle_gaps"]}
    line["check"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in rec["check"].items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    _paths()
    from chipbench import cell as cell_mod
    from chipbench import device, spec

    try:
        cell = spec.resolve(args.workload)
    except spec.SpecError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    try:
        peak = device.check(devices, cell.chips, device.load_peaks())
    except device.DeviceError as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    desc = device.describe(devices)
    cell_mod.log(f"device platform={desc['platform']} kind={desc['kind']} "
                 f"count={desc['count']}")

    from repro.launch.compile_cache import use_compile_cache

    cell_mod.log(f"compile cache: {use_compile_cache()}")
    rec = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START, devices=devices, peak=peak)
    line = result_line(cell, rec, bool(args.trace), desc)
    for name, v in line["check"].items():
        cell_mod.log(f"check {name}={v['value']!r} limit={v['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
