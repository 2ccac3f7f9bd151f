"""Readings behind the limit of ``correct``, on the chip.

    python3 benchmarks/chip/tools/calibrate.py --workload stablelm_3b.chat \\
        --seeds 11,12,13 --seconds 51

For each seed, in one process: one run of the cell exactly as the
benchmark runs it (the same set-up, window and sample), then the reference
and, beside it over the same samples, the float8 control.  Prints one JSON
line per seed: the program's widest served-token gap (a lower reading of
the limit) and the control's (an upper reading).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path[:0] = [BENCH_DIR, os.path.join(REPO_ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    import jax

    from chipbench import cell as cell_mod
    from chipbench import device, spec
    from repro.launch.compile_cache import use_compile_cache

    cell = spec.resolve(args.workload)
    devices = jax.devices()
    peak = device.check(devices, cell.chips, device.load_peaks())
    use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = cell_mod.run(cell, seed, args.seconds, False,
                           t_start=time.monotonic(),
                           devices=devices[:cell.chips], peak=peak,
                           opts=cell_mod.Options(control=True))
        gap = rec["check"]["served_logit_gap"]
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "program": gap["value"],
                          "control": gap.get("control"),
                          "requests": gap["requests"],
                          "tokens": gap["tokens"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
