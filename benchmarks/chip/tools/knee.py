"""Find an open-loop cell's knee once, by a sweep of fixed rates on the chip.

    python3 benchmarks/chip/tools/knee.py --workload stablelm_3b.chat \\
        --rates 0.6,0.9,1.2,1.5 --seconds 40 --seed 21

For each rate, in one process, one run of the cell at that rate in place
of its mix's.  Prints one JSON line per rate: requests offered and
answered, TTFT median of the first and second half of the window (a
backlog that grows shows as a second half far above the first) and the
decoded tokens per second.
The knee is the highest rate whose backlog does not grow; the cell's
rate is set at about four fifths of it.
"""

from __future__ import annotations

import argparse
import dataclasses
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)

    import jax

    from chipbench import cell as cell_mod
    from chipbench import device, spec, stats
    from repro.launch.compile_cache import use_compile_cache

    cell = spec.resolve(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("the knee is swept for an open loop only")
    devices = jax.devices()
    peak = device.check(devices, cell.chips, device.load_peaks())
    use_compile_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        at = dataclasses.replace(cell, traffic={**cell.traffic,
                                                "rate_per_s": rate})
        rec = cell_mod.run(at, args.seed, args.seconds, False,
                           t_start=time.monotonic(),
                           devices=devices[:cell.chips], peak=peak)
        half = rec["t0"] + rec["window_s"] / 2
        reqs = rec["requests"]

        def ttft(rs):
            v = [r["first_token"] - r["due"] for r in rs
                 if r["first_token"] is not None]
            p = stats.percentile(v, 50)
            return None if p is None else 1000 * p

        print(json.dumps({
            "rate": rate, "offered": len(reqs), "failed": rec["failed"],
            "ttft_p50_ms_first_half": ttft([r for r in reqs
                                            if r["due"] < half]),
            "ttft_p50_ms_second_half": ttft([r for r in reqs
                                             if r["due"] >= half]),
            "decoded_tokens_per_s": rec["gen_tokens_window"]
            / rec["window_s"],
            "steps": rec["steps"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
