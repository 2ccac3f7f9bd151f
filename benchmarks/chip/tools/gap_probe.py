"""One run of a cell exactly as ``run.py`` makes it, keeping its token times.

    python3 benchmarks/chip/tools/gap_probe.py --out gaps/run1.json \\
        --workload stablelm_3b.chat --seed 5 --seconds 51 --trace 0

Every argument but ``--out`` goes to ``run.py`` unchanged, and the run
prints what ``run.py`` prints.  Besides, ``--out`` receives the window's
bounds and, for every request the runner offered, its due time, prompt and
answer lengths and the time of each of its tokens.  From them the token
gaps can be read by the engine tick that made them (the ticks' ends are
the distinct token times), so a tail statistic can be told apart from the
ticks that carry prefill chunks.
"""

from __future__ import annotations

import json
import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TOOLS)
sys.path[:0] = [BENCH_DIR]


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--out")
    out = argv[i + 1]
    del argv[i:i + 2]

    import run
    from chipbench import loop

    kept = {}
    record = loop.Runner.record

    def keep(self):
        rec = record(self)
        kept.update(t0=self.t0, window_end=self.window_end, requests=[
            {"uid": t.plan.uid, "due": t.due, "prompt_len": t.plan.prompt_len,
             "max_new": t.plan.max_new, "stamps": list(t.stamps)}
            for t in self.all])
        return rec

    loop.Runner.record = keep
    rc = run.main(argv)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(kept, f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
