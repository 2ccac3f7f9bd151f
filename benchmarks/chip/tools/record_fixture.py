"""Record the small chip trace the trace-reduction test reads.

    python3 benchmarks/chip/tools/record_fixture.py --out <dir>

On one chip: two packed ``demm_xwT`` kernel calls at stablelm_3b's MLP
width (decode batch 16, 5:80 as the benchmark serves K = 2560) and one
plain XLA operation, each in a ``bench.step`` span, with a ``bench.wait``
sleep between them, all inside a ``bench.window`` span.  Writes
``kernel_trace.xplane.pb`` and ``kernel_trace.json`` (the calls' shapes
and dtypes, and the reduction of the trace as recorded) to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path[:0] = [BENCH_DIR, os.path.join(REPO_ROOT, "src")]

ROWS, O, K, N, M = 16, 6912, 2560, 5, 80


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import device, tracefile
    from repro.core.sparsity import SparsityConfig
    from repro.kernels.demm_spmm import demm_xwT_pallas

    peak = device.check(jax.devices(), 1, device.load_peaks())
    rng = np.random.default_rng(0)
    cfg = SparsityConfig(N, M)
    g = K // M
    x = jnp.asarray(rng.standard_normal((ROWS, K)), jnp.bfloat16)
    values = jnp.asarray(rng.standard_normal((g, N, O)), jnp.float32)
    idx = np.sort(np.argsort(rng.random((g, O, M)), axis=-1)[..., :N], -1)
    indices = jnp.asarray(np.transpose(idx, (0, 2, 1)), jnp.int32)
    kernel = jax.jit(lambda a, v, i: demm_xwT_pallas(a, v, i, cfg))
    other = jax.jit(lambda a: jnp.tanh(a @ a.T))
    big = jnp.asarray(rng.standard_normal((2048, 2048)), jnp.float32)
    jax.block_until_ready((kernel(x, values, indices), other(big)))

    tmp = tempfile.mkdtemp(prefix="chipbench-fixture-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    time.sleep(0.2)          # let the device tracer start
    # waits of 20 ms around every step: the device's clock in the trace is
    # off the host's by about a millisecond
    with jax.profiler.TraceAnnotation("bench.window"):
        for step in (0, 1, 2):
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(kernel(x, values, indices) if step < 2
                                      else other(big))
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.02)
    jax.profiler.stop_trace()

    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "kernel_trace.xplane.pb")
    shutil.copy(tracefile.find_xplane(tmp), dst)
    shutil.rmtree(tmp, ignore_errors=True)
    meta = {"kernel_calls": 2, "rows": ROWS, "out": O, "in": K, "n": N,
            "m": M, "x_bytes": 2, "value_bytes": 4, "index_bytes": 4,
            "y_bytes": 4,
            "device": device.describe(jax.devices()[:1]),
            "reduced": tracefile.reduce(tracefile.load(dst), peak)}
    with open(os.path.join(args.out, "kernel_trace.json"), "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta), flush=True)
    print(f"xplane bytes {os.path.getsize(dst)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
