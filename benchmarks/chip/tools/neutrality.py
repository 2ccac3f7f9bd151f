"""Read one traced run's inputs with two versions of the benchmark's code.

Open-loop timing changes which requests finish, so two runs cannot be
compared number for number.  Instead one run is kept, and each version of
the benchmark's code reads the same kept inputs:

    # on the chip: one traced run; keeps its sampled finished requests,
    # its window's work inputs and its trace under --out
    python3 benchmarks/chip/tools/neutrality.py run \\
        --workload stablelm_3b.chat --seed 5 --seconds 51 --out n/chat
    # each version reads them (the reference runs on the default device)
    python3 benchmarks/chip/tools/neutrality.py read --inputs n/chat \\
        --bench-dir <checkout>/benchmarks/chip --out n/chat/<version>.json
    python3 benchmarks/chip/tools/neutrality.py compare a.json b.json

``read`` computes, with the code under ``--bench-dir`` (older code that
predates layer kinds included): the reference's served-token gaps over the
kept requests, the window's decode and prompt operations from the kept
counts and that code's per-token weight count (read from the served tree's
shapes, with ``jax.eval_shape``), and the trace reduction's kernel time,
least kernel time and device ops.  ``compare`` prints which are equal and
exits 1 where one is not.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

T_START = time.monotonic()
HERE_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPARED = ("gaps_sha256", "served_logit_gap", "active_weights",
            "gen_flops_window", "prompt_flops_window", "kernel_s",
            "kernel_least_s", "device_ops")


def _use(bench_dir: str):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(bench_dir)))
    sys.path[:0] = [os.path.abspath(bench_dir), os.path.join(repo, "src")]


def run(args) -> int:
    _use(HERE_BENCH)
    import jax

    import run as run_mod
    from chipbench import cell as cell_mod
    from chipbench import device, spec
    from repro.launch.compile_cache import use_compile_cache

    cell = spec.resolve(args.workload)
    devices = jax.devices()
    peak = device.check(devices, cell.chips, device.load_peaks())
    devices = devices[:cell.chips]
    cell_mod.log(f"compile cache: {use_compile_cache()}")
    trace_dir = os.path.join(args.out, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    rec = cell_mod.run(cell, args.seed, args.seconds, True,
                       t_start=T_START, devices=devices, peak=peak,
                       trace_dir=trace_dir)
    line = run_mod.result_line(cell, rec, True, device.describe(devices))
    inputs = {
        "workload": cell.name, "seed": args.seed, "peak": peak,
        "samples": [{"uid": s.uid, "prompt": s.prompt.tolist(),
                     "served": s.served.tolist()} for s in rec["samples"]],
        "gen_ctx": rec["gen_ctx"].tolist(),
        "prompt_tokens_window": rec["prompt_tokens_window"],
        "prompt_ctx_window": rec["prompt_ctx_window"],
        "prefills_window": rec["prefills_window"],
        "line": line}
    with open(os.path.join(args.out, "inputs.json"), "w") as f:
        json.dump(inputs, f)
    print(json.dumps(line), flush=True)
    return 0


def _weights_per_token(spec, weights, costs, cell_mod, cell):
    """This code's per-token weight count and sizes, from shapes alone."""
    import jax

    from repro.launch.pack_tree import pack_tree
    from repro.models.families import build_model

    if hasattr(spec, "layer_of"):
        layer = cell.layer
        dims = weights.layer_dims(cell.config, layer)
        model = build_model(cell_mod.arch_config(cell.config, layer))
        build = weights.served_builder(model, cell.config, layer, pack_tree)
        shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
        return layer.active_weights(shapes, dims), dims
    dims = weights.dims_of(cell.config)
    model = build_model(cell_mod.arch_config(cell.config))
    build = weights.served_builder(model, cell.config, pack_tree)
    return costs.kept_weights(jax.eval_shape(build, jax.random.PRNGKey(0))), \
        dims


def read(args) -> int:
    _use(args.bench_dir)
    import numpy as np

    from chipbench import cell as cell_mod
    from chipbench import costs, reference, spec, tracefile, weights

    with open(os.path.join(args.inputs, "inputs.json")) as f:
        inputs = json.load(f)
    cell = spec.resolve(inputs["workload"])
    samples = [reference.Served(uid=s["uid"],
                                prompt=np.asarray(s["prompt"], np.int32),
                                served=np.asarray(s["served"], np.int64))
               for s in inputs["samples"]]
    buckets = cell.traffic["check"]["buckets"]
    t = time.monotonic()
    if hasattr(spec, "layer_of"):
        got = reference.gaps(cell.config, cell.layer, inputs["seed"],
                             samples, buckets)
    else:
        got = reference.gaps(cell.config, inputs["seed"], samples, buckets)
    ref_s = time.monotonic() - t
    h = hashlib.sha256()
    for g in got.served:
        h.update(np.asarray(g).tobytes())
    active, dims = _weights_per_token(spec, weights, costs, cell_mod, cell)
    t = time.monotonic()
    reduced = tracefile.reduce(tracefile.load(tracefile.find_xplane(
        os.path.join(args.inputs, "trace"))), inputs["peak"])
    out = {
        "bench_dir": os.path.abspath(args.bench_dir),
        "gaps_sha256": h.hexdigest(),
        "served_logit_gap": reference.Gaps.widest(got.served),
        "widest_per_request": [float(np.max(g)) for g in got.served],
        "tokens": int(sum(len(s.served) for s in samples)),
        "active_weights": int(active),
        "gen_flops_window": costs.token_flops(
            active, dims, np.asarray(inputs["gen_ctx"], np.int64)),
        "prompt_flops_window": costs.prompt_flops(
            active, dims, inputs["prompt_tokens_window"],
            inputs["prompt_ctx_window"], inputs["prefills_window"]),
        "kernel_s": reduced["kernel_s"],
        "kernel_least_s": reduced["kernel_least_s"],
        "kernel_events": reduced["kernel_events"],
        "device_ops": reduced["device_ops"],
        "kernels": reduced.get("kernels"),
        "reference_s": ref_s, "trace_read_s": time.monotonic() - t}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


def compare(args) -> int:
    with open(args.a) as f:
        a = json.load(f)
    with open(args.b) as f:
        b = json.load(f)
    same = {k: a[k] == b[k] for k in COMPARED}
    print(json.dumps({"same": same, "all_same": all(same.values())}),
          flush=True)
    return 0 if all(same.values()) else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--seconds", type=float, default=51.0)
    r.add_argument("--out", required=True)
    d = sub.add_parser("read")
    d.add_argument("--inputs", required=True)
    d.add_argument("--bench-dir", required=True)
    d.add_argument("--out", required=True)
    c = sub.add_parser("compare")
    c.add_argument("a")
    c.add_argument("b")
    args = ap.parse_args(argv)
    return {"run": run, "read": read, "compare": compare}[args.mode](args)


if __name__ == "__main__":
    sys.exit(main())
