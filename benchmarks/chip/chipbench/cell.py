"""One run of one cell: set-up, the measured window, the trace, the check.

Set-up (counted in ``setup_s``): the program's model at the configuration's
sizes, the benchmark's weights packed by the program on the device, the
paged engine, one short request that runs both compiled programs (prefill
chunk and decode step), then ``preroll_s`` of the cell's own traffic so the
window opens on a loaded engine.  Nothing compiles inside the window.

After the window the run reads ``memory_peak_bytes``, frees the program's
state and runs the reference over a sample of the finished requests, drawn
from the seed with the longest among them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from chipbench import costs, reference, stats, traffic, weights
from chipbench.loop import Runner
from chipbench.spec import Cell

WARM_PROMPT = 8          # tokens of the warm-up request (one prefill chunk)
WARM_NEW = 3             # its answer: the prefill's token and two decodes
# the profiler starts this long before the window opens, so the device
# tracer is running when the window's first step is dispatched
TRACE_LEAD_S = 1.0


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Options:
    """How a run reaches the program; the defaults are the chip's."""

    backend: str = "pallas"
    # also run the float8 control over the same samples (calibration only;
    # the benchmark's own runs never do)
    control: bool = False
    # a test plants a fault by wrapping the engine before the window
    engine_hook: Optional[Callable] = None


def arch_config(config: dict, layer):
    """The program's ArchConfig for a configuration file, with the layer
    kind's own replacements applied last."""
    import dataclasses as dc

    from repro.configs.base import get_arch
    from repro.core.sparsity import SparsityConfig

    n, m = (int(v) for v in config["sparsity"].split(":"))
    changes = dict(
        num_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        d_ff=int(config["intermediate_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        vocab_size=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        sparsity=SparsityConfig(n, m, 1),
        param_dtype=config["param_dtype"],
        compute_dtype=config["compute_dtype"])
    if "head_dim" in config:
        changes["head_dim"] = int(config["head_dim"])
    changes.update(layer.arch_changes(config))
    return dc.replace(get_arch(config["arch"]), **changes)


def _span_factory(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def build(cell: Cell, seed: int, opts: Options):
    """Model, packed params and engine, on the default device."""
    import jax

    from repro import obs
    from repro.core.sparse_linear import ExecPolicy
    from repro.launch.pack_tree import pack_tree
    from repro.models.families import build_model
    from repro.paged import PagedServeConfig
    from repro.serve import make_engine

    cfg = arch_config(cell.config, cell.layer)
    model = build_model(cfg)
    params = weights.build_served(model, cell.config, cell.layer, seed,
                                  pack_tree)
    jax.block_until_ready(params)
    eng = cell.traffic["engine"]
    serve_cfg = PagedServeConfig(
        num_slots=int(eng["num_slots"]), max_len=int(eng["max_len"]),
        page_size=int(eng["page_size"]), num_pages=int(eng["num_pages"]),
        prefill_chunk=int(eng["prefill_chunk"]))
    engine = make_engine(model, params, serve_cfg,
                         policy=ExecPolicy(mode="packed",
                                           backend=opts.backend),
                         metrics=obs.MetricsRegistry())
    return model, params, engine


def make_request(uid, prompt, max_new):
    from repro.serve import Request

    return Request(uid=int(uid), prompt=prompt, max_new_tokens=int(max_new))


def warm(engine, vocab: int):
    """Run both compiled programs once, on a request of the benchmark's own
    (uid -1), and wait for it."""
    req = make_request(-1, np.arange(WARM_PROMPT, dtype=np.int32) % vocab,
                       WARM_NEW)
    engine.submit(req)
    while req.complete_ts is None:
        engine.step()


def _counters(engine) -> dict:
    snap = engine.metrics.snapshot(meta=False)
    out = {}
    for c in snap["counters"]:
        key = c["name"] + "".join(f"{{{k}={v}}}"
                                  for k, v in sorted(c["labels"].items()))
        out[key] = c["value"]
    return out


def _decode_sketch(engine) -> dict:
    return engine.metrics.sketch(
        "serve_decode_token_seconds_sketch").to_entry()


def sample_finished(runner: Runner, seed: int, k: int):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    done = [t for t in runner.all if t.done and t.plan.uid >= 0]
    if not done:
        return []
    done.sort(key=lambda t: (t.plan.prompt_len + len(t.req.output),
                             t.plan.uid))
    longest = done[-1]
    rest = done[:-1]
    rng = np.random.default_rng(traffic.seed_words(seed) + [7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    chosen = [longest] + [rest[i] for i in sorted(pick)]
    return [reference.Served(uid=t.plan.uid,
                             prompt=traffic.make_prompt(
                                 seed, t.plan.uid, t.plan.prompt_len,
                                 runner.vocab),
                             served=np.asarray(t.req.output, np.int64))
            for t in chosen]


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices, peak: dict, opts: Options = Options(),
        trace_dir: Optional[str] = None) -> dict:
    """One run; returns the record the metric readers read, with the check
    and the device filled in."""
    import jax

    from chipbench import device as device_mod

    mix, config = cell.traffic, cell.config
    dims = weights.layer_dims(config, cell.layer)
    model, params, engine = build(cell, seed, opts)
    warm(engine, dims["vocab"])
    log(f"built and warmed in {time.monotonic() - t_start:.3f}s")
    if opts.engine_hook is not None:
        opts.engine_hook(engine)
    active = cell.layer.active_weights(params, dims)

    pool = traffic.build_pool(mix, seconds, seed)
    runner = Runner(engine, pool, mix, seed, dims["vocab"], make_request,
                    span=_span_factory(trace))
    runner.start(float(mix["preroll_s"]))
    own_dir = None
    if trace:
        runner.preroll(until=runner.t0 - TRACE_LEAD_S)
        if trace_dir is None:
            trace_dir = own_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    runner.preroll()
    setup_s = time.monotonic() - t_start
    c0, sk0 = _counters(engine), _decode_sketch(engine)
    runner.window(seconds)
    c1, sk1 = _counters(engine), _decode_sketch(engine)
    runner.drain(float(mix["drain_limit_s"]))
    jax.block_until_ready(engine.state)
    reduced = None
    if trace:
        from chipbench import tracefile

        jax.profiler.stop_trace()
        t_read = time.monotonic()
        reduced = tracefile.reduce(tracefile.load(
            tracefile.find_xplane(trace_dir)), peak)
        log(f"trace read in {time.monotonic() - t_read:.3f}s: "
            f"{reduced['kernel_events']} kernel events, "
            f"{reduced['gaps']} idle gaps")
        if own_dir is not None:
            shutil.rmtree(own_dir, ignore_errors=True)
    rec = runner.record()
    memory_peak = device_mod.memory_peak(devices)

    dispatched = {p: c1.get(f"serve_step_dispatch_total{{program={p}}}", 0)
                  - c0.get(f"serve_step_dispatch_total{{program={p}}}", 0)
                  for p in ("decode", "prefill")}
    rec.update({
        "setup_s": setup_s, "seed": seed, "peak": peak, "dims": dims,
        "counters_window": {k: c1[k] - c0.get(k, 0) for k in c1},
        "dispatched": dispatched,
        "decode_step_sketch": stats.sketch_window(sk0, sk1),
        "trace": reduced,
        "gen_flops_window": costs.token_flops(active, dims,
                                              rec["gen_ctx"]),
        "prompt_flops_window": costs.prompt_flops(
            active, dims, rec["prompt_tokens_window"],
            rec["prompt_ctx_window"], rec["prefills_window"]),
        "memory_peak_bytes": memory_peak,
    })
    _log_window(rec)

    samples = sample_finished(runner, seed,
                              int(mix["check"]["sample_requests"]))
    del runner, engine, params, model
    gc.collect()
    rec["check"] = check(cell, seed, samples, control=opts.control)
    rec["samples"] = samples
    return rec


def check(cell: Cell, seed: int, samples, control: bool = False) -> dict:
    """The numbers ``correct`` compares, each with its limit."""
    limit = float(cell.limits["served_logit_gap"]["limit"])
    if not samples:
        return {"served_logit_gap": {"value": math.inf, "limit": limit,
                                     "requests": 0, "tokens": 0}}
    t = time.monotonic()
    got = reference.gaps(cell.config, cell.layer, seed, samples,
                         cell.traffic["check"]["buckets"], control=control)
    widest = reference.Gaps.widest(got.served)
    log(f"reference over {len(samples)} requests "
        f"({sum(len(s.served) for s in samples)} served tokens, longest "
        f"{max(s.length for s in samples)}) in {time.monotonic() - t:.3f}s")
    out = {"served_logit_gap": {
        "value": widest, "limit": limit, "requests": len(samples),
        "tokens": int(sum(len(s.served) for s in samples))}}
    if control:
        out["served_logit_gap"]["control"] = reference.Gaps.widest(
            got.control)
    return out


def _log_window(rec: dict):
    reqs = rec["requests"]
    ttft = [r["first_token"] - r["due"] for r in reqs
            if r["first_token"] is not None]
    lag = [r["submitted"] - r["due"] for r in reqs]
    p95 = stats.percentile(ttft, 95)
    log(f"window {rec['window_s']:.3f}s: {len(reqs)} requests counted, "
        f"{sum(r['first_token'] is None for r in reqs)} without a first "
        f"token, {rec['steps']} engine steps, "
        f"{rec['gen_tokens_window']} decoded tokens, "
        f"{rec['prompt_tokens_window']} prompt tokens, "
        f"dispatches {rec['dispatched']}, "
        f"preempted {sum(r['preempts'] > 0 for r in reqs)}")
    if ttft:
        log(f"ttft_p95_ms={1000 * p95:.4f} over {len(ttft)} requests "
            f"(information only); generator lag p50 "
            f"{1000 * stats.percentile(lag, 50):.3f} ms, max "
            f"{1000 * max(lag):.3f} ms")


def correct(rec: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in rec["check"].values())
