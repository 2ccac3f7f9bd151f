"""Batched serving driver: a reduced config by default, the published
widths with ``--full`` (on a TPU chip; ``chip_smoke.py`` drives that path).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3_1b --requests 8 \
        --packed --layout block --quantize int8 --backend auto --autotune

``--packed`` converts every sparse weight to the paper's packed DeMM form
before serving: the decode matmuls then stream only packed bytes.
``--quantize int8`` additionally quantizes the packed values to symmetric
int8 (``repro.quant``) — the decode matmuls then stream int8 bytes and
dequantize in-register (w8a16 kernels); ``--quantize-granularity
per_group`` refines the xwT scales from per-row to per-(row, group).
``--backend auto`` resolves every packed matmul through the ``repro.tune``
registry + cache; ``--autotune`` pre-measures tile configs for the decode
shapes first (results persist in the tuning cache for later runs).

``--paged`` swaps the legacy dense-cache loop for the paged serving engine
(``repro.paged``, DESIGN.md §13): a shared paged KV arena sized by
``--page-size``/``--max-pages``, chunked prefill (``--prefill-chunk``
tokens per dispatch), and a ``--scheduler fcfs|priority`` admission/
preemption policy; ``--trace-replay trace.jsonl`` replays a
``benchmarks/serve_bench.py`` trace at its logical arrival ticks, with
prompt tokens derived deterministically from ``(--seed, uid)``.

``--spec-draft N:M`` turns on self-speculative decoding (``repro.spec``,
DESIGN.md §15): ``--spec-gamma`` tokens per window are drafted with the
sparser-tier view of the same packed buffers and verified in one batched
full-tier dispatch; ``--temperature``/``--top-k`` select replay-safe
coupled sampling (token streams are identical with and without
speculation, preemption included).

Without ``--ckpt-dir``, ``--packed`` builds the packed tree in one jitted
init-and-pack program (``launch.pack_tree.init_packed``): the dense float32
tree of a full-width model never exists whole on the device.

``--ckpt-dir`` restores trained params from a ``launch/train.py``
checkpoint before packing — the serve half of the dense → prune →
train/QAT → pack → serve pipeline (a ``--sparsify`` run's final checkpoint
has its masks baked in, so it packs losslessly).

Observability (``repro.obs``, DESIGN.md §12): ``--metrics-out m.json``
writes the process-wide metrics snapshot after the drain (request/token
counters, queue-wait/decode-latency histograms, kernel-dispatch and
tune-cache counters; a ``.prom`` suffix selects Prometheus text
exposition), ``--trace-out t.jsonl`` dumps the JSONL event trace, and
``--profile-dir d/`` wraps serving in a jax profiler trace for
TensorBoard/perfetto with every DeMM kernel named via ``obs.annotate``.
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro import obs
from repro.configs.base import ARCH_IDS, get_arch
from repro.core.sparse_linear import ExecPolicy
from repro.launch.compile_cache import use_compile_cache
from repro.launch.pack_tree import init_packed, pack_tree
from repro.models.families import build_model
from repro.serve import Request, ServeConfig, make_engine


def _load_trace(path: str):
    """benchmarks/serve_bench.py trace format: JSONL rows of
    {uid, arrival_tick, prompt_len, max_new[, priority]}."""
    import json

    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line and not line.startswith("#"):
                rows.append(json.loads(line))
    return sorted(rows, key=lambda r: (r["arrival_tick"], r["uid"]))


def _trace_prompt(seed: int, uid: int, length: int, vocab: int):
    """Per-request deterministic prompt, replayable from (seed, uid) —
    matches benchmarks/serve_bench.py so replays are comparable."""
    return np.random.default_rng((seed, uid)).integers(
        0, vocab, length, dtype=np.int32)


def run_serve(model, params, vocab_size: int, *, packed: bool = True,
              layout: str = "xwT", quantize=None,
              granularity: str = "per_row", backend: str = "reference",
              autotune: bool = False, requests: int = 8, slots: int = 4,
              max_new: int = 16, max_len: int = 128, seed: int = 0,
              paged: bool = False, page_size: int = 16, max_pages=None,
              prefill_chunk: int = 32, scheduler: str = "fcfs",
              trace_replay=None, plan=None, replicas: int = 1,
              spec_draft=None, spec_gamma: int = 4,
              temperature: float = 0.0, top_k: int = 0, recorder=None,
              sampler=None):
    """Pack (optionally) and serve ``requests`` random prompts; returns the
    drained engine.  The reusable core of ``main()`` — the end-to-end
    examples call this directly with their own trained params.

    ``paged=True`` serves through :class:`repro.paged.PagedServeEngine`
    (shared KV arena + chunked prefill + scheduled admission) instead of the
    legacy dense-cache loop; ``trace_replay`` submits a serve_bench-format
    JSONL trace at its logical arrival ticks instead of ``requests`` random
    prompts (prompt tokens derived from ``(seed, uid)`` either way).

    ``plan`` (a :class:`~repro.sharding.plan.ShardingPlan`) distributes the
    engine: TP renumbers + shards packed weights over the mesh, PP runs the
    microbatched pipelined decode step.  ``replicas`` > 1 serves through a
    data-parallel :class:`~repro.serve.ReplicaRouter` — N engines over one
    shared params tree, round-robin admission, merged metrics.

    ``spec_draft`` ("N:M") turns on self-speculative decoding
    (``repro.spec``, DESIGN.md §15): draft ``spec_gamma`` tokens per window
    at the sparser tier of the same packed buffers, verify in one batched
    full-tier dispatch.  ``temperature``/``top_k`` select replay-safe
    coupled sampling (0 = greedy); the token stream is identical with and
    without speculation.

    ``sampler`` (an object with ``sample(logits, uid, pos) -> int``)
    replaces each engine's token sampler, e.g. to record the logits the
    engine produced or to force a given token stream.
    """
    spec = None
    if spec_draft is not None:
        from repro.spec import SpecConfig
        if not packed:
            raise ValueError(
                "--spec-draft requires --packed: the draft tier is a view "
                "of the packed weight buffers")
        spec = SpecConfig(draft=spec_draft, gamma=spec_gamma)
    mode = "masked"
    if packed:
        params = pack_tree(params, layout=layout, quantize=quantize,
                           granularity=granularity)
        mode = "packed"
    policy = ExecPolicy(mode=mode, backend=backend, plan=plan)
    if paged:
        from repro.paged import PagedServeConfig, SchedConfig
        serve_cfg = PagedServeConfig(
            num_slots=slots, max_len=max_len, page_size=page_size,
            num_pages=max_pages, prefill_chunk=prefill_chunk,
            temperature=temperature, top_k=top_k, seed=seed,
            sched=SchedConfig(policy=scheduler))
    else:
        serve_cfg = ServeConfig(num_slots=slots, max_len=max_len,
                                temperature=temperature, top_k=top_k,
                                seed=seed)
    engine = make_engine(model, params, serve_cfg, policy=policy,
                         autotune=autotune and packed, replicas=replicas,
                         spec=spec, recorder=recorder)
    if sampler is not None:
        for e in getattr(engine, "replicas", [engine]):
            e.sampler = sampler
    if trace_replay:
        rows = _load_trace(trace_replay)
        t0 = time.time()
        tick, i = 0, 0
        while i < len(rows):
            while i < len(rows) and rows[i]["arrival_tick"] <= tick:
                r = rows[i]
                engine.submit(Request(
                    uid=r["uid"],
                    prompt=_trace_prompt(seed, r["uid"], r["prompt_len"],
                                         vocab_size),
                    max_new_tokens=r["max_new"],
                    priority=r.get("priority", 1)))
                i += 1
            engine.step()
            tick += 1
        engine.run_until_drained()
    else:
        rng = np.random.default_rng(seed)
        for i in range(requests):
            prompt = rng.integers(0, vocab_size, rng.integers(4, 12),
                                  dtype=np.int32)
            engine.submit(Request(uid=i, prompt=prompt,
                                  max_new_tokens=max_new))
        t0 = time.time()
        engine.run_until_drained()
    # decode-only wall time (packing / engine build / autotune excluded),
    # so reported tok/s stays comparable across runs and releases
    engine.drain_seconds = time.time() - t0
    return engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm_3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0,
                    help="request sampling seed (prompt tokens; trace "
                         "replays derive each prompt from (seed, uid))")
    ap.add_argument("--paged", action="store_true",
                    help="serve through repro.paged.PagedServeEngine: "
                         "shared paged KV arena + chunked prefill + "
                         "scheduled admission/preemption (full-attention "
                         "archs only; DESIGN.md §13)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="--paged: tokens per KV arena page")
    ap.add_argument("--max-pages", type=int, default=None,
                    help="--paged: arena pages incl. the reserved null page "
                         "(default: fully provisioned for num_slots; "
                         "undersize to exercise preemption)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="--paged: prompt tokens per prefill dispatch")
    ap.add_argument("--scheduler", choices=("fcfs", "priority"),
                    default="fcfs",
                    help="--paged: admission policy (priority preempts "
                         "lower-priority requests for higher ones)")
    ap.add_argument("--trace-replay", default=None, metavar="JSONL",
                    help="replay this serve_bench-format trace ({uid, "
                         "arrival_tick, prompt_len, max_new, priority} "
                         "rows) at its logical ticks instead of --requests "
                         "random prompts")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard packed weights over "
                         "a 'model' mesh axis (row-parallel block/xwT "
                         "weights are renumbered per shard); needs tp "
                         "visible devices — on CPU force them with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel degree: split the layer stack "
                         "into pp stages and run the microbatched pipelined "
                         "decode step (non-paged engine only)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind a "
                         "round-robin router sharing one params tree; "
                         "metrics are merged with a replica=<i> label")
    ap.add_argument("--spec-draft", default=None, metavar="N:M",
                    help="self-speculative decoding (repro.spec, DESIGN.md "
                         "§15): draft at this sparser tier of the packed "
                         "buffers (e.g. 8:128 on a 16:128-packed tree), "
                         "verify windows in one batched full-tier dispatch; "
                         "requires --packed")
    ap.add_argument("--spec-gamma", type=int, default=4,
                    help="tokens drafted per speculation window")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy); sampling is "
                         "replay-safe — randomness is keyed on (seed, "
                         "request, position), so preempt/resume and "
                         "speculative runs commit identical streams")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k mask for temperature sampling (0 = full "
                         "vocab)")
    ap.add_argument("--sparsity", default=None, metavar="N:M",
                    help="override the arch's N:M sparsity pattern before "
                         "init/packing (e.g. 8:16 to leave k-reconfigurable "
                         "headroom for --spec-draft 4:16)")
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--layout", choices=("xwT", "block"), default="xwT",
                    help="packed-weight layout for --packed: the row-packed "
                         "xwT stream or the two-level block format "
                         "(pack_block; dispatches the block-spmm kernel)")
    ap.add_argument("--quantize", choices=("int8",), default=None,
                    help="quantize the packed values (repro.quant): int8 "
                         "symmetric with traced scales, served by the "
                         "w8a16 xwT_q8/xwT_block_q8 kernels")
    ap.add_argument("--quantize-granularity",
                    choices=("per_row", "per_group"), default="per_row",
                    help="xwT scale unit for --quantize (block is always "
                         "per row-block × group × row)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore trained params from this launch/train.py "
                         "checkpoint directory before packing (--packed "
                         "then serves the trained sparse model)")
    ap.add_argument("--ckpt-step", type=int, default=None,
                    help="checkpoint step to restore (default: latest)")
    ap.add_argument("--full", action="store_true",
                    help="serve the full (non-reduced) config — match this "
                         "to how the checkpoint was trained")
    # valid backends come from the registry, so variants added via
    # repro.tune.register_variant are immediately servable
    from repro import tune
    ap.add_argument("--backend", default="reference",
                    choices=tuple(sorted(
                        {v.name for op in
                         ("xwT", "xwT_block", "xwT_q8", "xwT_block_q8")
                         for v in tune.variants_for(op)}))
                    + ("auto",))
    ap.add_argument("--autotune", action="store_true",
                    help="pre-measure tile configs for the packed decode "
                         "shapes (implies --backend auto)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics snapshot here after the drain "
                         "(.prom/.txt => Prometheus text exposition, "
                         "anything else => JSON)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the JSONL event trace (request lifecycle "
                         "spans/events, autotune measurements) here")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax profiler trace of the serve run "
                         "into this directory (TensorBoard/perfetto)")
    ap.add_argument("--slo-report", action="store_true",
                    help="print the SLO / goodput / phase-latency report "
                         "after the drain (repro.obs.slo, DESIGN.md §16); "
                         "implied by --slo-ttft-ms/--slo-e2e-ms")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="time-to-first-token deadline in ms; completed "
                         "requests are judged pass/fail against it")
    ap.add_argument("--slo-e2e-ms", type=float, default=None,
                    help="end-to-end (submit -> complete) deadline in ms")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="attach a flight recorder (repro.obs, DESIGN.md "
                         "§16): bounded per-subsystem event rings + a "
                         "per-engine tick stall watchdog; stalls, crashes, "
                         "and SIGTERM dump rings+metrics+metadata here")
    ap.add_argument("--watchdog-threshold", type=float, default=8.0,
                    help="--flight-dir: declare a stall when tick silence "
                         "exceeds this multiple of the EWMA tick interval "
                         "(floored at 1s)")
    ap.add_argument("--force-stall", action="store_true",
                    help="--flight-dir: after the drain, stop beating the "
                         "watchdog and wait for it to trip (CI leg that "
                         "proves the stall->dump path); exits nonzero if no "
                         "dump appears")
    args = ap.parse_args()
    use_compile_cache()
    if args.autotune:
        args.backend = "auto"
    if args.tp < 1 or args.pp < 1 or args.replicas < 1:
        ap.error("--tp/--pp/--replicas must be >= 1")
    if args.pp > 1 and args.paged:
        ap.error("--pp applies to the non-paged engine (pipelined decode "
                 "over dense caches); drop --paged or --pp")
    plan = None
    if args.tp > 1 or args.pp > 1 or args.replicas > 1:
        from repro.sharding.plan import ShardingPlan
        plan = ShardingPlan(tp=args.tp, pp=args.pp, dp=args.replicas)
        need = args.tp * args.pp
        if need > jax.device_count():
            ap.error(
                f"--tp {args.tp} --pp {args.pp} needs {need} devices but "
                f"only {jax.device_count()} are visible; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    if args.quantize and not args.packed:
        ap.error("--quantize applies to the packed serving form; add "
                 "--packed")
    if args.packed and args.backend != "auto":
        # fail invalid layout/backend pairs here, not deep inside the first
        # jitted decode step
        op = "xwT_block" if args.layout == "block" else "xwT"
        if args.quantize:
            op += "_q8"
        valid = {v.name for v in tune.variants_for(op)}
        if args.backend not in valid:
            ap.error(f"--backend {args.backend} is not a registered {op} "
                     f"variant for --layout {args.layout}"
                     + (f" --quantize {args.quantize}" if args.quantize
                        else "")
                     + f" (valid: {sorted(valid)} or 'auto')")

    if args.spec_draft and not args.packed:
        ap.error("--spec-draft requires --packed (the draft tier is a view "
                 "of the packed weight buffers)")
    if args.force_stall and not args.flight_dir:
        ap.error("--force-stall needs --flight-dir (there is no watchdog "
                 "to trip without a flight recorder)")

    log = obs.get_logger("launch.serve")
    recorder = None
    if args.flight_dir:
        recorder = obs.FlightRecorder(
            args.flight_dir, watchdog_threshold=args.watchdog_threshold)
        recorder.install_signal_handlers()
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if args.sparsity:
        import dataclasses as _dc
        from repro.core.sparsity import SparsityConfig
        from repro.spec.tiers import parse_tier
        n, m = parse_tier(args.sparsity)
        cfg = _dc.replace(cfg, sparsity=SparsityConfig(n, m, 1))
    model = build_model(cfg)
    if args.packed and not args.ckpt_dir:
        params = init_packed(model, jax.random.PRNGKey(0),
                             layout=args.layout, quantize=args.quantize,
                             granularity=args.quantize_granularity)
    else:
        params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        from repro.train import checkpoint as ckpt

        step = (args.ckpt_step if args.ckpt_step is not None
                else ckpt.latest_step(args.ckpt_dir))
        if step is None:
            ap.error(f"--ckpt-dir {args.ckpt_dir} holds no checkpoints")
        try:
            restored = ckpt.restore({"params": params}, args.ckpt_dir,
                                    step)["params"]
        except KeyError as e:
            ap.error(
                f"checkpoint {args.ckpt_dir} step {step} is missing leaf "
                f"{e} of the {cfg.name} param tree — was it trained with a "
                "different --arch?")
        # checkpoint.restore trusts the manifest's shapes; fail here with a
        # pointer at the config mismatch instead of deep inside a matmul
        mismatch = [
            f"  {jax.tree_util.keystr(path)}: checkpoint "
            f"{tuple(b.shape)} vs model {tuple(a.shape)}"
            for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                jax.tree.leaves(restored))
            if hasattr(a, "shape") and tuple(a.shape) != tuple(b.shape)]
        if mismatch:
            ap.error(
                f"checkpoint {args.ckpt_dir} step {step} does not fit the "
                f"{'full' if args.full else 'reduced'} {cfg.name} config "
                "(was it trained with the other of --full/--reduced, or a "
                "different --arch?):\n" + "\n".join(mismatch[:8]))
        params = restored
        log.info("restored params", ckpt_dir=args.ckpt_dir, step=step)

    profile_ctx = (obs.profile(args.profile_dir) if args.profile_dir
                   else contextlib.nullcontext())
    guard_ctx = (recorder.guard() if recorder is not None
                 else contextlib.nullcontext())
    with profile_ctx, guard_ctx:
        engine = run_serve(model, params, cfg.vocab_size, packed=args.packed,
                           layout=args.layout, quantize=args.quantize,
                           granularity=args.quantize_granularity,
                           backend=args.backend, autotune=args.autotune,
                           requests=args.requests, slots=args.slots,
                           max_new=args.max_new, max_len=args.max_len,
                           seed=args.seed, paged=args.paged,
                           page_size=args.page_size,
                           max_pages=args.max_pages,
                           prefill_chunk=args.prefill_chunk,
                           scheduler=args.scheduler,
                           trace_replay=args.trace_replay,
                           plan=plan, replicas=args.replicas,
                           spec_draft=args.spec_draft,
                           spec_gamma=args.spec_gamma,
                           temperature=args.temperature, top_k=args.top_k,
                           recorder=recorder)
    dt = engine.drain_seconds
    mode = "packed" if args.packed else "masked"
    total_tokens = sum(len(r.output) for r in engine.completed)
    tag = mode if not args.quantize else f"{mode}+{args.quantize}"
    if args.paged:
        tag += "+paged"
    if args.spec_draft:
        tag += f"+spec{args.spec_draft}"
    if plan is not None:
        tag += f"+tp{args.tp}" if args.tp > 1 else ""
        tag += f"+pp{args.pp}" if args.pp > 1 else ""
        tag += f"+dp{args.replicas}" if args.replicas > 1 else ""
    log.info("served", requests=len(engine.completed), tokens=total_tokens,
             seconds=round(dt, 3),
             tok_s=round(total_tokens / max(dt, 1e-9), 1), mode=tag)
    sm = getattr(engine, "_spec_metrics", None)
    if sm is not None and sm.drafted.value:
        log.info("speculation",
                 drafted=sm.drafted.value, accepted=sm.accepted.value,
                 acceptance=round(sm.accepted.value / sm.drafted.value, 3),
                 tokens_per_dispatch=round(
                     sm._committed_total / max(sm._verify_dispatches, 1), 3))
    for r in engine.completed[:3]:
        log.info(f"  req {r.uid}: prompt[:4]={r.prompt[:4].tolist()} "
                 f"-> {r.output[:8]}")
    slo_cfg = obs.SLOConfig(ttft_ms=args.slo_ttft_ms, e2e_ms=args.slo_e2e_ms)
    if args.slo_report or slo_cfg.enabled():
        import json as _json
        # the DP router's merged facade has no instruments of its own;
        # publish verdicts only on a real registry
        reg = engine.metrics if hasattr(engine.metrics, "gauge") else None
        report = obs.slo_report(engine.completed, slo_cfg, metrics=reg)
        log.info("slo report\n" + _json.dumps(report, indent=2))
    if args.metrics_out:
        engine.metrics.write(args.metrics_out)
        log.info("wrote metrics snapshot", path=args.metrics_out)
    if args.trace_out:
        engine.metrics.trace.write(args.trace_out)
        log.info("wrote event trace", path=args.trace_out)
    if args.profile_dir:
        log.info("wrote profiler trace", dir=args.profile_dir)
    if recorder is not None:
        if args.force_stall:
            # CI leg: the drain is done, nothing beats the watchdogs any
            # more — the stall must be detected and dumped on its own
            log.info("forcing a stall", flight_dir=args.flight_dir)
            if not recorder.wait_for_dump(timeout=30.0):
                recorder.close()
                raise SystemExit(
                    "--force-stall: no flight dump appeared within 30s")
            log.info("flight dump written", dumps=recorder.dumps)
        recorder.close()


if __name__ == "__main__":
    main()
