"""Smoke run of the DeMM serving stack on TPU chips.

    python chip_smoke.py                # one chip: kernels, then serving
    python chip_smoke.py --four-chips   # four chips: TP=4 and 4 replicas

One process drives every phase (a chip belongs to one process at a time),
in this order; any failure raises and the script exits non-zero:

1. Device check: the default JAX backend must be a TPU.  Without one the
   script exits with code 1 and prints no result (there is no CPU fallback).
2. Kernels (one chip): each serving op — ``xwT``, ``xwT_block`` and their
   int8 twins ``xwT_q8``/``xwT_block_q8`` — runs its Pallas backend on
   random packed operands at stablelm_3b widths (MLP up 6912x2560, MLP down
   2560x6912, attention 2560x2560), decode batch 4 and prefill chunk 32, at
   8:128 and the fine patterns 8:16, 2:16, 2:4.  Each result is compared
   with the op's ``reference`` backend on the same chip:
   ``max|y - y_ref| / max|y_ref| <= KERNEL_RTOL``.
3. Serving (one chip): ``repro.launch.serve.run_serve`` serves stablelm_3b
   at full width (32 layers, d_model 2560, d_ff 6912, vocab 50304; random
   weights from seed 0, packed in one jitted init-and-pack program) through
   the paged engine with ``backend="pallas"``: 8 requests on 4 slots, 16 new
   tokens each.  ``kernel_dispatch_total`` must show Pallas backends only.
   The engine's sampler records the logits behind every token (the first
   from the prefill program, the rest from the decode program); the same
   run on the reference backend, forced onto the Pallas run's tokens, must
   give logits within ``max|l - l_ref| / max|l_ref| <= LOGIT_RTOL``.
4. ``--four-chips`` runs only the multi-chip path: the same serving run on
   one chip, then with tensor parallelism over four chips
   (``ShardingPlan(tp=4)``) and as four replicas behind the router.  The
   replicas must give the one-chip tokens, each with its params and decode
   state on its own chip.  TP must stay within ``LOGIT_RTOL`` of one
   chip's logits on the one-chip tokens; TP does not reproduce one chip's
   greedy tokens (its compiled programs round differently, and bf16
   logits tie), so the tokens it moves are listed with the one-chip gap
   between the two tokens and the error on them, and the per-layer KV
   arena errors show where the runs part.

The last line of standard output is one JSON object, printed only when
every phase passed: ``{"ok": true, "device": {"platform", "kind",
"count"}}``.  The compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<checkout>/.jax_cache``; the tuning cache is not read (the
backends are explicit).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Activations are bf16 and both paths multiply on the MXU at the chip's
# default (bf16) precision with fp32 accumulation, so they differ by
# summation order and at most one bf16 rounding of the weight (~2^-8
# relative; about 1e-4 of the output's magnitude on a v5e).  A bound of
# 2e-2 catches any indexing or scaling fault (those give errors of order 1)
# without flagging rounding.
KERNEL_RTOL = 2e-2
# The logits pass 32 layers of bf16 activations on both paths, where one
# changed summation can flip a bf16 rounding and the layers amplify it.
LOGIT_RTOL = 5e-2

D_MODEL, D_FF = 2560, 6912
SHAPES = {"mlp_up": (D_FF, D_MODEL), "mlp_down": (D_MODEL, D_FF),
          "attn": (D_MODEL, D_MODEL)}
BATCHES = (4, 32)                   # decode slots, prefill chunk
PATTERNS = ("8:128", "8:16", "2:16", "2:4")
KERNEL_BACKEND = {"xwT": "pallas", "xwT_q8": "pallas",
                  "xwT_block": "block_spmm", "xwT_block_q8": "block_spmm"}
BLOCK_R = 128

SERVE = dict(requests=8, slots=4, max_new=16)


def log(*parts):
    print(*parts, flush=True)


def random_operands(op, shape, batch, cfg, seed):
    """Random packed operands of one call, drawn on the host from ``seed``
    and put on the device: bf16 activations, float32 (or int8) values,
    indices in [0, M), and for the block layout a random sorted half of the
    groups active in every row block.  (Drawing them inside the compiled
    case made each compile many times slower than the kernel's own.)"""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    o, k = shape
    g, ne, m = k // cfg.m, cfg.n_effective, cfg.m
    x = jnp.asarray(rng.standard_normal((batch, k), np.float32),
                    jnp.bfloat16)
    block = op.startswith("xwT_block")
    if block:
        rb, a_max = o // BLOCK_R, max(1, g // 2)
        core = (rb, a_max, ne, BLOCK_R)
        ag = np.sort(np.argsort(rng.random((rb, g)), axis=1)[:, :a_max],
                     axis=1).astype(np.int32)
        scale_shape = (rb, a_max, BLOCK_R)
    else:
        core, scale_shape = (g, ne, o), (o,)
    idx = rng.integers(0, m, core, np.int32)
    if op.endswith("_q8"):
        vals = rng.integers(-127, 128, core).astype(np.int8)
    else:
        vals = rng.standard_normal(core, np.float32)
    args = [x, jnp.asarray(vals), jnp.asarray(idx)]
    if block:
        args.append(jnp.asarray(ag))
    if op.endswith("_q8"):
        args.append(jnp.asarray(
            rng.uniform(1e-3, 1e-2, scale_shape).astype(np.float32)))
    return args


def kernel_phase(patterns=PATTERNS, shapes=SHAPES, batches=BATCHES,
                 backends=KERNEL_BACKEND):
    """Every serving op on its Pallas backend vs its reference backend."""
    import jax
    import jax.numpy as jnp

    from repro import tune
    from repro.core.sparsity import SparsityConfig

    for op, backend in backends.items():
        t0 = time.time()
        worst, cases = 0.0, 0
        kern = tune.get_variant(op, backend)
        ref = tune.get_variant(op, "reference")
        for pattern in patterns:
            n, m = (int(v) for v in pattern.split(":"))
            cfg = SparsityConfig(n, m)
            for name, shape in shapes.items():
                for batch in batches:
                    params = ({} if op.startswith("xwT_block") else
                              kern.default_params(tune.Problem.for_xwT(
                                  (batch, shape[1]), shape, cfg,
                                  jnp.bfloat16)))

                    def case(*args, cfg=cfg, shape=shape, params=params):
                        y = kern.call(*args, cfg, shape, **params)
                        y_ref = ref.call(*args, cfg, shape)
                        return (jnp.max(jnp.abs(y - y_ref)),
                                jnp.max(jnp.abs(y_ref)),
                                jnp.all(jnp.isfinite(y)))

                    err, mag, finite = jax.jit(case)(*random_operands(
                        op, shape, batch, cfg, seed=cases))
                    rel = float(err) / max(float(mag), 1e-30)
                    cases += 1
                    if not bool(finite) or not rel <= KERNEL_RTOL:
                        raise AssertionError(
                            f"{op}/{backend} {pattern} {name}{shape} "
                            f"batch {batch}: rel err {rel:.3e} > "
                            f"{KERNEL_RTOL} (finite={bool(finite)})")
                    worst = max(worst, rel)
        log(f"kernel {op:<13} backend={backend:<10} cases={cases} "
            f"max_rel_err={worst:.3e} bound={KERNEL_RTOL} "
            f"seconds={time.time() - t0:.1f}")


def dispatch_counts():
    """``kernel_dispatch_total`` by (op, backend) on the default registry."""
    from repro import obs

    return {(c["labels"]["op"], c["labels"]["backend"]): c["value"]
            for c in obs.metrics().snapshot()["counters"]
            if c["name"] == "kernel_dispatch_total"}


def build_model(full: bool = True):
    """stablelm_3b (full width unless ``full`` is False) and its packed
    params, built on the device by the jitted init-and-pack program."""
    import jax

    from repro.configs.base import get_arch
    from repro.launch.pack_tree import init_packed
    from repro.models.families import build_model as build

    cfg = get_arch("stablelm_3b")
    if not full:
        cfg = cfg.reduced()
    model = build(cfg)
    t0 = time.time()
    params = init_packed(model, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    log(f"model {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} heads={cfg.num_heads} "
        f"init_and_pack_seconds={time.time() - t0:.1f}")
    return cfg, model, params


class LogitRecorder:
    """Greedy token sampler for the serving engines that keeps the logits
    row behind every token it picks, keyed by (request uid, position) —
    the output of the engine's own prefill and decode programs.  With
    ``force`` ({(uid, pos): token}, another run's ``tokens``) it emits
    those tokens instead of the argmax, so the engine runs on that run's
    token stream and the two runs' logits are compared on identical
    inputs."""

    def __init__(self, vocab, force=None):
        self.vocab, self.force = vocab, force
        self.rows, self.tokens = {}, {}

    def sample(self, logits, uid, pos):
        import numpy as np

        row = np.asarray(logits, np.float32)[:self.vocab]
        tok = (int(np.argmax(row)) if self.force is None
               else self.force[(uid, pos)])
        self.rows[(uid, pos)], self.tokens[(uid, pos)] = row, tok
        return tok


def serve(cfg, model, params, backend, plan=None, replicas=1, force=None):
    """One serving run through the launch driver's core; returns the
    engine, the generated tokens by request uid, and the run's
    :class:`LogitRecorder`."""
    from repro.launch.serve import run_serve

    rec = LogitRecorder(cfg.vocab_size, force)
    engine = run_serve(model, params, cfg.vocab_size, packed=True,
                       backend=backend, paged=True, plan=plan,
                       replicas=replicas, sampler=rec, **SERVE)
    tokens = {r.uid: list(r.output) for r in engine.completed}
    if len(tokens) != SERVE["requests"] or any(
            len(t) != SERVE["max_new"] for t in tokens.values()):
        raise AssertionError(f"served {len(tokens)} requests with lengths "
                             f"{sorted(len(t) for t in tokens.values())}")
    return engine, tokens, rec


def serve_phase(cfg, model, params, backend="pallas",
                allowed=("pallas", "block_spmm")):
    """Full-width serving on the Pallas backend, its dispatch audit, and
    its logits against the reference backend's on the same tokens."""
    before = dispatch_counts()
    engine, tokens, rec = serve(cfg, model, params, backend)
    log(f"serve {cfg.name} paged packed backend={backend} "
        f"requests={SERVE['requests']} slots={SERVE['slots']} "
        f"new_tokens={SERVE['max_new']} "
        f"drain_seconds={engine.drain_seconds:.2f} (information only)")
    for uid in sorted(tokens):
        log(f"  tokens[{uid}] = {tokens[uid]}")
    after = dispatch_counts()
    used = {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0) > 0}
    log(f"kernel_dispatch_total during serving: "
        f"{ {f'{op}/{be}': int(v) for (op, be), v in sorted(used.items())} }")
    if not used or any(be not in allowed for (_, be) in used):
        raise AssertionError(f"serving dispatched non-Pallas backends: {used}")

    _, _, ref = serve(cfg, model, params, "reference", force=rec.tokens)
    compare_logits(f"{backend} vs reference", rec, ref)
    return tokens


def compare_logits(what, got, want):
    """The logits two engine runs produced at the same (uid, position) on
    the same inputs: ``max|got - want| / max|want| <= LOGIT_RTOL``, all
    finite.  Every position whose argmax differs is listed with ``want``'s
    gap between the two tokens and the error on them (a greedy token moves
    only where that error reaches the gap).  Returns those positions."""
    import numpy as np

    keys = sorted(want.rows)
    if sorted(got.rows) != keys:
        raise AssertionError(f"logits {what}: the runs sampled different "
                             f"positions")
    g = np.stack([got.rows[k] for k in keys])
    w = np.stack([want.rows[k] for k in keys])
    d = np.abs(g - w)
    rel = float(d.max() / max(float(np.abs(w).max()), 1e-30))
    ag, aw = g.argmax(-1), w.argmax(-1)
    moved = [i for i in range(len(keys)) if ag[i] != aw[i]]
    top2 = np.sort(w, axis=-1)[:, -2:]
    log(f"logits {what}: positions={len(keys)} (prefill + decode) "
        f"max_rel_err={rel:.3e} max_abs_err={float(d.max()):.4f} "
        f"median top-2 gap={float(np.median(top2[:, 1] - top2[:, 0])):.4f} "
        f"bound={LOGIT_RTOL} argmax_moved={len(moved)}")
    for i in moved:
        a, b = aw[i], ag[i]
        log(f"  uid {keys[i][0]} pos {keys[i][1]}: token {a} -> {b}, "
            f"gap {float(w[i, a] - w[i, b]):.4f}, "
            f"error on the two {float(d[i, a] + d[i, b]):.4f}, "
            f"row max error {float(d[i].max()):.4f}")
    if not (bool(np.all(np.isfinite(g))) and rel <= LOGIT_RTOL):
        raise AssertionError(f"logits {what} differ: rel err {rel:.3e}")
    return [keys[i] for i in moved]


def arena_gap(engine, base):
    """Per layer, ``max|K - K_1| / max|K_1|`` (and the same for V) between
    the KV arenas of two engines that ran the same token streams, over the
    real pages (page 0 takes the masked lanes' writes): the first layer
    whose keys or values differ is where the runs part."""
    import numpy as np

    out = {}
    for name in ("k", "v"):
        a, b = engine.state["caches"][name], base.state["caches"][name]
        out[name] = []
        for i in range(b.shape[0]):
            ai, bi = np.asarray(a[i, 1:]), np.asarray(b[i, 1:])
            out[name].append(float(np.abs(ai - bi).max()
                                   / max(float(np.abs(bi).max()), 1e-30)))
    return out


def four_chip_phase(cfg, model, params, backend="pallas"):
    """TP=4 and 4 replicas against a one-chip run in the same process.
    The replicas must give the one-chip tokens.  TP is bounded by its
    logits on the one-chip tokens; the greedy tokens it moves are listed
    with the one-chip gap between the two tokens and the error on them,
    and the KV arenas show from which layer on the runs differ."""
    import jax

    from repro.sharding.plan import ShardingPlan

    one_engine, base, one = serve(cfg, model, params, backend)
    log(f"one chip: {len(base)} requests served")

    plan = ShardingPlan(tp=4)
    engine, tp, _ = serve(cfg, model, params, backend, plan=plan)
    mesh_devices = sorted(d.id for d in engine.mesh.devices.flat)
    log(f"tp=4 mesh devices={mesh_devices} "
        f"drain_seconds={engine.drain_seconds:.2f}")
    if mesh_devices != sorted(d.id for d in jax.devices()[:4]):
        raise AssertionError(f"TP mesh is not the four chips: {mesh_devices}")
    same = [u for u in base if base[u] == tp[u]]
    log(f"tp=4 greedy tokens identical to one chip for {len(same)} of "
        f"{len(base)} requests")
    for u in base:
        if base[u] != tp[u]:
            at = next(i for i, (a, b) in enumerate(zip(base[u], tp[u]))
                      if a != b)
            log(f"  req {u} parts at new token {at}")
    engine, _, forced = serve(cfg, model, params, backend, plan=plan,
                              force=one.tokens)
    compare_logits("tp=4 vs one chip", forced, one)
    gap = arena_gap(engine, one_engine)
    for name in ("k", "v"):
        first = next((i for i, e in enumerate(gap[name]) if e > 0), None)
        log(f"tp=4 vs one chip, KV arena {name.upper()} rel err by layer "
            f"(first nonzero: {first}): "
            + " ".join(f"{e:.1e}" for e in gap[name]))
    del engine, one_engine

    router, rep, _ = serve(cfg, model, params, backend,
                           plan=ShardingPlan(dp=4), replicas=4)
    placement = []
    for eng in router.replicas:
        devs = {d.id for leaf in jax.tree.leaves((eng.params, eng.state))
                if hasattr(leaf, "devices") for d in leaf.devices()}
        placement.append(sorted(devs))
    log(f"replicas=4 device ids per replica (params + decode state): "
        f"{placement} drain_seconds={router.drain_seconds:.2f}")
    if placement != [[d.id] for d in jax.devices()[:4]]:
        raise AssertionError(f"replicas do not hold a chip each: {placement}")
    diff = [u for u in base if base[u] != rep[u]]
    log(f"replicas=4 tokens identical to one chip: {not diff}")
    if diff:
        raise AssertionError(f"replica tokens differ for requests {diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip path (TP=4, 4 replicas) "
                         "and the one-chip run it is compared with")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    log(f"device platform={platform} kind={kind} count={len(devices)}")
    if platform != "tpu":
        print("chip_smoke: no TPU found (JAX backend is "
              f"{platform!r}); nothing was run", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    # explicit backends only: the tuning cache is never consulted, and a
    # stale one in the checkout must not be read either
    os.environ["REPRO_TUNE_CACHE"] = ""

    t0 = time.time()
    if args.four_chips:
        four_chip_phase(*build_model())
    else:
        kernel_phase()
        serve_phase(*build_model())
    log(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
