"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3_1b \
        --steps 200 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/ckpt

Sparsity-aware training (``repro.sparsetrain``)::

    PYTHONPATH=src python -m repro.launch.train --sparsify 8:128 --qat int8

``--sparsify`` drives a gradual magnitude-pruning schedule (default
3-phase anneal dense → N:2M → N:M; explicit phases via
``dense@0,8:256@50,8:128@150``) whose mask state rides every checkpoint;
``--qat int8`` adds straight-through fake quantization on the serving int8
grid.  The final checkpoint has the masks baked in (weights satisfy their
N:M patterns exactly), so it packs + serves directly::

    PYTHONPATH=src python -m repro.launch.serve --ckpt-dir /tmp/repro_ckpt \
        --packed --quantize int8 --backend auto

On the CPU container this runs REDUCED configs on a single device (the
default when no ``--full`` is given off-TPU; the multi-device production
mesh is exercised by the dry-run); on a real TPU fleet the same driver runs
full configs with ``--full`` and lets ``--mesh`` pick the production mesh.

``--metrics-out m.json`` writes the ``repro.obs`` metrics snapshot after
training (step-time and checkpoint-duration histograms, restart/failure
counters, kernel-dispatch counters; DESIGN.md §12).  Step logs go through
the structured logger — ``REPRO_LOG_JSON=1`` switches them to JSON lines.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ARCH_IDS, get_arch
from repro.data.pipeline import DataConfig, global_batch
from repro.core.sparse_linear import ExecPolicy
from repro.models.families import build_model
from repro.optim import adamw
from repro.train.fault_tolerance import SupervisorConfig, TrainingSupervisor
from repro.train.train_loop import make_train_step
from repro.launch.compile_cache import use_compile_cache


def add_frontend_inputs(cfg, batch, rng):
    if cfg.frontend == "vision":
        b, t = batch["tokens"].shape
        batch["patch_embeds"] = rng.standard_normal(
            (b, cfg.num_patches, cfg.d_model)).astype(np.float32)
    if cfg.family == "audio":
        b, t = batch["tokens"].shape
        batch["frames"] = rng.standard_normal(
            (b, t // cfg.encoder_seq_divisor, cfg.d_model)).astype(np.float32)
    return batch


def verify_final_masks(params) -> int:
    """Assert every sparse linear satisfies its stored N:M pattern exactly
    (call after ``SparseTrainer.finalize``).  Returns the node count."""
    from repro.core.sparsity import satisfies_pattern
    from repro.sparsetrain.masks import map_sparse_nodes

    def check(node, cfg):
        w = node["w"]
        flat = w.reshape(-1, w.shape[-1])
        assert bool(satisfies_pattern(flat, cfg)), (
            f"final mask violates {cfg.pattern_name()}")
        return True

    return sum(x is True for x in
               jax.tree.leaves(map_sparse_nodes(params, check)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm_3b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (the default off-TPU)")
    ap.add_argument("--full", action="store_true",
                    help="force the full config even on CPU")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compression", choices=["topk", "int8"], default=None)
    ap.add_argument("--log-every", type=int, default=10)
    # --- sparsity-aware training (repro.sparsetrain) ---
    ap.add_argument("--sparsify", default=None, metavar="SCHEDULE",
                    help="gradual N:M sparsification: a target pattern "
                         "('8:128', '8:128:2') for the default dense → "
                         "N:2M → N:M anneal, or explicit phases "
                         "('dense@0,8:256@50,8:128@150')")
    ap.add_argument("--sparsify-update-every", type=int, default=25,
                    help="within-phase magnitude-mask refresh cadence")
    ap.add_argument("--sparsify-freeze-after", type=int, default=None,
                    help="stop mask refreshes from this step on (default: "
                         "90%% of --steps, so the final support settles "
                         "before baking)")
    ap.add_argument("--qat", choices=("int8",), default=None,
                    help="straight-through fake quantization on the int8 "
                         "serving grid (requires --sparsify)")
    ap.add_argument("--qat-granularity", choices=("per_row", "per_group"),
                    default="per_row")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics snapshot (step-time/checkpoint "
                         "histograms, restart counters, kernel-dispatch "
                         "counters) here after training; .prom/.txt => "
                         "Prometheus text, else JSON")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="attach a flight recorder (repro.obs, DESIGN.md "
                         "§16): a train_step stall watchdog + bounded event "
                         "rings, dumped here on stall/crash/SIGTERM")
    ap.add_argument("--watchdog-threshold", type=float, default=8.0,
                    help="--flight-dir: declare a stall when step silence "
                         "exceeds this multiple of the EWMA step interval "
                         "(floored at 1s)")
    args = ap.parse_args()
    use_compile_cache()
    if args.qat and not args.sparsify:
        ap.error("--qat rides the sparsify training path; add --sparsify")
    if args.reduced and args.full:
        ap.error("--reduced and --full are mutually exclusive")
    # Reduced by default only on CPU (this container): GPU/TPU runs keep
    # the full config unless --reduced is given explicitly.
    reduced = args.reduced or (not args.full
                               and jax.default_backend() == "cpu")

    log = obs.get_logger("launch.train")
    cfg = get_arch(args.arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params)
                   if hasattr(x, "size"))
    log.info("arch", name=cfg.name, params_m=round(n_params / 1e6, 1),
             sparsity=(cfg.sparsity.pattern_name() if cfg.sparsity
                       else None),
             reduced=reduced)

    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(args.steps // 20, 5),
                                compression=args.compression)
    opt_state = adamw.init(opt_cfg, params)

    trainer = None
    if args.sparsify:
        from repro.sparsetrain import SparseTrainRecipe, SparseTrainer
        from repro.sparsetrain.masks import parse_schedule

        schedule = parse_schedule(args.sparsify, args.steps,
                                  update_every=args.sparsify_update_every,
                                  freeze_after=args.sparsify_freeze_after)
        log.info("sparsify schedule", spec=schedule.spec(),
                 **({"qat": f"{args.qat}/{args.qat_granularity}"}
                    if args.qat else {}))
        recipe = SparseTrainRecipe(schedule=schedule, qat=args.qat,
                                   qat_granularity=args.qat_granularity)
        trainer = SparseTrainer(model, opt_cfg, recipe,
                                num_microbatches=args.microbatches)
        trainer.init_state(params)
        step_fn = trainer.train_step
    else:
        step_fn = jax.jit(make_train_step(
            model, opt_cfg, num_microbatches=args.microbatches,
            policy=ExecPolicy(mode="masked")))

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    rng = np.random.default_rng(0)
    recorder = None
    if args.flight_dir:
        recorder = obs.FlightRecorder(
            args.flight_dir, watchdog_threshold=args.watchdog_threshold)
        recorder.install_signal_handlers()
    sup = TrainingSupervisor(
        SupervisorConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
        step_fn, data_cfg,
        to_batch=lambda b: add_frontend_inputs(cfg, b, rng),
        extra_state=trainer, recorder=recorder)

    t0 = time.time()
    # keyed by step (not append-ordered) so supervisor restarts replaying
    # steps overwrite instead of duplicating entries
    loss_by_step = {}

    orig_step = sup.train_step

    def logging_step(p, o, b, s):
        p, o, m = orig_step(p, o, b, s)
        loss_by_step[s] = float(m["loss"])
        if s % args.log_every == 0:
            log.info(f"step {s:5d}", loss=round(float(m["loss"]), 4),
                     gnorm=round(float(m["grad_norm"]), 3),
                     lr=float(f"{float(m['lr']):.2e}"),
                     elapsed_s=round(time.time() - t0, 1))
        return p, o, m

    sup.train_step = logging_step
    params, opt_state, metrics, restarts = sup.run(params, opt_state,
                                                   args.steps)
    first, last = loss_by_step[0], loss_by_step[max(loss_by_step)]
    log.info("done", final_loss=round(last, 4), first_loss=round(first, 4),
             restarts=restarts)
    if trainer is None:
        assert last < first, "training must reduce loss"
    else:
        # Pruning phases cause transient loss spikes, so a very short
        # schedule may end above its dense-warmup start; require learning
        # relative to init OR recovery within the final (serving-pattern)
        # phase.
        t_final = min(trainer.recipe.schedule.phases[-1].start,
                      max(loss_by_step))
        assert last < first or last < loss_by_step[t_final], (
            "training must reduce loss (vs step 0 or vs the final "
            "sparsity phase's start)")

    if trainer is not None:
        from repro.train import checkpoint as ckpt

        # Bake the final masks (hard zeros) so the committed checkpoint
        # satisfies the N:M patterns exactly and packs losslessly for
        # launch/serve.py --ckpt-dir ... --packed [--quantize int8].
        params = trainer.finalize(params)
        n_sparse = verify_final_masks(params)
        ckpt.save({"params": params, "opt": opt_state,
                   "extra": trainer.extra_state()},
                  args.ckpt_dir, args.steps)
        log.info("final masks verified; baked checkpoint re-saved",
                 sparse_linears=n_sparse, step=args.steps)

    if args.metrics_out:
        sup.metrics.write(args.metrics_out)
        log.info("wrote metrics snapshot", path=args.metrics_out)
    if recorder is not None:
        recorder.close()


if __name__ == "__main__":
    main()
