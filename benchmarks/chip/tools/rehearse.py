"""Compile a cell's programs for a described TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python benchmarks/chip/tools/rehearse.py \\
        --workload stablelm_3b.chat [--only build,decode,prefill,reference]

For the cell's configuration and engine sizing it lowers and compiles, on
one chip of a ``v5e:2x2`` topology the installed TPU compiler describes:

* ``build``     — the benchmark's jitted weight build (draw and pack);
* ``decode``    — the engine's decode step over every slot;
* ``prefill``   — the engine's prefill chunk;
* ``reference`` — the reference's layer and head at its largest bucket;

and prints each program's ``memory_analysis`` (argument, output, temp and
alias bytes).  The decode state is not donated, so a step holds its input
and its output state at once: ``peak`` adds them.  A compile that passes
is not a chip run and says nothing about time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path[:0] = [BENCH_DIR, os.path.join(REPO_ROOT, "src")]


def _on(device, tree):
    import jax
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree)


def _report(name, compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"program": name,
           "argument_bytes": int(m.argument_size_in_bytes),
           "output_bytes": int(m.output_size_in_bytes),
           "temp_bytes": int(m.temp_size_in_bytes),
           "alias_bytes": int(m.alias_size_in_bytes)}
    out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                         + out["temp_bytes"] - out["alias_bytes"])
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--only", default="build,decode,prefill,reference")
    args = ap.parse_args(argv)
    want = set(args.only.split(","))

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from chipbench import cell as cell_mod
    from chipbench import reference, spec, weights
    from repro.core.sparse_linear import ExecPolicy
    from repro.launch.pack_tree import pack_tree
    from repro.models.families import build_model
    from repro.paged.kv_cache import PagedLayout

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.resolve(args.workload)
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2").devices[0]
    model = build_model(cell_mod.arch_config(cell.config, cell.layer))
    build = weights.served_builder(model, cell.config, cell.layer,
                                   pack_tree)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = _on(dev, jax.eval_shape(build, key))
    if "build" in want:
        _report("build", jax.jit(build).lower(_on(dev, key)).compile())

    eng = cell.traffic["engine"]
    layout = PagedLayout.for_serve(eng["max_len"], page_size=eng["page_size"],
                                   num_pages=eng["num_pages"],
                                   num_slots=eng["num_slots"])
    state = _on(dev, jax.eval_shape(lambda: model.init_decode_state(
        eng["num_slots"], eng["max_len"], dtype=jnp.float32, paged=layout)))
    policy = ExecPolicy(mode="packed", backend="pallas")
    i32 = jnp.int32
    if "decode" in want:
        step = jax.jit(lambda p, s, t: model.decode_step(p, s, t,
                                                         policy=policy))
        tok = _on(dev, jax.ShapeDtypeStruct((eng["num_slots"], 1), i32))
        _report("decode", step.lower(params, state, tok).compile())
    if "prefill" in want:
        chunk = jax.jit(lambda p, s, t, slot, n: model.prefill_chunk(
            p, s, t, slot, n, policy=policy))
        args_ = _on(dev, (jax.ShapeDtypeStruct((eng["prefill_chunk"],), i32),
                          jax.ShapeDtypeStruct((), i32),
                          jax.ShapeDtypeStruct((), i32)))
        _report("prefill", chunk.lower(params, state, *args_).compile())
    if "reference" in want:
        dims = weights.layer_dims(cell.config, cell.layer)
        frozen = tuple(sorted(dims.items()))
        groups = weights.groups_of(cell.config)
        tree = cell.layer.tree(dims)
        layer = _on(dev, jax.eval_shape(lambda k: weights.layer_weights(
            k, 0, tree, groups, 1.0), key))
        top = _on(dev, jax.eval_shape(lambda k: weights.top_weights(
            k, dims, groups, 1.0), key))
        t = max(cell.traffic["check"]["buckets"])
        h = _on(dev, jax.ShapeDtypeStruct((t, dims["d"]), jnp.float32))
        rows = _on(dev, jax.ShapeDtypeStruct((512,), i32))
        _report("reference_layer", reference._run_layer.lower(
            layer, h, cell.layer.forward, frozen, False).compile())
        _report("reference_head", reference._head.lower(
            top, h, rows, frozen, False).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
