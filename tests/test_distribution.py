"""Multi-device distribution tests (subprocess with 8 host devices):
sharding correctness, MoE expert parallelism, pipeline parallelism,
elastic checkpoint restore, compressed psum, and a mini dry-run."""

import pytest

from helpers import run_with_devices


def test_tp_dp_train_step_matches_single_device():
    """A distributed train step on a 2x4 mesh must match the single-device
    result numerically (same params, same batch)."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_arch
from repro.models.families import build_model
from repro.optim import adamw
from repro.train.train_loop import make_train_step
from repro.sharding.partitioning import opt_state_specs, shardings_for
from repro.sharding.plan import ShardingPlan
from repro.sharding import context as shctx

cfg = get_arch("stablelm_3b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt_cfg = adamw.AdamWConfig(lr=1e-3, total_steps=10, warmup_steps=1)
opt = adamw.init(opt_cfg, params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16))),
         "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)))}

# single device reference
step = make_train_step(model, opt_cfg, num_microbatches=2)
p_ref, _, m_ref = jax.jit(step)(params, opt, batch, 0)

# distributed
mesh = make_test_mesh((2, 4), ("data", "model"))
ctx = shctx.make_context(mesh, num_kv_heads=cfg.num_kv_heads)
pspecs = ShardingPlan().param_specs(params)
pshard = shardings_for(mesh, pspecs)
zspecs = opt_state_specs(pspecs, params, mesh.shape["data"])
ospecs = adamw.AdamWState(step=P(), m=zspecs, v=zspecs, compression=None)
oshard = shardings_for(mesh, ospecs)
bshard = jax.tree.map(lambda x: NamedSharding(mesh, P(("data",), None)), batch)
params_d = jax.device_put(params, pshard)
opt_d = jax.device_put(opt, oshard)
batch_d = jax.device_put(batch, bshard)
with shctx.use_mesh(ctx):
    p_dist, _, m_dist = jax.jit(
        step, in_shardings=(pshard, oshard, bshard, None),
        out_shardings=(pshard, oshard, None))(params_d, opt_d, batch_d, 0)

assert abs(float(m_ref["loss"]) - float(m_dist["loss"])) < 1e-3, \
    (float(m_ref["loss"]), float(m_dist["loss"]))
for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_dist)):
    if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-3)
print("TP/DP train step matches single-device")
""")


def test_moe_expert_parallel_matches_local():
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from jax.sharding import PartitionSpec as P
from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod
from repro.sharding import context as shctx

cfg = MoEConfig(num_experts=8, experts_per_token=2, d_ff_expert=32)
params = moe_mod.init_moe(jax.random.PRNGKey(0), 64, cfg, sparse=None)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64))

y_ref, aux_ref = moe_mod._apply_moe_local(params, x, cfg, capacity=64)

mesh = make_test_mesh((2, 4), ("data", "model"))
ctx = shctx.make_context(mesh, num_kv_heads=16)
# drop-free capacities on both paths -> results must agree exactly
with shctx.use_mesh(ctx):
    y_ep, aux_ep = jax.jit(
        lambda p, x: moe_mod.apply_moe(p, x, cfg, capacity=64))(params, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           rtol=2e-2, atol=2e-2)
print("MoE EP matches local dispatch")
""")


def test_pipeline_parallel_matches_sequential():
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.sharding.pipeline import pipeline_apply

n_stages, num_mb, mb, d = 8, 4, 2, 16
keys = jax.random.split(jax.random.PRNGKey(0), n_stages)
stage_params = {"w": jnp.stack([
    jax.random.normal(k, (d, d)) * 0.3 for k in keys])}

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"])

x = jax.random.normal(jax.random.PRNGKey(1), (num_mb, mb, d))
# sequential reference
y_ref = x
for i in range(n_stages):
    y_ref = jax.vmap(lambda xx: stage_fn({"w": stage_params["w"][i]}, xx))(y_ref)

mesh = make_test_mesh((8,), ("pipe",))
y_pipe = jax.jit(lambda p, x: pipeline_apply(stage_fn, p, x, mesh))(
    stage_params, x)
np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_ref),
                           rtol=1e-5, atol=1e-5)

# differentiability
g = jax.grad(lambda p: pipeline_apply(stage_fn, p, x, mesh).sum())(
    stage_params)
assert np.all(np.isfinite(np.asarray(g["w"])))
print("pipeline == sequential, grads finite")
""")


def test_elastic_restore_to_smaller_mesh(tmp_path):
    run_with_devices(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ckpt
from repro.train.fault_tolerance import elastic_restore

mesh8 = make_test_mesh((4, 2), ("data", "model"))
mesh4 = make_test_mesh((2, 2), ("data", "model"))
spec = {{"w": P("model", None)}}
w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
tree = {{"w": jax.device_put(w, NamedSharding(mesh8, spec["w"]))}}
ckpt.save(tree, r"{tmp_path}", 1)
restored = elastic_restore({{"w": w}}, r"{tmp_path}", 1, mesh4, spec)
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
assert restored["w"].sharding.mesh.shape["data"] == 2
print("elastic restore ok")
""")


def test_compressed_psum_int8():
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.optim.compression import compressed_psum_int8

mesh = make_test_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

out = jax.shard_map(lambda v: compressed_psum_int8(v[0], "data")[None],
                    mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                    check_vma=False)(x)
want = x.sum(0)
got = np.asarray(out[0])
scale = float(jnp.max(jnp.abs(x))) / 127
assert np.max(np.abs(got - np.asarray(want))) < scale * 8
print("compressed psum ok")
""")


def test_mini_dryrun_lower_compile():
    """The dry-run machinery on a small mesh: reduced config lower+compile
    with memory/cost/collective extraction end to end."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_arch
from repro.models.families import build_model
from repro.optim import adamw
from repro.train.train_loop import make_train_step
from repro.sharding.partitioning import opt_state_specs, shardings_for
from repro.sharding.plan import ShardingPlan
from repro.sharding import context as shctx
from repro.launch import hlo_analysis

cfg = get_arch("olmoe_1b_7b").reduced()
model = build_model(cfg)
pshapes = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
mesh = make_test_mesh((2, 4), ("data", "model"))
ctx = shctx.make_context(mesh, num_kv_heads=cfg.num_kv_heads)
pspecs = ShardingPlan().param_specs(pshapes)
pshard = shardings_for(mesh, pspecs)
opt_cfg = adamw.AdamWConfig()
ostate = jax.eval_shape(lambda p: adamw.init(opt_cfg, p), pshapes)
zspecs = opt_state_specs(pspecs, pshapes, mesh.shape["data"])
ospecs = adamw.AdamWState(step=P(), m=zspecs, v=zspecs, compression=None)
oshard = shardings_for(mesh, ospecs)
sds = jax.ShapeDtypeStruct
batch = {"tokens": sds((8, 32), jnp.int32), "targets": sds((8, 32), jnp.int32)}
bshard = jax.tree.map(lambda s: NamedSharding(mesh, P(("data",), None)), batch)
step = make_train_step(model, opt_cfg, num_microbatches=2)
with shctx.use_mesh(ctx):
    lowered = jax.jit(step, in_shardings=(pshard, oshard, bshard, None),
                      out_shardings=(pshard, oshard, None)).lower(
        pshapes, ostate, batch, jnp.zeros((), jnp.int32))
    compiled = lowered.compile()
mem = compiled.memory_analysis()
a = hlo_analysis.analyze(compiled.as_text())
assert a.flops > 0 and a.bytes_accessed > 0
assert a.unknown_trip_loops == 0
print("mini dryrun ok: flops=%.2e coll=%.2e" % (a.flops, a.collective_bytes))
""")
