"""Compile every serving DeMM kernel for a described TPU v5e chip.

Interpret mode (the rest of the suite) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, relayouts Mosaic cannot do,
scoped-VMEM overruns.  These tests lower and compile the four serving ops
(``xwT``, ``xwT_block`` and their int8 twins) with ``jax.jit(...).lower(...)
.compile()`` against a ``v5e:2x2`` topology described by the installed TPU
compiler — no chip is attached — at stablelm_3b widths (d_model 2560,
d_ff 6912), decode batch 4 and prefill chunk 32, for the relaxed 8:128
pattern and the fine patterns served by k-reconfiguration.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, so with several pytest
workers only the worker running this file loads it.  Where it cannot be
described the tests skip.

The grouped expert kernel (``demm_experts``) and OLMoE-1B-7B's two serving
programs are compiled at that model's published widths too.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sparsity import SparsityConfig
from repro.kernels.demm_block_spmm import demm_block_xwT_pallas
from repro.kernels.demm_experts import demm_experts_pallas
from repro.kernels.demm_q8 import (demm_block_xwT_q8_pallas,
                                   demm_xwT_q8_pallas)
from repro.kernels.demm_spmm import demm_xwT_pallas

D_MODEL, D_FF = 2560, 6912
# (out, in) of every packed linear of a stablelm_3b layer: MLP up/gate,
# MLP down, one attention projection (Q, K, V or O), and a fused QKV.
SHAPES = [(D_FF, D_MODEL), (D_MODEL, D_FF), (D_MODEL, D_MODEL),
          (3 * D_MODEL, D_MODEL)]
BATCHES = (4, 32)                  # decode slots, prefill chunk
PATTERNS = ["8:128", "8:16", "2:16", "2:4"]
BLOCK_R = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without the chip; keep these out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _operands(op, shape, batch, cfg, sharding):
    """ShapeDtypeStructs of one serving call: bf16 activations, float32
    packed values (the default param dtype) or int8 for the q8 twins."""
    o, k = shape
    g, ne = k // cfg.m, cfg.n_effective
    vdt = jnp.int8 if op.endswith("_q8") else jnp.float32

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sharding)

    x = s((batch, k), jnp.bfloat16)
    if op.startswith("xwT_block"):
        rb, a_max = o // BLOCK_R, g          # every group active: the bound
        core = (rb, a_max, ne, BLOCK_R)
        args = [x, s(core, vdt), s(core, jnp.int32), s((rb, a_max), jnp.int32)]
        if op.endswith("_q8"):
            args.append(s((rb, a_max, BLOCK_R), jnp.float32))
        return args
    args = [x, s((g, ne, o), vdt), s((g, ne, o), jnp.int32)]
    if op.endswith("_q8"):
        args.append(s((o,), jnp.float32))
    return args


KERNELS = {
    "xwT": demm_xwT_pallas,
    "xwT_q8": demm_xwT_q8_pallas,
    "xwT_block": demm_block_xwT_pallas,
    "xwT_block_q8": demm_block_xwT_q8_pallas,
}


def compile_op(op, shape, batch, cfg, sharding):
    kernel = KERNELS[op]
    fn = jax.jit(lambda *a: kernel(*a, cfg))
    return fn.lower(*_operands(op, shape, batch, cfg, sharding)).compile()


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("op", sorted(KERNELS))
def test_serving_kernel_compiles_for_v5e(one_chip, op, pattern):
    n, m = (int(v) for v in pattern.split(":"))
    cfg = SparsityConfig(n, m)
    for shape in SHAPES:
        for batch in BATCHES:
            compiled = compile_op(op, shape, batch, cfg, one_chip)
            text = compiled.as_text()
            assert "tpu_custom_call" in text, (op, shape, batch)
            mem = compiled.memory_analysis()
            # the packed operands go to the kernel as stored: no relayout
            # copy or transpose of a weight-sized buffer is inserted
            assert mem.temp_size_in_bytes < shape[0] * shape[1] // 16, (
                op, shape, batch, mem)


# The chat cell's served mapping (benchmarks/chip/configs/stablelm_3b.json):
# density 1/16 as 5:80 on K=2560 and 3:48 on K=6912, eight groups a grid
# step, at its 16 decode slots and 256-token prefill chunk.
CHAT_GROUPS = {D_MODEL: (5, 80), D_FF: (3, 48)}
CHAT_BATCHES = (16, 256)


@pytest.mark.parametrize("op", ["xwT", "xwT_q8"])
def test_chat_cell_mapping_compiles_for_v5e(one_chip, op):
    for shape in SHAPES:
        cfg = SparsityConfig(*CHAT_GROUPS[shape[1]])
        for batch in CHAT_BATCHES:
            compiled = compile_op(op, shape, batch, cfg, one_chip)
            assert "tpu_custom_call" in compiled.as_text(), (shape, batch)
            mem = compiled.memory_analysis()
            assert mem.temp_size_in_bytes < shape[0] * shape[1] // 16, (
                op, shape, batch, mem)


@pytest.mark.parametrize("layout", ["xwT", "block"])
def test_tp4_packed_decode_step_compiles_for_v5e(topo, layout):
    """The packed decode step under ``ShardingPlan(tp=4)`` on four described
    chips, Pallas backend: the compiler cannot partition a Pallas kernel,
    so every packed matmul must sit in a shard_map island (row-parallel:
    K split + psum; the rest: output split).  Reduced widths — what this
    checks is the partitioning, not the tiles."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import get_arch
    from repro.core.sparse_linear import ExecPolicy
    from repro.launch.pack_tree import pack_tree
    from repro.models.families import build_model
    from repro.sharding import context as shctx
    from repro.sharding.plan import ShardingPlan

    cfg = get_arch("stablelm_3b").reduced()
    model = build_model(cfg)
    plan = ShardingPlan(tp=4)
    mesh = plan.make_mesh(list(topo.devices))
    # block renumbering reads the packed data, so pack (tiny) real params
    params = plan.renumber_params(
        pack_tree(model.init(jax.random.PRNGKey(0)), layout=layout))
    specs = plan.param_specs(params)
    is_spec = lambda s: isinstance(s, P)
    flat_specs, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    params = treedef.unflatten([
        jax.ShapeDtypeStruct(p.shape, p.dtype,
                             sharding=NamedSharding(mesh, s))
        for s, p in zip(flat_specs, treedef.flatten_up_to(params))])
    state = jax.eval_shape(lambda: model.init_decode_state(4, 32))
    state = jax.tree.map(
        lambda p, s: jax.ShapeDtypeStruct(p.shape, p.dtype,
                                          sharding=NamedSharding(mesh, s)),
        state, plan.decode_state_specs(state, num_kv_heads=cfg.num_kv_heads))
    tokens = jax.ShapeDtypeStruct((4, 1), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    policy = ExecPolicy(mode="packed",
                        backend="pallas" if layout == "xwT" else "block_spmm",
                        plan=plan)
    ctx = plan.context(mesh, num_kv_heads=cfg.num_kv_heads,
                       num_heads=cfg.num_heads)
    with shctx.use_mesh(ctx):
        compiled = jax.jit(lambda p, s, t: model.decode_step(
            p, s, t, policy=policy)).lower(params, state, tokens).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text          # the row-parallel psum islands


# OLMoE-1B-7B's expert stacks: 64 experts, (O, K) of gate/up and of down,
# over the rows of a 4-slot decode step and of a 256-token chunk (top-8)
EXPERTS = 64
EXPERT_SHAPES = [(1024, 2048), (2048, 1024)]
EXPERT_ROWS = (32, 2048)


@pytest.mark.parametrize("shape", EXPERT_SHAPES)
@pytest.mark.parametrize("rows", EXPERT_ROWS)
def test_experts_kernel_compiles_for_v5e(one_chip, shape, rows):
    o, k = shape
    cfg = SparsityConfig(8, 128)
    core = (EXPERTS, k // 128, 8, o)

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    compiled = jax.jit(lambda *a: demm_experts_pallas(*a, cfg)).lower(
        s((rows, k), jnp.bfloat16), s((EXPERTS,), jnp.int32),
        s(core, jnp.float32), s(core, jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the stack goes to the kernel as stored: no relayout of it
    assert compiled.memory_analysis().temp_size_in_bytes < \
        EXPERTS * o * k // 16
    # the call's first operand has rows (the group offsets), not a scalar
    # grid bound: the benchmark's generic kernel costing reads them
    call = next(ln for ln in text.splitlines()
                if "demm_experts" in ln and "tpu_custom_call" in ln)
    first = call.split("operand_layout_constraints={", 1)[1].split(", ")[0]
    assert first.startswith(f"s32[{EXPERTS + 1}]"), first


def test_olmoe_serving_programs_compile_for_v5e(one_chip):
    """The packed decode step and prefill chunk of OLMoE-1B-7B at full
    width (16 layers, 64 experts a layer) on the olmoe_1b_7b.docqa cell's
    engine (4 slots, 1024 pages of 16, chunk 256) fit one chip: the params,
    the K/V arena held twice across a step, and the temporaries."""
    from repro.configs.base import get_arch
    from repro.core.sparse_linear import ExecPolicy
    from repro.launch.pack_tree import pack_tree
    from repro.models.families import build_model
    from repro.paged import PagedLayout

    model = build_model(get_arch("olmoe_1b_7b"))
    params = jax.eval_shape(
        lambda k: pack_tree(model.init(k, layer_fn=pack_tree)),
        jax.random.PRNGKey(0))
    layout = PagedLayout.for_serve(4096, page_size=16, num_pages=1024,
                                   num_slots=4)
    state = jax.eval_shape(lambda: model.init_decode_state(
        4, 4096, dtype=jnp.float32, paged=layout))

    def put(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def scalar(shape=()):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    policy = ExecPolicy(mode="packed", backend="pallas")
    programs = {
        "decode": (lambda p, s, t: model.decode_step(
            p, s, t, policy=policy),
            (put(params), put(state), scalar((4, 1)))),
        "prefill": (lambda p, s, t, slot, n: model.prefill_chunk(
            p, s, t, slot, n, policy=policy),
            (put(params), put(state), scalar((256,)), scalar(), scalar())),
    }
    for name, (fn, args) in programs.items():
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text(), name
        mem = compiled.memory_analysis()
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert peak < 16e9, (name, mem)
