"""repro.paged: page allocator/arena bookkeeping, scheduler policies,
chunked-prefill dispatch accounting, and paged-vs-dense serving equivalence
(including through preemption)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models.families import build_model
from repro.obs.metrics import MetricsRegistry
from repro.paged import (
    ChunkedPrefill,
    NULL_PAGE,
    PageAllocator,
    PagedKVCache,
    PagedLayout,
    PagedServeConfig,
    PagedServeEngine,
    SchedConfig,
    Scheduler,
)
from repro.serve.serve_loop import Request, ServeConfig, ServeEngine


# ---------------------------------------------------------------------------
# kv_cache: allocator + arena bookkeeping (no jax involved)
# ---------------------------------------------------------------------------

def test_layout_pages_for():
    layout = PagedLayout(page_size=8, num_pages=17, max_blocks=6)
    assert layout.usable_pages == 16
    assert layout.pages_for(0) == 0
    assert layout.pages_for(1) == 1
    assert layout.pages_for(8) == 1
    assert layout.pages_for(9) == 2


def test_layout_for_serve_fully_provisions_by_default():
    layout = PagedLayout.for_serve(96, page_size=8, num_slots=4)
    # every slot can hold max_len tokens simultaneously (+ the null page)
    assert layout.max_blocks == 12
    assert layout.num_pages == 4 * 12 + 1
    assert layout.tokens_per_seq >= 96


def test_allocator_all_or_none_and_free():
    a = PageAllocator(num_pages=5)          # pages 1..4 usable, 0 reserved
    got = a.alloc(3)
    assert got is not None and len(got) == 3
    assert NULL_PAGE not in got
    assert a.alloc(2) is None               # only 1 left: all-or-none
    assert a.alloc_failures == 1
    a.free(got)
    with pytest.raises(ValueError):
        a.free(got[:1])                     # double free
    assert a.alloc(4) is not None           # everything reusable


def test_arena_capacity_release_and_fragmentation():
    layout = PagedLayout(page_size=4, num_pages=7, max_blocks=4)  # 6 usable
    kv = PagedKVCache(layout, num_slots=2)
    assert kv.ensure_capacity(0, 5)         # 2 pages
    kv.note_tokens(0, 5)
    assert kv.pages_used == 2
    # last page holds 1 of 4 token slots -> 3 slack slots of 8 allocated
    assert kv.fragmentation() == pytest.approx(3 / 8)
    assert kv.ensure_capacity(1, 16)        # the remaining 4 pages
    kv.note_tokens(1, 16)
    assert not kv.ensure_capacity(0, 9)     # would need a 3rd page: none left
    assert kv.release(1) == 4
    assert kv.ensure_capacity(0, 9)
    assert kv.table[0, 0] != NULL_PAGE      # rows point at real pages
    kv.release(0)
    assert kv.pages_used == 0
    assert np.all(kv.table == NULL_PAGE)


# ---------------------------------------------------------------------------
# scheduler: ordering, requeue stability, victim selection
# ---------------------------------------------------------------------------

def _req(uid, priority=1):
    return Request(uid=uid, prompt=np.zeros(4, np.int32), priority=priority,
                   output=[])


def test_scheduler_fcfs_ignores_priority():
    s = Scheduler(SchedConfig(policy="fcfs"))
    for uid, prio in ((0, 2), (1, 0), (2, 1)):
        s.submit(_req(uid, prio))
    assert [s.pop().uid for _ in range(3)] == [0, 1, 2]


def test_scheduler_priority_orders_then_arrival():
    s = Scheduler(SchedConfig(policy="priority"))
    for uid, prio in ((0, 2), (1, 0), (2, 1), (3, 0)):
        s.submit(_req(uid, prio))
    assert [s.pop().uid for _ in range(4)] == [1, 3, 2, 0]


def test_scheduler_requeue_keeps_arrival_seq():
    """A preempted request re-enters ahead of later arrivals — the stable
    arrival sequence is what makes preempt/resume deterministic."""
    s = Scheduler(SchedConfig(policy="fcfs"))
    s.submit(_req(0))
    s.submit(_req(1))
    first = s.pop()
    s.submit(_req(2))
    s.requeue(first)
    assert [s.pop().uid for _ in range(3)] == [0, 1, 2]


def test_scheduler_rejects_duplicate_uid():
    s = Scheduler(SchedConfig())
    s.submit(_req(7))
    with pytest.raises(ValueError):
        s.submit(_req(7))


def test_victim_prefers_worst_priority_then_youngest():
    s = Scheduler(SchedConfig(policy="priority"))
    reqs = [_req(0, 0), _req(1, 2), _req(2, 2)]
    for r in reqs:
        s.submit(r)
    cands = [(i, s.pop()) for i in range(3)]
    assert s.victim(cands) == 2             # worst prio, youngest arrival
    # admission-preempt only evicts a STRICTLY lower-priority victim
    assert s.victim(cands, incoming=_req(9, 1)) == 2
    assert s.victim(cands[:1], incoming=_req(9, 0)) is None


def test_victim_admission_disabled_under_fcfs():
    s = Scheduler(SchedConfig(policy="fcfs"))
    r = _req(0, 2)
    s.submit(r)
    cands = [(0, s.pop())]
    assert s.victim(cands, incoming=_req(9, 0)) is None
    assert s.victim(cands) == 0             # growth-preempt still works


# ---------------------------------------------------------------------------
# engine: equivalence, dispatch accounting, preemption, validation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_setup():
    # float32 compute: the equivalence tests compare greedy argmax across
    # two differently-compiled programs; bf16 random-init logits tie often.
    cfg = dataclasses.replace(get_arch("stablelm_3b").reduced(),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
            for n in lengths]


def _serve(engine, prompts, max_new=6):
    for i, p in enumerate(prompts):
        engine.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
    engine.run_until_drained(max_ticks=2000)
    return {r.uid: list(r.output) for r in engine.completed}


def test_paged_matches_dense_tokens(paged_setup):
    """Mixed prompt lengths, fully provisioned arena: every request decodes
    the exact token sequence the legacy dense-cache engine produces."""
    cfg, model, params = paged_setup
    prompts = _prompts(cfg, (5, 23, 11, 37, 17))
    want = _serve(ServeEngine(model, params,
                              ServeConfig(num_slots=4, max_len=96),
                              metrics=MetricsRegistry()), prompts)
    got = _serve(PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=4, max_len=96, page_size=8,
                         prefill_chunk=16),
        metrics=MetricsRegistry()), prompts)
    assert got == want


def test_paged_preemption_keeps_tokens_identical(paged_setup):
    """An undersized arena forces page-eviction preemption; resumed requests
    must still emit exactly the uninterrupted token sequence."""
    cfg, model, params = paged_setup
    prompts = _prompts(cfg, (5, 23, 11, 37))
    want = _serve(ServeEngine(model, params,
                              ServeConfig(num_slots=4, max_len=96),
                              metrics=MetricsRegistry()), prompts)
    reg = MetricsRegistry()
    eng = PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=4, max_len=96, page_size=8, num_pages=13,
                         prefill_chunk=16),
        metrics=reg)
    got = _serve(eng, prompts)
    assert reg.counter("serve_preempt_total").value >= 1
    assert got == want


def test_prefill_dispatch_is_chunked(paged_setup):
    """Chunked prefill issues exactly ceil(prompt_len / K) compiled-program
    invocations per request — O(T/K), not the legacy O(T)."""
    cfg, model, params = paged_setup
    chunk = 16
    prompts = _prompts(cfg, (5, 23, 11, 37))
    reg = MetricsRegistry()
    eng = PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=4, max_len=96, page_size=8,
                         prefill_chunk=chunk),
        metrics=reg)
    _serve(eng, prompts)
    want = sum(-(-len(p) // chunk) for p in prompts)
    assert eng.prefill.dispatches == want
    snap = reg.snapshot()
    by_prog = {c["labels"]["program"]: c["value"]
               for c in snap["counters"]
               if c["name"] == "serve_step_dispatch_total"}
    assert by_prog["prefill"] == want
    assert by_prog["decode"] >= 1


def test_prefill_program_compiles_once(paged_setup):
    """Every chunk of every prompt length reuses ONE compiled program:
    slot / n_valid / block-table contents are traced values, shapes fixed."""
    cfg, model, params = paged_setup
    eng = PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=4, max_len=96, page_size=8,
                         prefill_chunk=16),
        metrics=MetricsRegistry())
    _serve(eng, _prompts(cfg, (3, 17, 30, 9)))
    if hasattr(eng.prefill._fn, "_cache_size"):
        assert eng.prefill._fn._cache_size() == 1
        assert eng._decode._cache_size() == 1


def test_kernel_dispatch_constant_across_prompt_lengths(paged_setup):
    """``kernel_dispatch_total`` increments at jit-TRACE time — with the two
    fixed-shape compiled programs (chunk prefill + masked decode), the
    packed-kernel dispatch count is independent of how many prompt tokens
    flow through them: the O(prompt_len / K) property at the kernel level
    (only *invocations* scale, counted by serve_step_dispatch_total)."""
    from repro import obs
    from repro.core.sparse_linear import ExecPolicy
    from repro.launch.pack_tree import pack_tree

    cfg, model, params = paged_setup
    packed = pack_tree(params)

    def dispatch_total():
        return sum(c["value"] for c in obs.metrics().snapshot()["counters"]
                   if c["name"] == "kernel_dispatch_total")

    deltas = []
    for lengths in ((4, 9), (31, 17)):      # very different prompt shapes
        before = dispatch_total()
        eng = PagedServeEngine(
            model, packed,
            PagedServeConfig(num_slots=2, max_len=96, page_size=8,
                             prefill_chunk=16),
            policy=ExecPolicy(mode="packed"), metrics=MetricsRegistry())
        _serve(eng, _prompts(cfg, lengths))
        deltas.append(dispatch_total() - before)
    assert deltas[0] == deltas[1] > 0


def test_scheduling_policy_does_not_change_tokens(paged_setup):
    """Greedy decoding is per-request deterministic, so admission order
    (fcfs vs priority, with preemptions) never changes any output."""
    cfg, model, params = paged_setup
    prompts = _prompts(cfg, (5, 23, 11, 37))
    outs = []
    for pol in ("fcfs", "priority"):
        eng = PagedServeEngine(
            model, params,
            PagedServeConfig(num_slots=2, max_len=96, page_size=8,
                             num_pages=13, prefill_chunk=16,
                             sched=SchedConfig(policy=pol)),
            metrics=MetricsRegistry())
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=6,
                               priority=i % 3))
        eng.run_until_drained(max_ticks=2000)
        outs.append({r.uid: list(r.output) for r in eng.completed})
    assert outs[0] == outs[1]


def test_submit_validation(paged_setup):
    cfg, model, params = paged_setup
    eng = PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=1, max_len=32, page_size=8, num_pages=3),
        metrics=MetricsRegistry())
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=np.zeros(0, np.int32)))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=1, prompt=np.zeros(40, np.int32)))
    with pytest.raises(RuntimeError):
        # needs 3 pages at peak; the arena only has 2 usable
        eng.submit(Request(uid=2, prompt=np.zeros(17, np.int32),
                           max_new_tokens=4))


def test_arena_exhaustion_without_preemption_raises(paged_setup):
    cfg, model, params = paged_setup
    eng = PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=2, max_len=64, page_size=8, num_pages=9,
                         prefill_chunk=16,
                         sched=SchedConfig(preempt=False)),
        metrics=MetricsRegistry())
    for i, p in enumerate(_prompts(cfg, (20, 20))):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=16))
    with pytest.raises(RuntimeError):
        eng.run_until_drained(max_ticks=2000)


def test_paged_init_rejects_non_full_attention():
    cfg = get_arch("h2o_danube_1_8b").reduced()     # swa: ring is O(window)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    layout = PagedLayout.for_serve(32, page_size=8, num_slots=1)
    with pytest.raises(NotImplementedError):
        model.init_decode_state(1, 32, dtype=jnp.float32, paged=layout)
    del params


def test_chunked_prefill_requires_capable_model():
    class NoPrefill:
        pass

    with pytest.raises(NotImplementedError):
        ChunkedPrefill(NoPrefill())


def test_encdec_paged_prefill_matches_decode_steps():
    """EncDecLM: chunked paged prefill of a sequence produces the same
    last-position logits as feeding it token-by-token through the paged
    decode step (cross-attention reads the same dense enc_out)."""
    cfg = dataclasses.replace(get_arch("seamless_m4t_medium").reduced(),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    layout = PagedLayout.for_serve(48, page_size=8, num_slots=1)
    tokens = np.arange(1, 12, dtype=np.int32) % cfg.vocab_size

    kv = PagedKVCache(layout, 1)
    assert kv.ensure_capacity(0, len(tokens) + 1)
    table = jnp.asarray(np.array(kv.table))

    st = model.init_decode_state(1, 48, dtype=jnp.float32, paged=layout)
    st["caches"] = {**st["caches"], "block_table": table}
    pf = ChunkedPrefill(model, chunk=4)
    logits_pf, _ = pf.ingest(params, st, tokens, 0)
    assert pf.dispatches == 3

    st = model.init_decode_state(1, 48, dtype=jnp.float32, paged=layout)
    st["caches"] = {**st["caches"], "block_table": table,
                    "active": jnp.ones((1,), bool)}
    step = jax.jit(lambda p, s, t: model.decode_step(p, s, t))
    logits_st = None
    for t in tokens:
        logits_st, st = step(params, st, jnp.asarray([[t]], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_pf[0, 0], np.float32),
                               np.asarray(logits_st[0, 0], np.float32),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# tick phases, named programs and compile counts
# ---------------------------------------------------------------------------

def _counters(reg, name, label):
    return {c["labels"][label]: c["value"]
            for c in reg.snapshot(meta=False)["counters"]
            if c["name"] == name}


def test_tick_phases_ticks_and_compiles_are_counted(paged_setup):
    """Every tick phase adds its host seconds to its own counter, none more
    than the whole tick; ticks are counted; each program compiles once in
    warm-up and never again for the same shapes."""
    cfg, model, params = paged_setup
    reg = MetricsRegistry()
    eng = PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=4, max_len=96, page_size=8,
                         prefill_chunk=16),
        metrics=reg)
    _serve(eng, _prompts(cfg, (5, 23)))
    warm_ticks = eng.tick_count
    assert _counters(reg, "serve_compiles_total", "program") == {
        "decode": 1, "prefill": 1}
    for i, p in enumerate(_prompts(cfg, (30, 9, 17), seed=1)):
        eng.submit(Request(uid=10 + i, prompt=p, max_new_tokens=5))
    for _ in range(6):
        eng.step()
    assert _counters(reg, "serve_compiles_total", "program") == {
        "decode": 1, "prefill": 1}
    assert reg.counter("serve_ticks_total").value == warm_ticks + 6
    phases = _counters(reg, "serve_phase_seconds_total", "phase")
    assert set(phases) == {"tick", "admit", "control", "prefill_dispatch",
                           "prefill_wait", "decode_dispatch", "decode_wait",
                           "sample", "pages"}
    assert all(0 < v <= phases["tick"] for v in phases.values())


def test_compiled_programs_are_named(paged_setup):
    cfg, model, params = paged_setup
    eng = PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=2, max_len=32, page_size=8,
                         prefill_chunk=8),
        metrics=MetricsRegistry())
    decode = eng._jits["decode"].lower(
        eng.params, eng.state, jnp.zeros((2, 1), jnp.int32)).as_text()
    prefill = eng._jits["prefill"].lower(
        eng.params, eng.state, jnp.zeros((8,), jnp.int32), jnp.int32(0),
        jnp.int32(8)).as_text()
    assert "module @jit_decode_step" in decode
    assert "module @jit_prefill_chunk" in prefill


def test_profiler_trace_holds_the_tick_phases(paged_setup, tmp_path):
    """The phases are profiler spans: a CPU trace of a few ticks holds each
    ``serve.*`` span in its host plane, one ``serve.tick`` per tick."""
    cfg, model, params = paged_setup
    eng = PagedServeEngine(
        model, params,
        PagedServeConfig(num_slots=2, max_len=64, page_size=8,
                         prefill_chunk=16),
        metrics=MetricsRegistry())
    _serve(eng, _prompts(cfg, (20,)))           # compile outside the trace
    eng.submit(Request(uid=5, prompt=_prompts(cfg, (20,))[0],
                       max_new_tokens=3))
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        eng.step()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    seen = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        name = e.name.split("#", 1)[0]
                        seen[name] = seen.get(name, 0) + 1
    assert seen["serve.tick"] == 3
    assert {"serve.admit", "serve.control", "serve.prefill.dispatch",
            "serve.prefill.wait", "serve.decode.dispatch",
            "serve.decode.wait", "serve.sample", "serve.pages"} <= set(seen)
