"""Observability tests (``repro.obs``, DESIGN.md §12): histogram math,
Prometheus exposition, event-trace ordering, dispatch/tune-cache counters,
the structured logger, supervisor metrics, and the snapshot schema
validator."""

import importlib.util
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, tune
from repro.configs.base import get_arch
from repro.core.sparsity import (PackedWeight, SparsityConfig, pack,
                                 pack_block, prune, random_sparse_dense)
from repro.kernels.ops import demm_matmul_packed
from repro.models.families import build_model
from repro.obs import MetricsRegistry, StructuredLogger
from repro.quant import quantize_packed
from repro.serve.serve_loop import Request, ServeConfig, ServeEngine


@pytest.fixture
def fresh_default_registry():
    """Isolate the process-wide registry (kernel dispatch / tune counters
    land there) and restore the previous one afterwards."""
    prev = obs.default_registry()
    reg = MetricsRegistry()
    obs.set_default_registry(reg)
    yield reg
    obs.set_default_registry(prev)


@pytest.fixture
def fresh_tune_cache(tmp_path):
    prev = tune.default_cache()
    cache = tune.TuneCache(path=str(tmp_path / "tune_cache.json"))
    tune.set_default_cache(cache)
    yield cache
    tune.set_default_cache(prev)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_histogram_bucket_math():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.005, 0.05, 5.0):
        h.observe(v)
    assert h.counts == [1, 2, 1, 1]           # last = +Inf overflow
    assert h.cumulative() == [1, 3, 4, 5]
    assert h.count == 5
    assert h.sum == pytest.approx(0.0005 + 0.005 + 0.005 + 0.05 + 5.0)
    # boundary lands in the bucket it equals (le semantics)
    h.observe(0.01)
    assert h.counts == [1, 3, 1, 1]


def test_histogram_rejects_unsorted_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(0.1, 0.01))


def test_counter_monotonic_and_kind_conflict():
    reg = MetricsRegistry()
    c = reg.counter("c", help="x")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    # same (name, labels) => same instrument; different kind => error
    assert reg.counter("c") is c
    with pytest.raises(ValueError):
        reg.gauge("c")


def test_snapshot_and_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("req_total", help="requests", op="xwT").inc(2)
    reg.gauge("slots").set(3)
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.05)

    snap = reg.snapshot()
    assert {"meta", "counters", "gauges", "histograms"} <= set(snap)
    (c,) = snap["counters"]
    assert c == {"name": "req_total", "labels": {"op": "xwT"}, "value": 2}
    (hh,) = snap["histograms"]
    assert hh["counts"] == [1, 1, 0] and hh["count"] == 2

    text = reg.to_prometheus()
    assert "# TYPE req_total counter" in text
    assert 'req_total{op="xwT"} 2' in text
    assert "slots 3" in text
    # cumulative le buckets ending in +Inf, plus _sum/_count series
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="0.1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text
    assert text.endswith("\n")


def test_registry_write_selects_format(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    p_json = tmp_path / "m.json"
    p_prom = tmp_path / "m.prom"
    reg.write(str(p_json))
    reg.write(str(p_prom))
    assert json.loads(p_json.read_text())["counters"][0]["value"] == 1
    assert "# TYPE c counter" in p_prom.read_text()


# ---------------------------------------------------------------------------
# event trace
# ---------------------------------------------------------------------------

def test_trace_span_and_event_ordering(tmp_path):
    reg = MetricsRegistry()
    tr = reg.trace
    with tr.span("outer", uid=1) as sp:
        tr.event("inner", step=0)
        sp.event("tagged")
    names = [e["name"] for e in tr.events]
    assert names == ["inner", "tagged", "outer"]
    tagged = tr.events[1]
    assert tagged["span"] == "outer" and tagged["uid"] == 1
    span_ev = tr.events[-1]
    assert span_ev["ph"] == "span" and span_ev["dur"] >= 0
    # span ts is the *start* time: before both intra-span point events
    assert span_ev["ts"] <= tr.events[0]["ts"] <= tr.events[1]["ts"]
    # JSONL round-trip
    out = tmp_path / "t.jsonl"
    tr.write(str(out))
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert [e["name"] for e in lines] == names


# ---------------------------------------------------------------------------
# serve-engine instrumentation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("stablelm_3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_serve_engine_request_lifecycle_metrics(small_model):
    cfg, model, params = small_model
    reg = MetricsRegistry()
    eng = ServeEngine(model, params, ServeConfig(num_slots=2, max_len=48),
                      metrics=reg)
    rng = np.random.default_rng(0)
    n_req, n_new = 3, 4
    for i in range(n_req):
        eng.submit(Request(uid=i,
                           prompt=rng.integers(0, cfg.vocab_size, 5,
                                               dtype=np.int32),
                           max_new_tokens=n_new))
    eng.run_until_drained()

    # counters agree with the engine's own completion list
    assert reg.counter("serve_requests_submitted_total").value == n_req
    assert (reg.counter("serve_requests_completed_total").value
            == len(eng.completed) == n_req)
    assert reg.counter("serve_tokens_total").value == n_req * n_new
    # every generated token was observed in the latency histogram
    assert reg.histogram("serve_decode_token_seconds").count == n_req * n_new
    assert reg.histogram("serve_queue_wait_seconds").count == n_req
    assert reg.histogram("serve_time_to_first_token_seconds").count == n_req
    assert reg.gauge("serve_slots_active").value == 0     # drained
    assert reg.gauge("serve_tokens_per_second").value > 0

    # per-request timestamp ordering: submit <= claim <= first <= complete
    for r in eng.completed:
        assert (r.submit_ts <= r.claim_ts <= r.first_token_ts
                <= r.complete_ts)

    # trace ordering per uid: submit -> claim -> first_token -> complete,
    # closed by one "request" span carrying the token count
    order = {"request_submit": 0, "request_claim": 1,
             "request_first_token": 2, "request_complete": 3}
    by_uid = {}
    spans = {}
    for e in reg.trace.events:
        if e["name"] in order:
            by_uid.setdefault(e["uid"], []).append(e)
        elif e["name"] == "request" and e.get("ph") == "span":
            spans[e["uid"]] = e
    assert set(by_uid) == set(spans) == set(range(n_req))
    for uid, evs in by_uid.items():
        assert [order[e["name"]] for e in evs] == [0, 1, 2, 3]
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        assert spans[uid]["tokens"] == n_new


# ---------------------------------------------------------------------------
# kernel-dispatch counters (all four packed layouts)
# ---------------------------------------------------------------------------

def _dispatch_counts(reg):
    return {(c["labels"]["op"], c["labels"]["backend"]): c["value"]
            for c in reg.snapshot(meta=False)["counters"]
            if c["name"] == "kernel_dispatch_total"}


def test_dispatch_counters_cover_all_packed_ops(fresh_default_registry):
    reg = fresh_default_registry
    rng = np.random.default_rng(0)
    sp = SparsityConfig(8, 128)
    o, k, b = 128, 256, 4
    w = jnp.asarray(random_sparse_dense(rng, o, k, sp))
    x = jnp.asarray(rng.standard_normal((b, k)), jnp.float32)

    p = pack(w, sp)
    pw = PackedWeight(p.values, p.indices, cfg=sp, dense_shape=(o, k))
    demm_matmul_packed(x, pw, backend="reference")
    demm_matmul_packed(x, quantize_packed(pw), backend="reference")
    blk = pack_block(w, sp)
    demm_matmul_packed(x, blk, backend="reference")
    demm_matmul_packed(x, quantize_packed(blk), backend="reference")

    counts = _dispatch_counts(reg)
    assert counts == {("xwT", "reference"): 1,
                      ("xwT_q8", "reference"): 1,
                      ("xwT_block", "reference"): 1,
                      ("xwT_block_q8", "reference"): 1}

    # dispatch is trace-time: re-running the same jitted computation must
    # not inflate the audit counters (the <=2% overhead guarantee)
    f = jax.jit(lambda xx: demm_matmul_packed(xx, pw, backend="reference"))
    f(x).block_until_ready()
    before = _dispatch_counts(reg)[("xwT", "reference")]
    f(x + 1).block_until_ready()
    assert _dispatch_counts(reg)[("xwT", "reference")] == before


def test_scatter_slice_counter_per_traced_pallas_call(fresh_default_registry):
    """``kernel_scatter_slices_total`` records, per traced packed Pallas
    call, the Sᵀ rows built at a time and per grid step: 5:80 takes eight
    groups a step and builds them one group (80 rows) at a time; 8:128
    takes one group a step, so its one slice is the whole step."""
    rng = np.random.default_rng(0)
    o, b = 128, 4
    for pattern, k in (((5, 80), 640), ((8, 128), 256)):
        sp = SparsityConfig(*pattern)
        p = pack(jnp.asarray(random_sparse_dense(rng, o, k, sp)), sp)
        pw = PackedWeight(p.values, p.indices, cfg=sp, dense_shape=(o, k))
        jax.eval_shape(lambda xx: demm_matmul_packed(xx, pw,
                                                     backend="pallas"),
                       jax.ShapeDtypeStruct((b, k), jnp.float32))
    got = {(c["labels"]["op"], c["labels"]["slice_rows"],
            c["labels"]["chunk_rows"]): c["value"]
           for c in fresh_default_registry.snapshot(meta=False)["counters"]
           if c["name"] == "kernel_scatter_slices_total"}
    assert got == {("xwT", "80", "640"): 1, ("xwT", "128", "128"): 1}


# ---------------------------------------------------------------------------
# tune-cache accounting + atomic save
# ---------------------------------------------------------------------------

def test_tune_cache_hit_miss_accounting(fresh_default_registry,
                                        fresh_tune_cache):
    reg, cache = fresh_default_registry, fresh_tune_cache
    sp = SparsityConfig(8, 128)
    p = tune.Problem.for_xwT((4, 256), (128, 256), sp, jnp.float32)

    cache.resolve(p)   # empty cache -> heuristic fallback
    cache.resolve(p)   # memoized heuristic -> hit
    cache.resolve(p)
    hits = {c["labels"]["op"]: c["value"]
            for c in reg.snapshot(meta=False)["counters"]
            if c["name"] == "tune_cache_hits_total"}
    misses = {c["labels"]["op"]: c["value"]
              for c in reg.snapshot(meta=False)["counters"]
              if c["name"] == "tune_cache_misses_total"}
    assert misses == {"xwT": 1}
    assert hits == {"xwT": 2}


def test_tune_cache_save_is_atomic(tmp_path):
    cache = tune.TuneCache(path=str(tmp_path / "d" / "cache.json"))
    sp = SparsityConfig(8, 128)
    p = tune.Problem.for_xwT((4, 256), (128, 256), sp, jnp.float32)
    cache.put(p, cache.resolve(p), persist=True)
    d = tmp_path / "d"
    assert (d / "cache.json").exists()
    # no temp files left behind, and the file is complete valid JSON
    assert [f.name for f in d.iterdir()] == ["cache.json"]
    blob = json.loads((d / "cache.json").read_text())
    assert blob["version"] == 1 and len(blob["entries"]) == 1
    # a second process-equivalent cache loads it back
    cache2 = tune.TuneCache(path=str(d / "cache.json"))
    assert cache2.load() == 1


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------

def test_logger_level_filtering(capsys):
    log = StructuredLogger("t", level="warning", json_lines=False)
    log.info("hidden")
    log.warning("shown", code=7)
    out = capsys.readouterr().out
    assert "hidden" not in out
    assert out == "[warning] shown code=7\n"


def test_logger_json_mode(capsys):
    log = StructuredLogger("t", level="info", json_lines=True)
    log.info("served", tokens=8, tok_s=41.5)
    rec = json.loads(capsys.readouterr().out)
    assert rec["logger"] == "t" and rec["level"] == "info"
    assert rec["msg"] == "served"
    assert rec["tokens"] == 8 and rec["tok_s"] == 41.5


def test_logger_text_quotes_awkward_values(capsys):
    log = StructuredLogger("t", json_lines=False)
    log.info("m", path="a b", eq="x=y")
    out = capsys.readouterr().out
    assert out == 'm path="a b" eq="x=y"\n'


# ---------------------------------------------------------------------------
# training supervisor metrics
# ---------------------------------------------------------------------------

def test_supervisor_metrics_and_restart_counters(tmp_path):
    from repro.data.pipeline import DataConfig
    from repro.train.fault_tolerance import (SupervisorConfig,
                                             TrainingSupervisor,
                                             inject_failure_once)

    reg = MetricsRegistry()

    def train_step(params, opt, batch, step):
        return params + 1, opt, {"loss": 0.0}

    sup = TrainingSupervisor(
        SupervisorConfig(ckpt_dir=str(tmp_path), ckpt_every=2,
                         max_restarts=2),
        train_step,
        DataConfig(vocab_size=16, seq_len=4, global_batch=2),
        metrics=reg)
    sup.run(np.zeros(4), np.zeros(4), 6,
            failure_injector=inject_failure_once(3))

    # the failure at step 3 restores to the step-2 checkpoint and replays
    # step 2, so 7 step *executions* complete the 6-step run
    assert reg.counter("train_steps_total").value == 7
    assert reg.counter("train_failures_total").value == 1
    assert reg.counter("train_restarts_total").value == 1
    assert reg.histogram("train_step_seconds").count == 7
    assert reg.counter("train_checkpoint_saves_total").value \
        == reg.histogram("train_checkpoint_save_seconds").count == 3
    assert reg.histogram("train_checkpoint_restore_seconds").count == 1
    names = [e["name"] for e in reg.trace.events]
    assert names.count("restart") == 1
    assert names.count("checkpoint_save") == 3
    assert names.count("checkpoint_restore") == 1


def test_straggler_monitor_folds_into_registry():
    from repro.train.fault_tolerance import StragglerMonitor

    reg = MetricsRegistry()
    mon = StragglerMonitor(4, metrics=reg)
    mon.record([1.0, 1.0, 1.0, 5.0])
    rep = mon.report()
    assert rep.flagged_hosts == [3]
    assert reg.gauge("train_host_step_seconds", host="3").value == 5.0
    assert reg.gauge("train_straggler_median_step_seconds").value == 1.0
    assert reg.gauge("train_stragglers_flagged").value == 1
    assert any(e["name"] == "stragglers_flagged"
               for e in reg.trace.events)


# ---------------------------------------------------------------------------
# snapshot schema validation (the CI metrics-smoke gate)
# ---------------------------------------------------------------------------

def _load_validator():
    root = pathlib.Path(__file__).resolve().parents[1]
    spec = importlib.util.spec_from_file_location(
        "validate_metrics", root / "benchmarks" / "validate_metrics.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, root


def test_snapshot_validates_against_checked_in_schema(small_model):
    vm, root = _load_validator()
    schema = json.loads(
        (root / "benchmarks" / "metrics_schema.json").read_text())

    cfg, model, params = small_model
    reg = MetricsRegistry()
    eng = ServeEngine(model, params, ServeConfig(num_slots=1, max_len=32),
                      metrics=reg)
    eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2))
    eng.run_until_drained()

    snap = reg.snapshot()
    assert vm.validate(snap, schema) == []
    assert vm.check_counter(snap, "serve_requests_completed_total") == []
    assert vm.check_histogram(snap, "serve_decode_token_seconds") == []
    # a required-but-absent family fails
    assert vm.check_counter(snap, "no_such_counter")
    # schema catches shape violations
    broken = json.loads(json.dumps(snap))
    broken["counters"][0]["value"] = -1
    assert vm.validate(broken, schema)
    del broken["meta"]
    assert vm.validate(broken, schema)


def test_validator_histogram_consistency_check():
    vm, _ = _load_validator()
    snap = {"histograms": [{"name": "h", "labels": {}, "buckets": [1.0],
                            "counts": [1, 0], "sum": 0.5, "count": 2}]}
    errs = vm.check_histogram(snap, "h")
    assert any("sum(counts)" in e for e in errs)


def test_phase_adds_its_seconds_to_the_counter():
    c = MetricsRegistry().counter("phase_seconds_total")
    with obs.phase("serve.test", c, uid=3):
        pass
    first = c.value
    assert first > 0
    with obs.phase("serve.test", c, step_num=7):
        pass
    assert c.value > first
    with obs.phase("serve.test"):           # no counter: a span alone
        pass
    with pytest.raises(KeyError):
        with obs.phase("serve.test", c):
            raise KeyError("propagates")
    assert c.value > first
