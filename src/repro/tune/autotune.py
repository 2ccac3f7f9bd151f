"""Measured autotuning of DeMM kernel variants.

Pipeline (per problem):

  1. **Enumerate** — every supported registered variant × the cartesian grid
     of its declared tile-candidate values (plus its heuristic default).
  2. **Prune** — drop candidates whose per-grid-step VMEM working set
     exceeds the budget (the TPU has ~16 MiB/core and the Pallas pipeline
     double-buffers every block), then rank the survivors with the
     first-order DeMM schedule model (:func:`repro.core.perfmodel
     .demm_tile_cycles`) and keep the ``max_measure`` most promising.
  3. **Measure** — run each survivor with ``warmup`` untimed iterations
     (compile + cache warm) followed by ``iters`` timed calls, each fenced
     with ``block_until_ready``; the score is the minimum (least-noise
     estimator for a deterministic kernel).  Every dispatchable candidate is
     measured under ``jax.jit`` — the regime production dispatch runs in —
     so eager-dispatch overhead never mis-ranks variants.
  4. **Select & persist** — the fastest *dispatchable* candidate is written
     to the tuning cache keyed by the full problem description.  The
     heuristic default is always measured, so the tuned choice is never
     slower than the default on the measured host.

``measure_only`` variants (the spmm-orientation block_spmm, which repacks
flat packed operands on the host) are measured and reported in the result
table but never selected for dispatch — they cannot be invoked from inside a
jit trace.  The ``xwT_block`` op has no such restriction: its operands are
packed ahead of time by ``core.sparsity.pack_block``, so the block kernel is
a first-class dispatch target (see :func:`autotune_xwT_block`).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.perfmodel import demm_tile_cycles
from repro.core.sparsity import SparsityConfig
from repro.tune.cache import TuneCache, TunedConfig, default_cache
from repro.tune.registry import KernelVariant, Problem, variants_for

# ~16 MiB/core on current TPUs; leave headroom for semaphores/scalars.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024
_DOUBLE_BUFFER = 2


def _dtype_bytes(dtype: str) -> int:
    return jnp.dtype(dtype).itemsize


def vmem_bytes(problem: Problem, variant: str, params: Dict[str, int]) -> int:
    """Per-grid-step VMEM working set of a Pallas candidate (bytes).

    Counts the double-buffered input/output blocks plus the materialized
    (rows, M) scatter matrix S.  Non-Pallas variants (no tile params) have
    no VMEM footprint to check — returns 0.
    """
    if not params:
        return 0
    eb = _dtype_bytes(problem.dtype)
    n, m, _ = problem.sparsity
    ne = problem.cfg.n_effective
    # quantized ops stream int8 values (+ a small fp32 scale per row) while
    # activations/scatter stay in the activation dtype (w8a16)
    quant = problem.op.endswith("_q8")
    vb = 1 if quant else eb
    if problem.op in ("xwT", "xwT_q8"):
        bb = params.get("block_b", 128)
        bo = params.get("block_o", 128)
        x_blk = bb * m * eb
        w_blk = bo * ne * (vb + 4)          # values + int32 indices
        if quant:
            w_blk += bo * 4                 # per-row scales
        out_blk = bb * bo * 4               # fp32 accumulator
        scatter = bo * m * eb
    elif problem.op in ("xwT_block", "xwT_block_q8"):
        # block_r is pack-time geometry (Problem.block_r), not a tile param.
        br = problem.block_r or 128
        bc = params.get("cd_block", 256)
        x_blk = m * bc * eb                 # gathered B (= xᵀ) block
        w_blk = br * ne * (vb + 4)
        if quant:
            w_blk += br * 4                 # per-(group, row) scales
        out_blk = br * bc * 4
        scatter = br * m * eb
    else:  # spmm / block_spmm
        br = params.get("block_r", 128)
        bc = params.get("block_c", params.get("cd_block", 256))
        x_blk = m * bc * eb                 # resident B block
        w_blk = br * ne * (eb + 4)
        out_blk = br * bc * 4
        scatter = br * m * eb
    return _DOUBLE_BUFFER * (x_blk + w_blk + out_blk) + scatter


@functools.lru_cache(maxsize=512)
def _schedule_cycles(problem: Problem, block_cols: int) -> int:
    # The perfmodel schedule depends only on (problem, block_cols); dozens of
    # tile candidates share a block_cols, and the representative mask draw is
    # expensive for big shapes — memoize.
    return demm_tile_cycles(problem.out, problem.k, problem.rows,
                            problem.cfg, block_cols)


def estimate_cycles(problem: Problem, params: Dict[str, int]) -> int:
    """Rank a tile candidate with the perfmodel DeMM schedule + a per-grid-
    step dispatch overhead (favors fewer, fatter tiles at equal schedule)."""
    if problem.op in ("xwT", "xwT_q8"):
        block_cols = params.get("block_b", 128)
        row_tiles = -(-problem.out // max(1, params.get("block_o", 128)))
        col_tiles = -(-problem.rows // max(1, block_cols))
        inner = problem.groups
    elif problem.op in ("xwT_block", "xwT_block_q8"):
        block_cols = params.get("cd_block", 256)
        row_tiles = -(-problem.out // max(1, problem.block_r or 128))
        col_tiles = -(-problem.rows // max(1, block_cols))
        # the inner grid dim visits only the active groups — the decoupled
        # address stream's whole point.
        inner = max(1, problem.a_max)
    else:
        block_cols = params.get("block_c", params.get("cd_block", 256))
        row_tiles = -(-problem.out // max(1, params.get("block_r", 128)))
        col_tiles = -(-problem.rows // max(1, block_cols))
        inner = problem.groups
    base = _schedule_cycles(problem, block_cols)
    grid_steps = row_tiles * col_tiles * inner
    return int(base + 50 * grid_steps)


def measure(thunk: Callable[[], jax.Array], *, warmup: int = 2,
            iters: int = 5) -> float:
    """Wall-time a jax thunk: ``warmup`` untimed calls (compile), then the
    min over ``iters`` fenced timings, in seconds."""
    for _ in range(max(1, warmup)):
        thunk().block_until_ready()
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        thunk().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


@dataclasses.dataclass
class Candidate:
    backend: str
    params: Dict[str, int]
    vmem: int = 0
    est_cycles: Optional[int] = None
    measured_s: Optional[float] = None
    status: str = "enumerated"   # pruned_vmem | pruned_rank | measured | error
    note: str = ""

    def row(self) -> dict:
        return {"backend": self.backend, "params": dict(self.params),
                "vmem_bytes": self.vmem, "est_cycles": self.est_cycles,
                "measured_us": (None if self.measured_s is None
                                else self.measured_s * 1e6),
                "status": self.status, "note": self.note}


@dataclasses.dataclass
class TuneResult:
    problem: Problem
    best: TunedConfig
    candidates: List[Candidate]

    @property
    def best_us(self) -> float:
        return self.best.measured_us

    def table(self) -> List[dict]:
        return [c.row() for c in self.candidates]


def _param_grid(variant: KernelVariant, problem: Problem) -> List[Dict[str, int]]:
    space = variant.param_space(problem)
    if not space:
        return [{}]
    names = sorted(space)
    grids = [space[n] for n in names]
    out = [dict(zip(names, vals)) for vals in itertools.product(*grids)]
    default = variant.default_params(problem)
    if default not in out:
        out.append(default)
    return out


def enumerate_candidates(problem: Problem,
                         include_measure_only: bool = True) -> List[Candidate]:
    cands = []
    for v in variants_for(problem.op, problem,
                          include_measure_only=include_measure_only):
        for params in _param_grid(v, problem):
            cands.append(Candidate(backend=v.name, params=params))
    return cands


def prune_candidates(problem: Problem, cands: List[Candidate], *,
                     vmem_budget: int = DEFAULT_VMEM_BUDGET,
                     max_measure: int = 8) -> List[Candidate]:
    """VMEM-budget check, then perfmodel ranking; keeps the defaults of each
    variant unconditionally so tuned-vs-default is always a measured pair."""
    defaults = {v.name: v.default_params(problem)
                for v in variants_for(problem.op, problem,
                                      include_measure_only=True)}
    survivors = []
    for c in cands:
        c.vmem = vmem_bytes(problem, c.backend, c.params)
        if c.vmem > vmem_budget:
            c.status = "pruned_vmem"
            continue
        c.est_cycles = (estimate_cycles(problem, c.params)
                        if c.params else None)
        survivors.append(c)
    keep = [c for c in survivors if defaults.get(c.backend) == c.params]
    rest = sorted((c for c in survivors if c not in keep),
                  key=lambda c: (c.est_cycles is None, c.est_cycles or 0))
    limit = max(max_measure, len(keep))
    for c in rest:
        if len(keep) < limit:
            keep.append(c)
        else:
            c.status = "pruned_rank"
    return keep


def _autotune(problem: Problem,
              make_thunk: Callable[[Candidate], Callable[[], jax.Array]],
              *, vmem_budget: int, max_measure: int, warmup: int, iters: int,
              cache: Optional[TuneCache], persist: bool) -> TuneResult:
    from repro import obs

    m = obs.metrics()
    measurements = m.counter("tune_autotune_measurements_total",
                             help="candidate kernels timed by autotune",
                             op=problem.op)
    cands = enumerate_candidates(problem)
    keep = prune_candidates(problem, cands, vmem_budget=vmem_budget,
                            max_measure=max_measure)
    variants = variants_for(problem.op, problem, include_measure_only=True)
    measure_only = {v.name for v in variants if v.measure_only}
    defaults = {v.name: v.default_params(problem) for v in variants}
    for c in keep:
        try:
            c.measured_s = measure(make_thunk(c), warmup=warmup, iters=iters)
            c.status = "measured"
            measurements.inc()
        except Exception as e:  # noqa: BLE001 — an unmeasurable candidate
            # A kernel that fails at its own default tiles on the chip is a
            # broken kernel, not a slow candidate: recording it would
            # quietly serve the reference path instead.
            if (problem.platform == "tpu" and c.params
                    and c.params == defaults.get(c.backend)):
                raise
            c.status = "error"  # (e.g. unsupported tiling) is skipped, not fatal
            c.note = f"{type(e).__name__}: {e}"[:200]
        # one trace event per candidate: the autotune audit trail a tuned
        # cache entry can be traced back to
        m.trace.event("autotune_measure", op=problem.op, backend=c.backend,
                      params=dict(c.params), status=c.status,
                      us=(None if c.measured_s is None
                          else c.measured_s * 1e6))
    measured = [c for c in keep if c.status == "measured"
                and c.backend not in measure_only]
    if not measured:
        raise RuntimeError(
            f"autotune: no dispatchable candidate measured for {problem}; "
            f"statuses: {[(c.backend, c.status, c.note) for c in keep]}")
    best_c = min(measured, key=lambda c: c.measured_s)
    best = TunedConfig(backend=best_c.backend, params=dict(best_c.params),
                       measured_us=best_c.measured_s * 1e6, source="tuned")
    m.trace.event("autotune_select", op=problem.op, backend=best.backend,
                  params=dict(best.params), us=best.measured_us)
    cache = cache or default_cache()
    cache.put(problem, best, persist=persist)
    return TuneResult(problem=problem, best=best, candidates=cands)


def autotune_xwT(x: jax.Array, values: jax.Array, indices: jax.Array,
                 cfg: SparsityConfig, w_shape: Tuple[int, int], *,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET, max_measure: int = 8,
                 warmup: int = 2, iters: int = 5,
                 cache: Optional[TuneCache] = None,
                 persist: bool = True) -> TuneResult:
    """Tune ``y = x @ W_sparseᵀ`` for the concrete operands given."""
    from repro.tune.registry import get_variant

    problem = Problem.for_xwT(x.shape, w_shape, cfg, x.dtype)

    def make_thunk(c: Candidate):
        v = get_variant("xwT", c.backend)
        # Production dispatch runs inside jit-compiled steps: measure every
        # candidate in that regime (the Pallas entry points are themselves
        # jitted; timing the reference eagerly would compare eager-dispatch
        # XLA against compiled Pallas and mis-rank them).
        if v.measure_only:
            return lambda: v.call(x, values, indices, cfg, tuple(w_shape),
                                  **c.params)
        jf = jax.jit(lambda xx, vv, ii: v.call(
            xx, vv, ii, cfg, tuple(w_shape), **c.params))
        return lambda: jf(x, values, indices)

    return _autotune(problem, make_thunk, vmem_budget=vmem_budget,
                     max_measure=max_measure, warmup=warmup, iters=iters,
                     cache=cache, persist=persist)


def autotune_xwT_q8(x: jax.Array, values: jax.Array, indices: jax.Array,
                    scales: jax.Array, cfg: SparsityConfig,
                    w_shape: Tuple[int, int], *,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET,
                    max_measure: int = 8, warmup: int = 2, iters: int = 5,
                    cache: Optional[TuneCache] = None,
                    persist: bool = True) -> TuneResult:
    """Tune ``y = x @ W_q8ᵀ`` (int8 values + per-output-row scales); keyed
    under the distinct ``xwT_q8`` op so float entries are never shadowed."""
    from repro.tune.registry import get_variant

    problem = Problem.for_xwT(x.shape, w_shape, cfg, x.dtype, quantized=True)

    def make_thunk(c: Candidate):
        v = get_variant("xwT_q8", c.backend)
        jf = jax.jit(lambda xx, vv, ii, ss: v.call(
            xx, vv, ii, ss, cfg, tuple(w_shape), **c.params))
        return lambda: jf(x, values, indices, scales)

    return _autotune(problem, make_thunk, vmem_budget=vmem_budget,
                     max_measure=max_measure, warmup=warmup, iters=iters,
                     cache=cache, persist=persist)


def autotune_xwT_block(x: jax.Array, pw, *,
                       vmem_budget: int = DEFAULT_VMEM_BUDGET,
                       max_measure: int = 8, warmup: int = 2, iters: int = 5,
                       cache: Optional[TuneCache] = None,
                       persist: bool = True) -> TuneResult:
    """Tune ``y = x @ W^T`` for a block-layout
    :class:`~repro.core.sparsity.PackedWeight` (geometry, pattern, and
    quantization come from the type's static aux data — a quantized node
    tunes the ``xwT_block_q8`` op).  All block variants are dispatchable, so
    the winner is directly selectable by ``backend="auto"``.
    """
    from repro.tune.registry import get_variant

    problem = Problem.for_xwT_block(x.shape, pw, x.dtype)
    cfg, w_shape = pw.cfg, tuple(pw.dense_shape)
    values, indices, active_groups = pw.values, pw.indices, pw.active_groups
    scales = pw.scales

    def make_thunk(c: Candidate):
        v = get_variant(problem.op, c.backend)
        if scales is not None:
            jf = jax.jit(lambda xx, vv, ii, ag, ss: v.call(
                xx, vv, ii, ag, ss, cfg, w_shape, **c.params))
            return lambda: jf(x, values, indices, active_groups, scales)
        jf = jax.jit(lambda xx, vv, ii, ag: v.call(
            xx, vv, ii, ag, cfg, w_shape, **c.params))
        return lambda: jf(x, values, indices, active_groups)

    return _autotune(problem, make_thunk, vmem_budget=vmem_budget,
                     max_measure=max_measure, warmup=warmup, iters=iters,
                     cache=cache, persist=persist)


def autotune_spmm(values: jax.Array, indices: jax.Array, b: jax.Array,
                  cfg: SparsityConfig, a_shape: Tuple[int, int], *,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET, max_measure: int = 8,
                  warmup: int = 2, iters: int = 5,
                  cache: Optional[TuneCache] = None,
                  persist: bool = True) -> TuneResult:
    """Tune ``C = A_sparse @ B`` for the concrete operands given."""
    from repro.tune.registry import get_variant

    problem = Problem.for_spmm(a_shape, b.shape, cfg, b.dtype)

    def make_thunk(c: Candidate):
        v = get_variant("spmm", c.backend)
        if v.measure_only:   # host-side repacking cannot trace under jit
            return lambda: v.call(values, indices, b, cfg, tuple(a_shape),
                                  **c.params)
        jf = jax.jit(lambda vv, ii, bb: v.call(
            vv, ii, bb, cfg, tuple(a_shape), **c.params))
        return lambda: jf(values, indices, b)

    return _autotune(problem, make_thunk, vmem_budget=vmem_budget,
                     max_measure=max_measure, warmup=warmup, iters=iters,
                     cache=cache, persist=persist)
