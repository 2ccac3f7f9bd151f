"""``repro.serve`` — serving engines behind one protocol + factory.

Two concrete engines share the protocol surface (``submit`` / ``step`` /
``run_until_drained``, aliases ``tick``/``drain`` — see
:mod:`repro.serve.protocol`):

* :class:`ServeEngine` — dense per-slot KV caches, continuous batching.
* :class:`~repro.paged.PagedServeEngine` — shared paged KV arena, chunked
  prefill, scheduled admission/preemption (selected by passing a
  :class:`~repro.paged.PagedServeConfig`).

:func:`make_engine` dispatches on the config type and folds an optional
:class:`~repro.sharding.plan.ShardingPlan` into the policy, so callers
write one construction path for single-device, TP, PP, and (with
:class:`~repro.serve.router.ReplicaRouter` / ``replicas=``) DP serving.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

from repro.serve.protocol import Engine, EngineBase
from repro.serve.router import ReplicaRouter, make_replicas
from repro.serve.serve_loop import Request, ServeConfig, ServeEngine

__all__ = [
    "Engine", "EngineBase", "ReplicaRouter", "Request", "ServeConfig",
    "ServeEngine", "make_engine", "make_replicas",
]


def make_engine(model, params, config, *, plan=None, policy=None,
                autotune: bool = False, metrics=None, replicas: int = 1,
                spec=None, recorder=None):
    """Build a serving engine for ``config``.

    * ``config`` — :class:`ServeConfig` selects the dense-cache
      :class:`ServeEngine`; :class:`~repro.paged.PagedServeConfig` selects
      the paged :class:`~repro.paged.PagedServeEngine`.
    * ``plan`` — optional :class:`~repro.sharding.plan.ShardingPlan`,
      folded onto the policy (``policy.plan``); the engine then renumbers
      row-parallel packed weights, builds the mesh, and shards params +
      decode state.  Passing both ``plan`` and a policy that already
      carries a *different* plan is an error.
    * ``replicas`` — N > 1 wraps N engines (each with its own metrics
      registry and decode state) in a round-robin
      :class:`~repro.serve.router.ReplicaRouter`; ``metrics`` must then be
      None (each replica owns a registry; the router merges snapshots).
      Single-device replicas get a device each when there are enough
      (:func:`replica_devices`): replica ``i`` holds its own copy of
      ``params`` and its decode state on ``jax.devices()[i]``.  Otherwise
      they share ``params`` on one device.
    * ``spec`` — optional :class:`~repro.spec.SpecConfig`: the engine
      drafts with the sparser-tier view of the same packed buffers and
      verifies in batched full-tier dispatches (DESIGN.md §15).  Requires
      a packed params tree whose pattern the draft tier can narrow.
    * ``recorder`` — optional :class:`~repro.obs.FlightRecorder`: each
      engine taps its trace into the recorder's rings and beats a stall
      watchdog once per tick (DESIGN.md §16).
    """
    from repro.core.sparse_linear import resolve_policy

    policy = resolve_policy(policy, None, None)
    if plan is not None:
        if policy.plan is not None and policy.plan != plan:
            raise ValueError(
                "make_engine(plan=...) conflicts with policy.plan; pass the "
                "plan in one place")
        policy = policy.replace(plan=plan)

    def build(m, device=None):
        p, scope = params, contextlib.nullcontext()
        if device is not None:
            # the replica's params and everything its engine allocates
            # (decode state, KV arena) live on its own device
            p, scope = jax.device_put(params, device), jax.default_device(device)
        # dispatch on config type, paged imported lazily (repro.paged
        # imports repro.serve for the Request type)
        type_name = type(config).__name__
        with scope:
            if type_name == "PagedServeConfig":
                from repro.paged import PagedServeEngine
                return PagedServeEngine(model, p, config, policy=policy,
                                        autotune=autotune, metrics=m,
                                        spec=spec, recorder=recorder)
            if isinstance(config, ServeConfig):
                return ServeEngine(model, p, config, policy=policy,
                                   autotune=autotune, metrics=m, spec=spec,
                                   recorder=recorder)
        raise TypeError(
            f"make_engine: unknown config type {type(config).__name__!r} "
            "(expected ServeConfig or PagedServeConfig)")

    if replicas > 1:
        if metrics is not None:
            raise ValueError(
                "make_engine(replicas=N, metrics=...) is unsupported: each "
                "replica owns a registry and the router merges snapshots")
        return make_replicas(replicas, build,
                             devices=replica_devices(replicas, policy.plan))
    return build(metrics)


def replica_devices(replicas: int, plan=None):
    """One device per replica when every replica is a single-device engine
    and enough devices are visible; ``None`` (replicas share the default
    device) otherwise."""
    per_replica = 1 if plan is None else plan.tp * plan.pp
    if per_replica == 1 and jax.device_count() >= replicas:
        return jax.devices()[:replicas]
    return None
