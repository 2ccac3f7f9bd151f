"""Resolve a cell by name.

``BENCHMARK.json`` names the cells; everything that belongs to one
configuration, traffic mix, cell or metric sits in a file of its own under
the benchmark's directory, found by that name:

* ``configs/<config>.json``   (the path is the configuration's ``file``)
* ``traffic/<traffic>.json``
* ``limits/<workload>.json``  (the limit of each number ``correct`` compares)
* ``metrics/<metric>.py``     (a ``read(record)`` function)
* ``layers/<kind>.py``        (the configuration's ``"layer"``, ``dense``
  where it names none: its weight tree, reference layer and per-token
  work; see :data:`LAYER_FUNCTIONS`)
* ``kernels/<family>.py``     (optional: ``call(hlo)``, the operations and
  bytes of one call of a kernel family; see :func:`load_kernel_call`)

So a later change adds a cell, a metric, a layer kind or a kernel's costs
by adding files and entries, never by editing a file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))

# what a layer module defines (``layers/dense.py`` documents each)
LAYER_FUNCTIONS = ("dims", "tree", "forward", "active_weights",
                   "arch_changes")


class SpecError(ValueError):
    """A cell, or a file it names, is missing or malformed."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    entry: dict
    read: Callable[[dict], Optional[float]]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    layer: types.ModuleType


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not valid JSON: {e}") from e


def _load_module(path: str, prefix: str, name: str) -> types.ModuleType:
    """The module at ``path`` (names may hold dots, so the file is loaded
    by path, not imported by module name)."""
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: str,
                name: str) -> Callable[[dict], Optional[float]]:
    """``read`` of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"metric {name!r} has no reader at {path}")
    mod = _load_module(path, "chipbench_metric_", name)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(record)")
    return mod.read


def load_layer(bench_dir: str, kind: str) -> types.ModuleType:
    """The layer module ``layers/<kind>.py``, with every function of
    :data:`LAYER_FUNCTIONS`."""
    path = os.path.join(bench_dir, "layers", kind + ".py")
    if not os.path.exists(path):
        raise SpecError(f"layer kind {kind!r} has no module at {path}")
    mod = _load_module(path, "chipbench_layer_", kind)
    missing = [f for f in LAYER_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"{path} defines no {', '.join(missing)}")
    return mod


def layer_of(config: dict, bench_dir: str = BENCH_DIR) -> types.ModuleType:
    """The layer module a configuration names (``dense`` by default)."""
    return load_layer(bench_dir, config.get("layer", "dense"))


def load_kernel_call(bench_dir: str, family: str) -> Optional[Callable]:
    """``call(hlo) -> costs.Call`` of ``kernels/<family>.py``, or None where
    the family has no file (its calls are then counted by
    :func:`chipbench.costs.kernel_call`)."""
    path = os.path.join(bench_dir, "kernels", family + ".py")
    if not os.path.exists(path):
        return None
    mod = _load_module(path, "chipbench_kernel_", family)
    if not callable(getattr(mod, "call", None)):
        raise SpecError(f"{path} defines no call(hlo)")
    return mod.call


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def resolve(workload: str, repo_root: str = REPO_ROOT,
            bench_dir: str = BENCH_DIR) -> Cell:
    bench = _load_json(os.path.join(repo_root, "BENCHMARK.json"))
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; known: "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(os.path.join(repo_root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    limits = _load_json(os.path.join(bench_dir, "limits", workload + ".json"))

    def metrics(kind: str) -> List[Metric]:
        return [Metric(m["name"], m["unit"], m, load_reader(bench_dir,
                                                            m["name"]))
                for m in bench[kind] if _applies(m, workload)]

    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic, limits=limits,
                end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"),
                layer=layer_of(config, bench_dir))
