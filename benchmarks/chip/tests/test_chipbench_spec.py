"""Cells resolve their files by name, and a new cell or metric needs only
new files and entries."""

import hashlib
import json
import os
import shutil

import pytest

import chipbench_tiny
from chipbench import spec

REPO = chipbench_tiny.REPO_ROOT
BENCH = chipbench_tiny.BENCH_DIR


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


WORKLOADS = [w["name"] for w in _bench()["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_resolves(workload):
    cell = spec.resolve(workload)
    names = [m.name for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert cell.limits["served_logit_gap"]["limit"] > 0
    assert cell.traffic["loop"] in ("open", "closed")
    for key in ("num_slots", "max_len", "page_size", "num_pages",
                "prefill_chunk"):
        assert cell.traffic["engine"][key] > 0
    # the reference's buckets hold the longest request the mix can send
    longest = (cell.traffic["prompt_len"]["max"]
               + cell.traffic["output_len"]["max"])
    assert max(cell.traffic["check"]["buckets"]) >= longest - 1
    assert longest <= cell.traffic["engine"]["max_len"]
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.read)


def test_per_layer_metrics_name_cells_that_report_what_they_move():
    bench = _bench()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w]), (m["name"], w)


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_extra_cell_and_metric_resolve_from_new_files_only(tmp_path):
    repo = tmp_path / "repo"
    bench_dir = repo / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = _bench()
    before = _digest(bench_dir)

    # new files only: a configuration, a mix, a cell's limits, a metric
    cfg = dict(chipbench_tiny.CONFIG, source="https://example.org/tiny",
               reduced=[])
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "tiny_mix.json").write_text(
        json.dumps(chipbench_tiny.MIX))
    (bench_dir / "limits" / "tiny.tiny_mix.json").write_text(json.dumps(
        {"served_logit_gap": {"limit": 0.5}}))
    (bench_dir / "metrics" / "steps_per_s.py").write_text(
        "def read(rec):\n    return rec['steps'] / rec['window_s']\n")
    # and new entries
    bench["configs"].append({"name": "tiny", "source": cfg["source"],
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.tiny_mix", "config": "tiny",
                               "traffic": "tiny_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "Engine and scheduler",
                               "moves": "setup_s",
                               "workloads": ["tiny.tiny_mix"]})
    (repo / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.resolve("tiny.tiny_mix", repo_root=str(repo),
                        bench_dir=str(bench_dir))
    assert cell.config["hidden_size"] == 128
    assert [m.name for m in cell.per_layer] == ["steps_per_s"]
    assert cell.per_layer[0].read({"steps": 10, "window_s": 2.0}) == 5.0
    # the existing cells still resolve, and no existing file changed
    for w in WORKLOADS:
        spec.resolve(w, repo_root=str(repo), bench_dir=str(bench_dir))
    after = _digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_workload_and_missing_reader_refused(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.resolve("no_such.cell")
    with pytest.raises(spec.SpecError):
        spec.load_reader(str(tmp_path), "missing_metric")
