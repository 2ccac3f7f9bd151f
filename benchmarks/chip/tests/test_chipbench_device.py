"""A run refuses any device but a TPU listed in ``peaks.json``."""

import os
import subprocess
import sys

import pytest

import chipbench_tiny
from chipbench import device


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


PEAKS = device.load_peaks()


def test_peaks_table_has_the_v5e_with_its_source():
    v5e = PEAKS["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]


@pytest.mark.parametrize("devices,chips", [
    ([_Dev("cpu", "cpu")], 1),
    ([_Dev("tpu", "TPU v9 imaginary")], 1),
    ([_Dev("tpu", "TPU v5 lite")], 4),
    ([], 1),
])
def test_refused(devices, chips):
    with pytest.raises(device.DeviceError):
        device.check(devices, chips, PEAKS)


def test_v5e_accepted():
    peak = device.check([_Dev("tpu", "TPU v5 lite")] * 4, 4, PEAKS)
    assert peak is PEAKS["TPU v5 lite"]


def test_run_on_cpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(chipbench_tiny.BENCH_DIR, "run.py"),
         "--workload", "stablelm_3b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=chipbench_tiny.REPO_ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
