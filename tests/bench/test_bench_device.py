"""A run measures a TPU or nothing: a CPU backend, too few chips and a
device kind without peaks are refused, and so is a checkout that holds
only the benchmark."""
import os
import shutil
import subprocess
import sys

import pytest

from benchutil import BENCH, REPO, tiny_root  # noqa: F401 (fixture)

sys.path.insert(0, str(BENCH))
from harness import device  # noqa: E402


class Dev:
    def __init__(self, platform, kind="TPU v5 lite", stats=None):
        self.platform, self.device_kind = platform, kind
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_cpu_is_refused():
    with pytest.raises(device.NoDevice, match="no tpu"):
        device.require([Dev("cpu", "cpu")], 1)


def test_too_few_chips_are_refused():
    with pytest.raises(device.NoDevice, match="needs 4 chips"):
        device.require([Dev("tpu")], 4)
    assert len(device.require([Dev("tpu")] * 4, 4)) == 4


def test_unknown_kind_is_refused():
    with pytest.raises(device.NoDevice, match="no peaks"):
        device.peaks("TPU v99")
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_memory_peak_counts_program_temporaries():
    # a TPU program's temporaries are reserved, not in use
    a = Dev("tpu", stats={"peak_bytes_in_use": 200, "peak_bytes_reserved":
                          2100})
    b = Dev("tpu", stats={"peak_bytes_in_use": 500})
    assert device.memory_peak([a, b]) == 2300
    assert device.memory_peak([Dev("cpu")]) == 0


def test_run_without_a_tpu_prints_no_result(tiny_root):
    # the command line pins the TPU; the CPU this test runs on is refused
    # before anything is set up, with no result line. (In a process of its
    # own: a JAX that started pinned to the TPU stays so.)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnist-tab1.restarts",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "cannot measure" in out.stderr


def test_benchmark_alone_is_refused(tmp_path):
    # a directory that holds only BENCHMARK.json and the benchmark's own
    # files has no program to run
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnist-tab1.restarts",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
