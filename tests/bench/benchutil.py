"""Helpers of the benchmark's CPU tests: a small copy of the benchmark (the
real ``bench/`` tree and ``BENCHMARK.json``, each configuration cut to a
few thousand rows) that ``run.main`` takes as its root, and the fixture
that makes it. (Not ``conftest.py``: the suite's own ``tests/conftest.py``
is imported by name.)"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"

TINY = {"mnist-tab1": {"rows": 2000},
        "imnist-8m": {"rows": 60000, "distinct_rows": 20000}}
TINY_TRAFFIC = {"assign-poisson": {"rate_rps": 200, "pool_rows": 4096},
                "stream": {"heldout_rows": 1000}}


def with_unlisted_cells(bench: dict) -> dict:
    """``bench`` with an entry for every ``bench/workloads/<cell>.json``
    that it does not list yet (cells whose files are ready but that were
    not proved on the chip), so that the tests drive their code too."""
    listed = {w["name"] for w in bench["workloads"]}
    for p in sorted((BENCH / "workloads").glob("*.json")):
        if p.stem not in listed:
            w = json.loads(p.read_text())
            bench["workloads"].append({"name": p.stem, **{
                k: w[k] for k in ("config", "traffic", "chips", "why")}})
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        if w["config"] not in configs:
            configs.add(w["config"])
            bench["configs"].append({
                "name": w["config"],
                "file": f"bench/configs/{w['config']}.json"})
    return bench


def make_root(dest: Path) -> Path:
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dest / "BENCHMARK.json").write_text(json.dumps(with_unlisted_cells(
        json.loads((REPO / "BENCHMARK.json").read_text()))))
    (dest / "src").symlink_to(REPO / "src")
    for name, cut in TINY.items():
        p = dest / "bench" / "configs" / f"{name}.json"
        p.write_text(json.dumps(dict(json.loads(p.read_text()), **cut)))
    for name, cut in TINY_TRAFFIC.items():
        p = dest / "bench" / "traffic" / f"{name}.json"
        p.write_text(json.dumps(dict(json.loads(p.read_text()), **cut)))
    return dest


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """The small copy, with the process environment that ``run.main``
    stages (``repro.launch.env.configure``) restored after the test and
    the chip's memory limit replaced by 200 MB, so that plan() splits the
    small streams into several batches."""
    for var in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "TPU_LOG_DIR"):
        if var in os.environ:
            monkeypatch.setenv(var, os.environ[var])
        else:
            monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.syspath_prepend(str(BENCH))
    from harness import device
    monkeypatch.setattr(device, "bytes_limit", lambda d: int(2e8))
    return make_root(tmp_path)


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def run_cell(root: Path, capsys, workload: str, seed: int = 3,
             seconds: float = 2.0, trace: int = 0, **kw) -> dict:
    sys.path.insert(0, str(BENCH))
    import run
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  platform="cpu", **kw)
    assert rc == 0
    return last_json(capsys.readouterr().out)
