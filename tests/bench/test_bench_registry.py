"""Cells, configurations, drivers and metrics are found by name: a new cell
or metric is new files and new entries, and no existing file changes."""
import json
import sys

from benchutil import BENCH, REPO, make_root

sys.path.insert(0, str(BENCH))
from harness import registry  # noqa: E402


def test_every_cell_resolves():
    bench = registry.load_benchmark(REPO)
    for w in bench["workloads"]:
        cell = registry.load_cell(REPO, w["name"])
        assert cell.chips == w["chips"]
        assert registry.driver(REPO, cell.traffic["driver"]).Driver
        assert registry.reference(REPO, cell.config["reference"]).Batch
        assert "limits" in cell.workload
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bench["per_layer"]:
        assert callable(registry.metric(REPO, m["name"]).read)


def test_listed_cells_match_their_files():
    bench = registry.load_benchmark(REPO)
    for w in bench["workloads"]:
        cell = registry.load_cell(REPO, w["name"])
        assert {k: cell.workload[k] for k in ("config", "traffic", "chips",
                                              "why")} == \
            {k: w[k] for k in ("config", "traffic", "chips", "why")}


def test_configs_match_their_files():
    bench = registry.load_benchmark(REPO)
    for c in bench["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]


def test_new_cell_and_metric_are_new_files_only(tmp_path):
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"driver": "restarts", "checked_batches": 1}))
    (root / "bench" / "workloads" / "mnist-tab1.dummy-mix.json").write_text(
        json.dumps({"limits": {}}))
    (root / "bench" / "metrics" / "dummy.rows_seen.py").write_text(
        "def read(run):\n    return float(len(run.counters['batches']))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mnist-tab1.dummy-mix",
                               "config": "mnist-tab1", "traffic": "dummy-mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "dummy.rows_seen", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "host outer loop",
                               "moves": "fit_rows_per_s",
                               "workloads": ["mnist-tab1.dummy-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.load_cell(root, "mnist-tab1.dummy-mix")
    assert cell.traffic["checked_batches"] == 1
    assert [m["name"] for m in cell.per_layer] == ["dummy.rows_seen"]
    reader = registry.metric(root, "dummy.rows_seen")

    class Run:
        counters = {"batches": [{}, {}]}
    assert reader.read(Run) == 2.0
    assert registry.driver(root, cell.traffic["driver"]).Driver
    after = {p: p.read_bytes() for p in before}
    assert after == before
