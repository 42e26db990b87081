"""Find a cell's pieces by name.

``BENCHMARK.json`` names the cells, configurations and metrics. Every piece
that belongs to one of them is a file of its own, found by that name:

    bench/configs/<config>.json        sizes, source, assumed, reduced
    bench/traffic/<traffic>.json       traffic parameters, naming a driver
    bench/drivers/<driver>.py          the general generator of that kind
    bench/workloads/<cell>.json        limits of the correctness check and
                                       the planner's pick at definition
    bench/metrics/<metric>.py          the reader of one per-layer metric
    bench/references/<reference>.py    the plain reference a config names

A new cell, traffic mix or metric is a new file and a new entry; nothing
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]
    root: Path


def load_benchmark(root: Path) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file {path}")
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    wl_path = root / "bench" / "workloads" / f"{name}.json"
    workload = _json(wl_path) if wl_path.is_file() else {}
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        workload=workload,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        root=root)


def load_module(path: Path, name: str):
    """Import a file by path: metric files carry dots in their names."""
    if not Path(path).is_file():
        raise FileNotFoundError(f"missing benchmark module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(root: Path, kind: str):
    return load_module(Path(root) / "bench" / "drivers" / f"{kind}.py", kind)


def metric(root: Path, name: str):
    return load_module(Path(root) / "bench" / "metrics" / f"{name}.py", name)


def reference(root: Path, name: str):
    return load_module(Path(root) / "bench" / "references" / f"{name}.py",
                       name)
