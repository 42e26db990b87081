"""The device a run measures: which chips, how many, and their peaks.

A run measures the accelerator or nothing. ``require`` refuses a CPU
backend and a host with fewer chips than the cell asks for; ``peaks``
refuses a device kind that is not in ``bench/peaks.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class NoDevice(RuntimeError):
    """The run cannot measure: no accelerator, too few chips, or a kind
    with no peaks on record."""


def peaks(kind: str, path: Path = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} has no peaks in {path.name}; "
                       f"have {sorted(table)}")
    return table[kind]


def require(devices, chips: int, platform: str = "tpu") -> list:
    """The first ``chips`` devices, if they are all of ``platform``."""
    if not devices or devices[0].platform != platform:
        found = devices[0].platform if devices else "nothing"
        raise NoDevice(f"no {platform}: JAX found {found}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return list(devices[:chips])


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes of the fullest chip: ``peak_bytes_in_use`` (buffers)
    plus ``peak_bytes_reserved``, the region where a TPU program's
    temporaries live, which ``peak_bytes_in_use`` does not count (a
    program with a 2.1 GB temporary on one v5e left ``peak_bytes_in_use``
    at its 0.2 GB of buffers and raised ``peak_bytes_reserved`` to 2.1 GB,
    held after the run). The sum bounds the peak from above. 0 where the
    backend keeps no such statistics, as the CPU does."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def bytes_limit(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("bytes_limit", 16e9))
