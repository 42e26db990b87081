"""Events manifest: the run header every JSONL log opens with, readers for
the log, and the fold into the ``results/BENCH_*.json`` perf trajectory.

The header pins the run to a code state and a machine (commit, backend,
device inventory, jax version) plus whatever the caller knows (the
``core.memory.plan`` dict, the benchmark name) — a log file is then
self-describing: no out-of-band context needed to interpret it.

``summarize`` reduces a log to per-name aggregates (count/total/mean/max
of span seconds and of series and gauge values; final totals for
counters; the last measured-vs-predicted watermark pair) — the compact form
``benchmarks.common.record_bench`` embeds into ``BENCH_<name>.json`` so
the perf trajectory carries measured costs, not just end-to-end wall time.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import time


def git_commit() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def run_header(**extra) -> dict:
    """First line of every flight-recorder log (see module docstring).
    ``extra`` may carry a plan (dataclasses are flattened to dicts)."""
    import jax
    devs = jax.devices()
    header = {
        "kind": "header",
        "t": time.time(),
        "commit": git_commit(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "n_devices": len(devs),
        "n_processes": jax.process_count(),
    }
    for k, v in extra.items():
        header[k] = dataclasses.asdict(v) if dataclasses.is_dataclass(v) \
            else v
    return header


def read_events(path: str) -> list[dict]:
    """All records of a JSONL log (header included)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def summarize(path: str) -> dict:
    """Fold a log into per-name aggregates (see module docstring)."""
    stats: dict[str, dict] = {}
    counters: dict[str, float] = {}
    watermark = None
    n = 0
    for rec in read_events(path):
        n += 1
        kind = rec.get("kind")
        if kind == "counter":
            counters[rec["name"]] = rec.get("total", 0.0)
        elif kind in ("span", "series", "gauge"):
            v = rec.get("seconds") if kind == "span" else rec.get("value")
            if v is None:
                continue
            s = stats.setdefault(rec["name"], {"count": 0, "total": 0.0,
                                               "max": float("-inf")})
            s["count"] += 1
            s["total"] += v
            s["max"] = max(s["max"], v)
        elif kind == "event" and rec.get("name") == "hbm_watermark":
            watermark = {k: rec.get(k) for k in
                         ("measured_bytes", "peak_bytes", "predicted_bytes",
                          "source", "batch")}
    for s in stats.values():
        s["mean"] = s["total"] / max(s["count"], 1)
    return {"events": n, "stats": stats, "counters": counters,
            "last_watermark": watermark, "path": path}
