#!/usr/bin/env python3
"""Readings for the limits of a cell, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        [--precision default] [--fault label_altered] [--rates 500,1000] \
        [--trace-out t.json]

For each seed (and each offered rate, for a serving cell) it makes one run
of the cell as ``bench/run.py`` does (``run.execute``: set-up, a window of
``--seconds``, the reference comparison) and prints one JSON line with the
numbers that comparison gives, the end-to-end values and the set-up time.
Programs compile once for the whole process, so a dozen seeds cost little
more than one run. ``--precision`` runs the program at another matmul
precision than the configuration states: the control whose readings set
the upper end of each limit. ``--fault`` plants one of
``harness.faults.NAMES`` under the timed path for every seed. ``--rates``
overrides the serving cell's offered rate, for the sweep that finds the
highest rate it sustains. ``--trace-out`` traces the first seed's window
and keeps the middle ``--trace-ms`` of it as plain events (a test
fixture).

It is not a benchmark run: `BENCHMARK.json`'s command is `bench/run.py`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None, *, root: Path = ROOT, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--precision", default=None)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--trace-out", default=None)
    ap.add_argument("--trace-ms", type=float, default=40.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import run
    cell, devices, _, compiles = run.start(root, args.workload, platform)
    from harness import faults, registry

    precision = args.precision or cell.config["matmul_precision"]
    reference = registry.reference(root, cell.config["reference"])
    patches = faults.Patches()
    if args.fault:
        faults.plant(args.fault, patches)
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [None])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        for rate in rates:
            c = cell
            if rate is not None:
                c = dataclasses.replace(
                    cell, traffic=dict(cell.traffic, rate_rps=rate))
            ctx = run.Context(c, seed, devices, False, reference, precision,
                              t_start=time.perf_counter())
            drv = registry.driver(root, c.traffic["driver"]).Driver(ctx)
            logdir = (str(root / "bench_out" / "calibrate_trace")
                      if args.trace_out and i == 0 else None)
            res = run.execute(ctx, drv, args.seconds, compiles, logdir)
            if logdir is not None:
                inspect(logdir, args.trace_out, args.trace_ms)
            iters = [b["inner_iters"]
                     for b in res["counters"].get("batches", ())]
            print(json.dumps({
                "workload": c.name, "seed": seed, "precision": precision,
                "fault": args.fault, "rate_rps": c.traffic.get("rate_rps"),
                "setup_s": res["setup_s"],
                "end_to_end": res.get("end_to_end"),
                "memory_peak_bytes": res["memory_peak_bytes"],
                "attempted": res["attempted"], "inner_iters": iters,
                "numbers": res["numbers"],
                "sweep": getattr(drv, "sweep_info", dict)()}), flush=True)
    patches.restore()
    return 0


def inspect(logdir: str, out: str, ms: float) -> None:
    """Print the trace's planes and lines, and keep ``ms`` of it."""
    import glob

    from jax.profiler import ProfileData

    from harness import trace as btrace
    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for ln in plane.lines:
            tot: dict = {}
            for e in ln.events:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:15]
            lines.append((ln.name, len(tot), top))
        print(json.dumps({"plane": plane.name, "lines": lines}),
              file=sys.stderr)
    tr = btrace.parse(logdir)
    # centred on the end of the first inner-loop program, where its last
    # iterations (collectives among them) meet the outer loop's launches
    inner = [e for e in (tr.devices[0].modules if tr.devices else [])
             if "_mesh_program" in e[0]]
    mid = (inner[0][1] + inner[0][2] if inner
           else 0.5 * (tr.window[0] + tr.window[1]))
    btrace.save(tr, out, lo=mid - ms * 5e5, hi=mid + ms * 5e5)
    print(json.dumps({"trace_window_s": tr.window_s,
                      "busy_s": btrace.busy_s(tr),
                      "top_ops": btrace.top_ops(tr),
                      "idle_gaps": btrace.idle_gaps(tr)}), file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
