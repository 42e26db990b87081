#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell; its configuration, traffic, driver,
limits and metric readers are files under ``bench/`` found by name
(``bench/harness/registry.py``). The run

1. pins JAX to the TPU and refuses to measure anything else: no TPU, fewer
   chips than the cell asks for, or a device kind without peaks in
   ``bench/peaks.json`` exits 1 with no result line;
2. sets up (data from the seed, the planned program, warm-up of every
   shape the window uses) under the configuration's matmul precision;
   ``setup_s`` runs from process start to the window's first job;
3. measures for ``--seconds``; with ``--trace 1`` the window runs under the
   profiler and the result carries the per-layer metrics, with ``--trace
   0`` the end-to-end ones;
4. reads the fullest chip's peak memory (``harness.device.memory_peak``),
   frees the program's state, and compares what the window produced with
   the plain reference:
   each number beside its limit on stderr, last, and under ``checks``,
   last, in the result line.

The persistent compilation cache is ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another (``repro.launch.env``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    """A line on stderr, stamped with the seconds since process start."""
    print(f"[bench {time.perf_counter() - T_START:8.3f}s] {msg}",
          file=sys.stderr, flush=True)


class Context:
    """What a driver is given: the cell, the seed, the devices, the
    configuration's reference and the matmul precision it runs under, and
    where to log. ``t_start`` is when its set-up began."""

    def __init__(self, cell, seed, devices, trace, reference, precision,
                 t_start=T_START):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.trace, self.reference = trace, reference
        self.precision = precision
        self.log = log
        self.t_start = t_start


class Run:
    """What a per-layer metric reader is given."""

    def __init__(self, trace, counters, peaks):
        self.trace, self.counters, self.peaks = trace, counters, peaks


class CompileCount:
    """Programs built by the backend (``n``), and how many of them came
    from the persistent compilation cache (``hits``): JAX times a cache
    load as a backend compile."""

    def __init__(self, jax):
        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def since(self, mark: tuple[int, int]) -> str:
        return (f"{self.n - mark[0]} programs, {self.hits - mark[1]} of "
                f"them from the persistent cache")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; a number without a limit,
    or not finite, fails."""
    out, ok = {}, bool(numbers)
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and math.isfinite(value)
                and value <= limit)
        ok &= good
        out[name] = {"value": value, "limit": limit}
    return ok, out


def start(root: Path, workload: str, platform: str):
    """Load the cell and the JAX backend; returns (cell, devices, peaks,
    compile counter). Raises ``RuntimeError`` (``device.NoDevice`` among
    them) where the run cannot measure."""
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(root / "src"))
    from harness import device, registry

    cell = registry.load_cell(root, workload)
    # libtpu writes its logs under /tmp/tpu_logs unless told otherwise:
    # keep everything a run writes inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", str(root / "bench_out" / "tpu_logs"))
    from repro.launch.env import configure
    configure(platform=platform)
    import jax

    devices = device.require(jax.devices(), cell.chips, platform)
    peaks = device.peaks(devices[0].device_kind) \
        if platform == "tpu" else None
    return cell, devices, peaks, CompileCount(jax)


def execute(ctx: Context, drv, seconds: float, compiles: CompileCount,
            logdir: str | None = None) -> dict:
    """One run of a cell: set-up and the window under the context's matmul
    precision (the window under the profiler where ``logdir`` is given),
    then the fullest chip's peak memory, the program's state freed, the
    end-to-end values (untraced runs only) and the numbers the reference
    comparison gives."""
    import jax
    from harness import device
    from harness import trace as btrace

    with jax.default_matmul_precision(ctx.precision):
        drv.setup()
        setup_s = time.perf_counter() - ctx.t_start
        log(f"setup_s {setup_s:.3f} ({compiles.since((0, 0))})")
        mark = (compiles.n, compiles.hits)
        if logdir is not None:
            with btrace.capture(logdir):
                drv.window(seconds)
        else:
            drv.window(seconds)
        log(f"in the window: {compiles.since(mark)}")
    out = {"setup_s": setup_s, "attempted": drv.attempted(),
           "memory_peak_bytes": device.memory_peak(ctx.devices),
           "counters": drv.counters()}
    drv.release()
    if logdir is None:
        out["end_to_end"] = dict(drv.end_to_end(), setup_s=setup_s)
    t0 = time.perf_counter()
    out["numbers"] = drv.checks()
    log(f"reference comparison took {time.perf_counter() - t0:.2f}s")
    return out


def main(argv=None, *, root: Path = ROOT, platform: str = "tpu") -> int:
    """``platform`` is for the CPU tests only: the command line always
    measures the TPU."""
    args = parse(argv)
    try:
        cell, devices, peaks, compiles = start(root, args.workload, platform)
    except RuntimeError as e:
        log(f"cannot measure: {e}")
        return 1
    from harness import device, registry
    from harness import trace as btrace

    dev_info = device.describe(devices)
    log(f"cell {cell.name} on {dev_info}, seed {args.seed}, "
        f"{args.seconds:g}s window, trace {args.trace}")
    ctx = Context(cell, args.seed, devices, bool(args.trace),
                  registry.reference(root, cell.config["reference"]),
                  cell.config["matmul_precision"])
    drv = registry.driver(root, cell.traffic["driver"]).Driver(ctx)
    logdir = (str(root / "bench_out" / "trace" / cell.name) if args.trace
              else None)
    res = execute(ctx, drv, args.seconds, compiles, logdir)

    metrics, tr = {}, None
    if args.trace:
        t0 = time.perf_counter()
        tr = btrace.parse(logdir)
        run = Run(tr, res["counters"], peaks)
        for m in cell.per_layer:
            value = registry.metric(root, m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev_info.update(busy_s=btrace.busy_s(tr), window_s=tr.window_s)
        log(f"trace read in {time.perf_counter() - t0:.2f}s")
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    dev_info["memory_peak_bytes"] = res["memory_peak_bytes"]

    correct, checks = judge(res["numbers"], cell.workload.get("limits", {}))
    attempted, failed = res["attempted"]
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev_info}
    if tr is not None:
        result["breakdown"] = {"device_ops": btrace.top_ops(tr),
                               "idle_gaps": btrace.idle_gaps(tr)}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
