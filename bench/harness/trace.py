"""The profiler trace of a window, reduced to plain events.

``capture`` records a JAX profiler trace around the window, with the
window itself marked on the host as ``bench:window``. ``parse`` turns the
``.xplane.pb`` into a ``Trace``: per device plane its operations ("XLA
Ops" line), its asynchronous operations such as collectives and copies
("Async XLA Ops"), and its program executions ("XLA Modules"), and the host
events of the thread that ran the window, all in nanoseconds on one clock.
The metric readers under ``bench/metrics`` read only a ``Trace``, which
``to_json``/``from_json`` keep, so a trace recorded on the chip can be
committed (trimmed to a few milliseconds) and read again by the tests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import shutil

WINDOW = "bench:window"
OPS_LINE = "XLA Ops"
NAME_CHARS = 96          # an op's HLO text is long; its head names it
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"

Event = tuple  # (name, start_ns, dur_ns)


@dataclasses.dataclass
class Device:
    name: str
    ops: list
    modules: list
    async_ops: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    window: tuple          # (start_ns, end_ns) of the bench:window span
    devices: list          # [Device]
    host: list             # [Event] on the thread that ran the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


@contextlib.contextmanager
def capture(logdir: str):
    """Trace the enclosed window into ``logdir`` (emptied first). Python
    function tracing stays off: only TraceMe annotations and device
    activity are recorded."""
    import jax
    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def parse(logdir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    devices, host, window = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append(Device(plane.name, *(
                    _events(lines[k]) if k in lines else []
                    for k in (OPS_LINE, MODULES_LINE, ASYNC_LINE))))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = _events(ln)
                win = [e for e in evs if e[0] == WINDOW]
                if win:
                    window = (win[0][1], win[0][1] + win[0][2])
                    host = evs
    if window is None:
        raise ValueError(f"no {WINDOW} span in the trace")
    devices.sort(key=lambda d: d.name)
    return Trace(window, devices, host)


# -- reductions shared by the metric readers ---------------------------------

def clip(events, lo: float, hi: float) -> list:
    """Events cut to [lo, hi]; those outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events) -> list:
    """Merged [start, end] intervals covered by the events."""
    spans = sorted((s, s + d) for _, s, d in events)
    merged = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which an operation ran on the device,
    averaged over the devices."""
    if not trace.devices:
        return 0.0
    lo, hi = trace.window
    tot = 0.0
    for dev in trace.devices:
        tot += sum(b - a for a, b in union(clip(dev.ops, lo, hi)))
    return tot * 1e-9 / len(trace.devices)


def idle_share(trace: Trace) -> float | None:
    if not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def top_ops(trace: Trace, k: int = 10) -> list:
    """[[name, seconds]]: the device operations that took most time,
    averaged over the devices."""
    lo, hi = trace.window
    tot: dict = {}
    for dev in trace.devices:
        for name, _, d in clip(dev.ops, lo, hi):
            tot[name] = tot.get(name, 0.0) + d
    n = max(len(trace.devices), 1)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name[:NAME_CHARS], d * 1e-9 / n] for name, d in rows]


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """[[host activity, seconds]]: the idle time of the first device,
    each gap named by the innermost host event on the window's thread
    that covers its middle, summed by name; the longest k."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = union(clip(trace.devices[0].ops, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = sorted(trace.host, key=lambda e: e[2])     # innermost first
    tot: dict = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        name = next((n for n, s, d in host if s <= mid <= s + d), "none")
        tot[name] = tot.get(name, 0.0) + (b - a)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, d * 1e-9] for name, d in rows]


def module_events(trace: Trace, match) -> list:
    """Per device, the program executions whose name satisfies ``match``,
    cut to the window."""
    lo, hi = trace.window
    return [[e for e in clip(dev.modules, lo, hi) if match(e[0])]
            for dev in trace.devices]


def ops_within(ops, spans) -> list:
    """The operations that start inside one of ``spans``."""
    spans = sorted((s, s + d) for _, s, d in spans)
    out, j = [], 0
    for op in sorted(ops, key=lambda e: e[1]):
        while j < len(spans) and spans[j][1] < op[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= op[1] <= spans[j][1]:
            out.append(op)
    return out


# -- keeping a trace -----------------------------------------------------------

def to_json(trace: Trace, lo: float | None = None,
            hi: float | None = None) -> dict:
    """A plain copy of the trace, cut to [lo, hi] when given (the window
    becomes that range)."""
    lo = trace.window[0] if lo is None else lo
    hi = trace.window[1] if hi is None else hi
    return {
        "window": [lo, hi],
        "devices": [{"name": d.name, "ops": clip(d.ops, lo, hi),
                     "modules": clip(d.modules, lo, hi),
                     "async_ops": clip(d.async_ops, lo, hi)}
                    for d in trace.devices],
        "host": clip(trace.host, lo, hi),
    }


def from_json(obj: dict) -> Trace:
    return Trace(tuple(obj["window"]),
                 [Device(d["name"], [tuple(e) for e in d["ops"]],
                         [tuple(e) for e in d["modules"]],
                         [tuple(e) for e in d.get("async_ops", [])])
                  for d in obj["devices"]],
                 [tuple(e) for e in obj["host"]])


def save(trace: Trace, path: str, **cut) -> None:
    with open(path, "w") as f:
        json.dump(to_json(trace, **cut), f)


def load(path: str) -> Trace:
    with open(path) as f:
        return from_json(json.load(f))
