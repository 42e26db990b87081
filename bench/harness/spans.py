"""The host outer loop's own spans in a ``Trace``.

``DistributedMiniBatchKMeans.fit`` opens an ``outer/batch`` host span per
batch and one child span per step of it (``outer/stage`` ...
``outer/record``; ``repro.obs.trace.span``), on the window's thread and on
the profiler's clock. ``Spans`` reads them:

- ``batches``: the ``outer/batch`` spans that lie wholly in the window (a
  span cut by the window's edge is not a batch of the window, nor one that
  a kept slice clipped to its edge);
- ``idle_ns(lo, hi)``: device-idle time in [lo, hi], the complement of the
  union of each device's operations, averaged over the devices (as
  ``harness.trace.idle_share`` reads the window);
- ``lowerings(lo, hi)``: host ``lower_sharding_computation`` events on the
  window's thread that start in [lo, hi]: the programs the host lowers
  there;
- ``by_phase``: both, per step and per batch.

A program without these spans gives no batches, and every reader then
returns None.
"""
from __future__ import annotations

import bisect

from .trace import clip, union

BATCH = "outer/batch"
FIT = "outer/fit"
PREFIX = "outer/"
LOWER = "lower_sharding_computation"
UNNAMED = "(unnamed)"


class _Busy:
    """One device's busy intervals in the window, for sums over ranges."""

    def __init__(self, ops, lo: float, hi: float):
        iv = union(clip(ops, lo, hi))
        self.starts = [a for a, _ in iv]
        self.ends = [b for _, b in iv]
        self.cum = [0.0]
        for a, b in iv:
            self.cum.append(self.cum[-1] + (b - a))

    def within(self, lo: float, hi: float) -> float:
        i = bisect.bisect_right(self.ends, lo)     # first ending after lo
        j = bisect.bisect_left(self.starts, hi)    # past the last before hi
        if j <= i:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, lo - self.starts[i])
                - max(0.0, self.ends[j - 1] - hi))


class Spans:

    def __init__(self, trace):
        lo, hi = trace.window
        # strictly inside: a kept slice clips a span cut by its edge to
        # end (or start) exactly there
        self.batches = sorted((s, s + d) for name, s, d in trace.host
                              if name == BATCH and lo < s and s + d < hi)
        self.steps = sorted(
            (name, s, s + d) for name, s, d in trace.host
            if name.startswith(PREFIX) and name not in (BATCH, FIT)
            and self._batch_of(s) is not None)
        self._lower = sorted(s for name, s, _ in trace.host if name == LOWER)
        self._busy = [_Busy(dev.ops, lo, hi) for dev in trace.devices]

    def _batch_of(self, t: float):
        """Index of the batch span that holds time ``t``, or None."""
        i = bisect.bisect_right(self.batches, (t, float("inf"))) - 1
        if i >= 0 and self.batches[i][0] <= t <= self.batches[i][1]:
            return i
        return None

    def idle_ns(self, lo: float, hi: float) -> float | None:
        if not self._busy:
            return None
        busy = sum(b.within(lo, hi) for b in self._busy) / len(self._busy)
        return (hi - lo) - busy

    def lowerings(self, lo: float, hi: float) -> int:
        return (bisect.bisect_right(self._lower, hi)
                - bisect.bisect_left(self._lower, lo))

    def by_phase(self) -> dict:
        """{step: (idle_ms, lowerings)} per batch of the window, with
        ``(unnamed)`` for what lies in the batches outside every step
        span (idle None without devices). Empty without a whole batch."""
        if not self.batches:
            return {}
        tot: dict = {}
        for name, a, b in self.steps:
            idle, low = tot.get(name, (0.0, 0))
            tot[name] = (idle + (self.idle_ns(a, b) or 0.0),
                         low + self.lowerings(a, b))
        idle_all = sum(self.idle_ns(a, b) or 0.0 for a, b in self.batches)
        low_all = sum(self.lowerings(a, b) for a, b in self.batches)
        tot[UNNAMED] = (idle_all - sum(v[0] for v in tot.values()),
                        low_all - sum(v[1] for v in tot.values()))
        n = len(self.batches)
        devices = bool(self._busy)
        return {name: (idle * 1e-6 / n if devices else None, low / n)
                for name, (idle, low) in tot.items()}

    def coverage(self) -> list:
        """Per batch of the window, the share of its duration that its
        step spans cover."""
        out = []
        for k, (a, b) in enumerate(self.batches):
            kids = [(name, s, e - s) for name, s, e in self.steps
                    if self._batch_of(s) == k]
            out.append(sum(e - s for s, e in union(clip(kids, a, b)))
                       / (b - a))
        return out


def by_phase(trace) -> dict:
    return Spans(trace).by_phase()
