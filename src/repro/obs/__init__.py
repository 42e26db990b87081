"""repro.obs — the flight recorder: jit-safe runtime metrics, host spans
on the profiler's clock, and HBM watermarks for the clustering runtime.

Three rules make it safe to leave on in production:

  1. The recorder NEVER crosses the jit boundary. Every hook is host-side,
     around already-jitted calls or on values those calls return — so
     enabling metrics cannot change a traced program or trigger a single
     extra compilation (tests/test_obs.py proves the lowered-program count
     is identical with the recorder on and off).
  2. Device scalars are DEFERRED, never synced mid-loop: ``series`` accepts
     live ``jax.Array`` values and parks them; ``batch_boundary`` drains
     all of them with one batched ``device_get`` at the mini-batch edge,
     where the host loop is about to block on the next dispatch anyway.
  3. The default is ``NullRecorder`` — every hook is a no-op attribute
     lookup, so uninstrumented runs pay nothing.

Contrast with ``repro.core.metrics``: that module scores clustering
*quality* (NMI, accuracy, elbow); this package records where the *runtime*
spends time and bytes. ``repro.obs.trace.span`` is the one host span: a
profiler annotation always, plus a ``span`` record (duration, parent,
lowerings inside it) when the recorder is enabled. The mesh outer loop
(``repro.distributed.outer``) opens ``outer/fit``, ``outer/batch`` and one
child per step of a batch (``outer/stage`` ... ``outer/record``).
``repro.obs.export.summarize`` folds a JSONL event log into per-name
aggregates, which ``benchmarks.common.record_bench`` embeds in
``results/BENCH_*.json``.
"""
from .recorder import (JsonlRecorder, MetricsRecorder, NullRecorder, NULL,
                       resolve)
from .trace import span, start_profile, stop_profile
from . import export, memory

__all__ = [
    "JsonlRecorder", "MetricsRecorder", "NullRecorder", "NULL", "resolve",
    "span", "start_profile", "stop_profile", "export", "memory",
]
