"""MetricsRecorder contract + the two implementations.

``MetricsRecorder`` defines the vocabulary every instrumented layer speaks:

  counter(name, inc)      monotonically accumulating count (psums issued,
                          batches staged, checkpoints written)
  gauge(name, value)      instantaneous host scalar (queue depth, empty
                          clusters) — recorded immediately
  series(name, value)     per-iteration measurement; ``value`` MAY be a
                          live ``jax.Array`` — it is parked unconverted and
                          drained in one batched fetch at ``batch_boundary``
                          (never a mid-loop blocking sync)
  event(name, **fields)   structured one-off (straggler_detected, resume,
                          hbm_watermark)
  record_span(name, **f)  one closed host span, written by
                          ``repro.obs.trace.span`` (the only caller)
  batch_boundary(batch)   drain deferred device scalars + flush the sink

``NullRecorder`` (singleton ``NULL``) is the zero-overhead default: every
hook is a no-op and no state is kept. ``JsonlRecorder`` appends one JSON
object per record to a file; it is thread-safe (the embedded fit's staging
records its spans on the PrefetchLoader producer thread, concurrently with
the consumer loop) and buffers lines host-side, flushing only at batch
boundaries and on ``close``.

Nothing in this module imports jax at call time beyond ``device_get`` in
the drain — recorder hooks must stay cheap enough to leave on.

The ``collectives/*`` counters the mesh fit loops emit are derived from
the STATIC audit (``repro.analysis.collective_bill`` over the traced
inner program, cached per batch shape): per-iteration while-body counts x
realized ``n_iter`` + the audited outside-the-loop prologue sync. If that
trace-time audit ever fails, the loops fall back to the analytic
``collectives_per_iteration`` bill and emit an ``audit_error`` event with
the exception — billing must never take a fit down.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional


class MetricsRecorder:
    """The contract (and the no-op base — see module docstring)."""

    enabled: bool = False

    def counter(self, name: str, inc: float = 1, **tags) -> None:
        pass

    def gauge(self, name: str, value, **tags) -> None:
        pass

    def series(self, name: str, value, **tags) -> None:
        pass

    def event(self, name: str, **fields) -> None:
        pass

    def record_span(self, name: str, **fields) -> None:
        pass

    def batch_boundary(self, batch: int) -> None:
        pass

    def close(self) -> None:
        pass

    def __enter__(self) -> "MetricsRecorder":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class NullRecorder(MetricsRecorder):
    """Zero-overhead default; every hook is a no-op."""


NULL = NullRecorder()


def resolve(recorder: Optional[MetricsRecorder]) -> MetricsRecorder:
    """The threading currency: ``recorder=None`` anywhere means ``NULL``."""
    return NULL if recorder is None else recorder


class JsonlRecorder(MetricsRecorder):
    """Flight recorder writing one JSON object per line.

    ``header`` (see ``repro.obs.export.run_header``) is written as the
    first line so a log is self-describing: commit, backend, device
    inventory, plan. Counter increments are written as they happen AND
    accumulated into per-name totals (``totals``) for cheap end-of-run
    summaries. Deferred ``series`` values (live ``jax.Array``s) are parked
    in ``_pending`` and drained by ``batch_boundary`` with ONE
    ``jax.device_get`` over the whole list — the only place this class
    touches device values.
    """

    enabled = True

    def __init__(self, path: str, *, header: Optional[dict] = None):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._lock = threading.Lock()
        self._lines: list[dict] = []
        self._pending: list[dict] = []      # deferred device-valued series
        self.totals: dict[str, float] = {}
        self._file = open(path, "w")
        if header is not None:
            self._append(header)
            self._flush()

    # -- record vocabulary --------------------------------------------------

    def counter(self, name: str, inc: float = 1, **tags) -> None:
        with self._lock:
            self.totals[name] = self.totals.get(name, 0.0) + inc
        self._append(dict(kind="counter", name=name, inc=inc,
                          total=self.totals[name], **tags))

    def gauge(self, name: str, value, **tags) -> None:
        self._append(dict(kind="gauge", name=name, value=float(value),
                          **tags))

    def series(self, name: str, value, **tags) -> None:
        # a jax.Array stays a future here; plain floats are written now.
        if hasattr(value, "device") or hasattr(value, "devices"):
            with self._lock:
                self._pending.append(dict(kind="series", name=name,
                                          value=value, **tags))
            return
        self._append(dict(kind="series", name=name, value=float(value),
                          **tags))

    def event(self, name: str, **fields) -> None:
        self._append(dict(kind="event", name=name, **fields))

    def record_span(self, name: str, **fields) -> None:
        self._append(dict(kind="span", name=name, **fields))

    def batch_boundary(self, batch: int) -> None:
        """Drain deferred device scalars (one batched fetch) and flush."""
        with self._lock:
            pending, self._pending = self._pending, []
        if pending:
            import jax
            vals = jax.device_get([p["value"] for p in pending])
            for p, v in zip(pending, vals):
                p["value"] = float(v)
                self._append(p)
        self._append(dict(kind="boundary", batch=int(batch)))
        self._flush()

    # -- sink ---------------------------------------------------------------

    def _append(self, rec: dict) -> None:
        rec.setdefault("t", time.time())
        with self._lock:
            self._lines.append(rec)

    def _flush(self) -> None:
        with self._lock:
            lines, self._lines = self._lines, []
            if lines and self._file is not None:
                self._file.write("".join(
                    json.dumps(l, default=_jsonable) + "\n" for l in lines))
                self._file.flush()

    def close(self) -> None:
        if self._file is None:
            return
        self.batch_boundary(-1)     # final drain (marks end-of-run)
        with self._lock:
            self._file.close()
            self._file = None


def _jsonable(v):
    """json.dumps fallback: numpy / jax scalars and arrays -> python."""
    try:
        import numpy as np
        a = np.asarray(v)
        return a.item() if a.ndim == 0 else a.tolist()
    except Exception:
        return str(v)
