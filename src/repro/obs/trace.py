"""Host spans on the profiler's clock + on-demand trace dumps.

``span(name, rec=None, **tags)`` is the one host span primitive. It always
opens a ``jax.profiler.TraceAnnotation(name, **tags)``: the span lands in
the profiler's host plane, in the same ``.xplane.pb`` and on the same clock
as the device planes, and costs well under a microsecond while no profile
is being captured. When ``rec.enabled`` it also keeps one ``span`` record
in the recorder (flushed at the next ``batch_boundary``):

    {"kind": "span", "name", "id", "parent", "start", "seconds",
     "lowerings", "lower_s", **tags}

``parent`` is the ``id`` of the enclosing recorded span on the same thread
(None at the top). ``start`` is wall-clock seconds (``time.time()``),
``seconds`` the span's duration. ``lowerings`` and ``lower_s`` count the
jaxpr-to-MLIR lowerings that ran inside the span on its thread, and their
summed seconds (JAX's ``/jax/core/compile/jaxpr_to_mlir_module_duration``
monitoring event, listened to from the first recorded span on): the
program's own count of which step re-lowers its programs. A parent's
counts include its children's.

Device-side regions are named with ``jax.named_scope("obs:...")`` where the
ops are traced (Gram panel build, engine stats, psum hooks): free at run
time of a compiled program and identical with the recorder on or off.

``start_profile(logdir)`` / ``stop_profile()`` wrap
``jax.profiler.start_trace`` / ``stop_trace``: dump a TensorBoard-loadable
trace of a chosen window on demand (``tensorboard --logdir <dir>`` or
``xprof`` opens it). The launchers expose this as ``--profile <dir>``.
"""
from __future__ import annotations

import itertools
import threading
import time

import jax

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class _ThreadState(threading.local):
    """Per thread: the ids of the open recorded spans, and the lowerings
    seen so far. JAX's monitoring listeners are process-wide and run on
    the thread that lowers, so the counts live with the thread."""

    def __init__(self):
        self.stack: list[int] = []
        self.lowerings = 0
        self.lower_s = 0.0


_thread = _ThreadState()
_ids = itertools.count(1)
_listen_lock = threading.Lock()
_listening = False


def _on_duration(event: str, duration: float, **_) -> None:
    if event == LOWER_EVENT:
        _thread.lowerings += 1
        _thread.lower_s += duration


def _listen() -> None:
    """Register the lowering listener, once per process."""
    global _listening
    if _listening:
        return
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


def span(name: str, rec=None, **tags):
    """Host span (see module docstring): the profiler annotation alone
    unless ``rec`` is enabled."""
    if rec is None or not rec.enabled:
        return jax.profiler.TraceAnnotation(name, **tags)
    return _RecordedSpan(name, rec, tags)


class _RecordedSpan:
    """A span that also writes a ``span`` record; ``seconds`` holds its
    duration after exit."""

    __slots__ = ("_ann", "_rec", "_name", "_tags", "_id", "_parent",
                 "_start", "_t0", "_low0", "_lows0", "seconds")

    def __init__(self, name: str, rec, tags: dict):
        self._ann = jax.profiler.TraceAnnotation(name, **tags)
        self._rec = rec
        self._name = name
        self._tags = tags
        self.seconds = None

    def __enter__(self) -> "_RecordedSpan":
        self._ann.__enter__()
        _listen()
        st = _thread
        self._parent = st.stack[-1] if st.stack else None
        self._id = next(_ids)
        st.stack.append(self._id)
        self._low0, self._lows0 = st.lowerings, st.lower_s
        self._start = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        st = _thread
        st.stack.pop()
        self._rec.record_span(
            self._name, id=self._id, parent=self._parent, start=self._start,
            seconds=self.seconds, lowerings=st.lowerings - self._low0,
            lower_s=st.lower_s - self._lows0, **self._tags)
        self._ann.__exit__(*exc)
        return False


_active_logdir: str | None = None


def start_profile(logdir: str) -> None:
    """Begin capturing a profiler trace into ``logdir`` (idempotent —
    starting while active restarts nothing and keeps the first window)."""
    global _active_logdir
    if _active_logdir is not None:
        return
    jax.profiler.start_trace(logdir)
    _active_logdir = logdir


def stop_profile() -> str | None:
    """Stop the capture; returns the logdir the trace was written to
    (None when no capture was active)."""
    global _active_logdir
    if _active_logdir is None:
        return None
    out, _active_logdir = _active_logdir, None
    jax.profiler.stop_trace()
    return out
