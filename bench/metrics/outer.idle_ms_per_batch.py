"""Device-idle milliseconds inside the host outer loop's ``outer/batch``
spans (``distributed/outer.py``), per ``outer/batch`` span in the window:
the span's length less the union of each device's operations in it,
averaged over the devices (source: program spans on the profiler's
clock). ``by_phase`` splits it, with the lowerings, by the batch's step
spans."""
from harness.spans import Spans, by_phase  # noqa: F401


def read(run):
    if run.trace is None:
        return None
    sp = Spans(run.trace)
    if not sp.batches or not run.trace.devices:
        return None
    return sum(sp.idle_ns(a, b) for a, b in sp.batches) * 1e-6 \
        / len(sp.batches)
