"""Programs the host outer loop lowers again each batch: host
``lower_sharding_computation`` events on the window's thread that start
inside an ``outer/batch`` span (``distributed/outer.py``), per
``outer/batch`` span in the window (source: program spans on the
profiler's clock). ``by_phase`` splits it, with the device-idle time, by
the batch's step spans."""
from harness.spans import Spans, by_phase  # noqa: F401


def read(run):
    if run.trace is None:
        return None
    sp = Spans(run.trace)
    if not sp.batches:
        return None
    return sum(sp.lowerings(a, b) for a, b in sp.batches) / len(sp.batches)
