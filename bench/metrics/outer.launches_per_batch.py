"""Device program executions per batch completed in the traced window:
the "XLA Modules" events of the first device over the window's batches.
The host outer loop (``distributed/outer.py``) launches one program per
eager step, so this counts its launches (source: device trace)."""
from harness.trace import module_events


def read(run):
    batches = len(run.counters.get("batches", ()))
    if run.trace is None or not run.trace.devices or not batches:
        return None
    return len(module_events(run.trace, lambda name: True)[0]) / batches
