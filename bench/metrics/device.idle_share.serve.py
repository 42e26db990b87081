"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy the union of the device's operation intervals,
averaged over the cell's devices (source: device trace)."""
from harness.trace import idle_share


def read(run):
    return None if run.trace is None else idle_share(run.trace)
