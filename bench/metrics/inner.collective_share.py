"""Share of the inner-loop program's device time spent in collectives:
the time covered by all-reduce / all-gather operations (synchronous, or
asynchronous start to done) that start inside executions of the mesh
inner-loop program (``_mesh_program``), over those executions' duration,
averaged over the devices (source: device trace)."""
import re

from harness.trace import module_events, ops_within, union

INNER = "_mesh_program"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)


def read(run):
    if run.trace is None or len(run.trace.devices) < 2:
        return None
    shares = []
    for dev, spans in zip(run.trace.devices,
                          module_events(run.trace, lambda n: INNER in n)):
        total = sum(d for _, _, d in spans)
        if total <= 0:
            continue
        coll = [e for e in ops_within(dev.ops + dev.async_ops, spans)
                if COLLECTIVE.search(e[0])]
        coll = sum(b - a for a, b in union(coll))
        shares.append(100.0 * coll / total)
    return sum(shares) / len(shares) if shares else None
