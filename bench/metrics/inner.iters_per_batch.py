"""Mean ``BatchStats.inner_iters`` of the window's batches: Lloyd
iterations of the mesh inner loop (``distributed/inner.py``) to its
fixpoint (source: program counter)."""


def read(run):
    b = run.counters.get("batches", ())
    return sum(r["inner_iters"] for r in b) / len(b) if b else None
