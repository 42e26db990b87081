"""The Gram engine's share of its roofline (``core/engine.py`` and the
kernels under it), over the mesh inner-loop program (``_mesh_program``).

Least time of a batch on one device: the larger of its operations over
the chip's bf16 peak and its bytes over the HBM bandwidth, counting the
algorithm's work once whatever engine runs it (``work``, ``traffic``).
The share is that least time, summed over the window's batches, over the
device time of the inner-loop program's executions, averaged over the
devices (source: device trace). An engine that rebuilds the Gram every
iteration shows a low share.
"""
from harness.trace import module_events

INNER = "_mesh_program"
RBF_ELEMENTWISE = 4      # d^2 assembly (2 adds), scale, exp per entry


def work(rows, landmarks, dim, clusters, iters):
    """Operations: one Gram evaluation [rows, landmarks] over dim features
    with its rbf terms, then iters f/g products against the [L, C]
    one-hot."""
    return (2.0 * rows * landmarks * dim + RBF_ELEMENTWISE * rows * landmarks
            + iters * 2.0 * rows * landmarks * clusters)


def traffic(rows, landmarks, dim):
    """Bytes: the batch rows and the landmark rows, f32, read once."""
    return 4.0 * (rows + landmarks) * dim


def least_seconds(batch, chips, peaks):
    rows = batch["rows"] / chips
    ops = work(rows, batch["landmarks"], batch["dim"], batch["clusters"],
               batch["inner_iters"])
    nbytes = traffic(rows, batch["landmarks"], batch["dim"])
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    batches = run.counters.get("batches", ())
    if run.trace is None or run.peaks is None or not batches:
        return None
    spans = module_events(run.trace, lambda n: INNER in n)
    times = [sum(d for _, _, d in s) * 1e-9 for s in spans]
    busy = sum(times) / len(times) if times else 0.0
    if busy <= 0:
        return None
    chips = run.counters.get("chips", 1)
    least = sum(least_seconds(b, chips, run.peaks) for b in batches)
    return 100.0 * least / busy
