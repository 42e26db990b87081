"""What the fit cells share: the planned exact fit, the inner-loop tap, the
records of the window's batches, and the comparison with the reference.

The system under test is ``DistributedMiniBatchKMeans.fit`` with B, s and
the Gram engine from ``plan()`` at the chip's ``bytes_limit`` (the
``launch/cluster.py`` path), on a ("data",) mesh over the cell's chips.

The inner loop's labels never leave ``fit``. ``InnerTap`` keeps them: it
wraps the module attribute ``repro.distributed.outer.distributed_kkmeans_fit``
that the outer loop calls once per batch, holds the landmark indices, labels
and per-cluster kernel means f it returns (device arrays, a few MB a
batch), and changes nothing else. The states after each batch come from ``fit``'s own
``checkpoint_cb``; the reported counts and cost from ``FitResult.history``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import data as bdata
from . import fitcheck
from .quality import nmi
from .window import run_jobs


class InnerTap:
    TARGET = ("repro.distributed.outer", "distributed_kkmeans_fit")

    def __init__(self):
        self.records: list = []
        self._orig = None

    def install(self):
        import importlib
        mod = importlib.import_module(self.TARGET[0])
        orig = getattr(mod, self.TARGET[1])

        def tapped(mesh, x, landmarks, l_idx, diag_k, u0, *, cfg, wgt=None):
            res = orig(mesh, x, landmarks, l_idx, diag_k, u0, cfg=cfg,
                       wgt=wgt)
            self.records.append((l_idx, res.labels, res.f))
            return res

        self._mod, self._orig = mod, orig
        setattr(mod, self.TARGET[1], tapped)

    def remove(self):
        if self._orig is not None:
            setattr(self._mod, self.TARGET[1], self._orig)
            self._orig = None

    def take(self) -> list:
        out, self.records = self.records, []
        return out


@dataclasses.dataclass
class BatchRecord:
    x: np.ndarray          # the batch's rows, as fitted (host)
    l_idx: object          # device array [L]
    labels: object         # device array [n], row-sharded
    f: object              # device array [n, C]: the labels' kernel means
    stats: object          # BatchStats of FitResult.history
    prev: object           # GlobalState before the batch, or None
    new: object            # GlobalState after it


class FitDriver:
    """Set-up, window and check of an exact fit cell. Subclasses give the
    set-up with its warm-up (``setup``), what a job is (``job``) and what
    NMI means (``nmi``)."""

    def __init__(self, ctx):
        self.ctx = ctx
        cfg = ctx.cell.config
        self.classes = int(cfg["clusters"])
        self.dim = int(cfg["dim"])
        self.max_iters = int(cfg["max_inner_iters"])
        self.tap = InnerTap()
        self.records: list[BatchRecord] = []
        self.window_result = None
        self.unread = 0            # batches the tap did not see

    # -- set-up ----------------------------------------------------------------

    def plan(self, rows_total: int):
        from repro.core import MachineSpec, plan
        from repro.core.landmarks import num_landmarks
        from harness.device import bytes_limit
        limit = bytes_limit(self.ctx.devices[0])
        p = plan(rows_total, self.classes,
                 MachineSpec(memory_bytes=limit,
                             n_processors=len(self.ctx.devices)),
                 d=self.dim)
        rows = rows_total // p.b
        live = {"B": p.b, "s": p.s, "engine": p.engine,
                "engine_bytes": p.engine_footprints[p.engine],
                "bytes_limit": limit, "batch_rows": rows,
                "landmarks": num_landmarks(rows, p.s,
                                           n_clusters=self.classes,
                                           multiple_of=len(self.ctx.devices))}
        self.ctx.log(f"plan (live): {live}")
        self.ctx.log(f"plan (at definition): "
                     f"{self.ctx.cell.workload.get('plan_at_definition')}")
        return p

    def mesh(self):
        return jax.make_mesh((len(self.ctx.devices),), ("data",),
                             devices=self.ctx.devices)

    def base_config(self, p, seed: int):
        from repro.core import KernelSpec, MiniBatchConfig
        return MiniBatchConfig(n_clusters=self.classes, n_batches=p.b,
                               s=p.s, kernel=KernelSpec("rbf",
                                                        gamma=self.gamma),
                               max_inner_iters=self.max_iters, seed=seed)

    def fit(self, km, batches, state=None):
        """One ``fit`` call, recorded batch by batch."""
        states = []
        res = km.fit(batches, state=state,
                     checkpoint_cb=lambda st, i: states.append(st))
        jax.block_until_ready(res.state.medoids)
        taps = self.tap.take()
        self.unread += abs(len(taps) - len(res.history))
        prevs = [state] + states[:-1]
        for xb, (l_idx, labels, f), st, prev, new in zip(
                batches, taps, res.history, prevs, states):
            self.records.append(BatchRecord(xb, l_idx, labels, f, st, prev,
                                            new))
        return res

    # -- window ------------------------------------------------------------------

    def window(self, seconds: float):
        self.records = []
        self.window_result = run_jobs(self.job, seconds)
        return self.window_result

    def attempted(self) -> tuple[int, int]:
        return self.window_result.jobs, 0

    def counters(self) -> dict:
        return {"batches": [
            {"rows": len(r.x), "landmarks": int(r.l_idx.shape[0]),
             "dim": self.dim, "clusters": self.classes,
             "inner_iters": int(r.stats.inner_iters)}
            for r in self.records], "chips": len(self.ctx.devices)}

    def release(self):
        self.tap.remove()
        self.km = None

    # -- after the window ----------------------------------------------------------

    def end_to_end(self) -> dict:
        return {"fit_rows_per_s": self.window_result.rows_per_s,
                "nmi": self.nmi()}

    def checks(self) -> list:
        rng = np.random.default_rng(bdata.seed_words(self.ctx.seed, 7))
        k = int(self.ctx.cell.traffic.get("checked_batches", 3))
        pick = self.checked_batches(rng, k)
        t0 = time.perf_counter()
        out = fitcheck.numbers([self.records[i] for i in pick],
                               self.classes, self.gamma,
                               self.ctx.devices[0], self.ctx.reference,
                               self.max_iters)
        self.ctx.log(f"checked batches {pick} of {len(self.records)} in "
                     f"{time.perf_counter() - t0:.2f}s")
        out["batches_unread"] = float(self.unread)
        return out

    def checked_batches(self, rng, k: int) -> list:
        n = len(self.records)
        return sorted(rng.choice(n, size=min(k, n), replace=False).tolist())


@jax.jit
def _nearest(x, medoids):
    with jax.default_matmul_precision("highest"):
        d2 = jnp.sum(medoids * medoids, 1)[None, :] - 2.0 * x @ medoids.T
    return jnp.argmin(d2, axis=1)


def nmi_of_medoids(x_dev, y: np.ndarray, medoids) -> float:
    """NMI against the classes of the partition of x by nearest medoid."""
    m = jax.device_put(np.asarray(medoids, np.float32),
                       next(iter(x_dev.devices())))
    return nmi(y, np.asarray(_nearest(x_dev, m)))
