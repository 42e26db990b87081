"""One long stream of batches carrying the fit's global state.

Set-up makes a pool of the configuration's batch-sized row blocks from
the seed (host arrays, as ``fit`` takes them) and runs the stream's first
two batches: the first (k-means++ seeding, Eq.7) and one that merges
(Eq.8 init, Eq.12). The window then goes on with the stream's next
batches, cycling the pool; a job is one batch, ``fit([batch], state=...)``
with the state the previous batch left. ``nmi`` is the final state's
medoids assigning a held-out set made from the seed.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from harness import data as bdata
from harness.fit import FitDriver, nmi_of_medoids


class Driver(FitDriver):

    def setup(self):
        from repro.distributed.outer import DistributedMiniBatchKMeans
        cfg, seed = self.ctx.cell.config, self.ctx.seed
        gen, classes = cfg["generator"], int(cfg["classes"])
        p = self.plan(int(cfg["rows"]))
        self.p = p
        rows = int(cfg["rows"]) // p.b
        n_pool = int(cfg["distinct_rows"]) // rows
        if n_pool < 2:
            raise ValueError(f"distinct_rows {cfg['distinct_rows']} hold "
                             f"fewer than two batches of {rows} rows")
        params = bdata.class_params(bdata.key(seed, 1), gen, self.dim,
                                    classes)
        self.pool = [bdata.rows(bdata.key(seed, 2, i), params, rows, gen)[0]
                     for i in range(n_pool)]
        self.heldout = bdata.rows(bdata.key(seed, 4), params,
                                  int(self.ctx.cell.traffic["heldout_rows"]),
                                  gen)
        # sigma = 4 d_max over the rows the stream has when it starts
        self.gamma = bdata.gamma_sigma_rule(self.pool[0],
                                            float(cfg["sigma_factor"]))
        self.ctx.log(f"pool {n_pool} x {self.pool[0].shape}, gamma "
                     f"{self.gamma:.6e}, made by "
                     f"{time.perf_counter() - self.ctx.t_start:.2f}s")
        self._mesh = self.mesh()
        self.km = DistributedMiniBatchKMeans(
            self._mesh, self.base_config(p, bdata.small_seed(seed, 3)),
            mode=p.engine)
        self.tap.install()
        self.state = None
        for i in range(2):          # warm-up: a first and a merging batch
            t0 = time.perf_counter()
            res = self.fit(self.km, [self.pool[i]], self.state)
            self.state = res.state
            self.ctx.log(f"stream batch {i} (set-up): "
                         f"{time.perf_counter() - t0:.2f}s, "
                         f"{res.history[0].inner_iters} inner iterations")
        self.next = 2
        self.records = []

    def job(self, i: int) -> int:
        xb = self.pool[self.next % len(self.pool)]
        self.next += 1
        self.state = self.fit(self.km, [xb], self.state).state
        return len(xb)

    def nmi(self) -> float:
        x, y = self.heldout
        x_dev = jax.device_put(x, self.ctx.devices[0])
        return nmi_of_medoids(x_dev, y, np.asarray(self.state.medoids))
