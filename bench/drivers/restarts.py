"""Whole exact fits of one data set, repeated as k-means restarts.

Set-up makes the configuration's rows from the seed, plans (B, s, engine),
splits the rows into B stride batches and runs one fit to warm every
program. Restart i of the window fits the same batches with the fit seed
derived from (seed, i), so each restart draws its own landmarks and
k-means++ seeds. A job is one whole fit; ``nmi`` is the mean over the
window's fits of the NMI of its final medoids assigning the fitted rows.
"""
from __future__ import annotations

import jax
import numpy as np

from harness import data as bdata
from harness.fit import FitDriver, nmi_of_medoids


class Driver(FitDriver):

    def setup(self):
        cfg, seed = self.ctx.cell.config, self.ctx.seed
        n = int(cfg["rows"])
        params = bdata.class_params(bdata.key(seed, 1), cfg["generator"],
                                    self.dim, int(cfg["classes"]))
        self.x, self.y = bdata.rows(bdata.key(seed, 2), params, n,
                                    cfg["generator"])
        self.gamma = bdata.gamma_sigma_rule(self.x,
                                            float(cfg["sigma_factor"]))
        self.ctx.log(f"data {self.x.shape} gamma {self.gamma:.6e}")
        p = self.plan(n)
        self.p = p
        self.batches = [self.x[b::p.b] for b in range(p.b)]
        self._mesh = self.mesh()
        self.fits = []
        self.tap.install()
        self._fit(-1)               # warm-up: every program of a fit
        self.ctx.log("warm-up fit done")
        self.records, self.fits = [], []

    def _fit(self, i: int):
        from repro.distributed.outer import DistributedMiniBatchKMeans
        cfg = self.base_config(self.p, bdata.small_seed(self.ctx.seed, 3,
                                                        i + 1))
        km = DistributedMiniBatchKMeans(self._mesh, cfg, mode=self.p.engine)
        res = self.fit(km, self.batches)
        self.fits.append(np.asarray(res.state.medoids))
        return res

    def job(self, i: int) -> int:
        self._fit(i)
        return len(self.x)

    def nmi(self) -> float:
        x_dev = jax.device_put(self.x, self.ctx.devices[0])
        return float(np.mean([nmi_of_medoids(x_dev, self.y, m)
                              for m in self.fits]))
