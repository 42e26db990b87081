"""Online assignment through ``AssignService``: an open loop of requests.

Set-up makes a query pool from the seed, the served model, and the
service. The model is an exact rbf artifact (``serving.freeze`` of a
``FitResult``) whose medoids are pool rows drawn from the seed, one per
class: the benchmark makes it, as a model benchmark makes its weights, so
the reference shares nothing the program made. A share of the pool
(``boundary_share``) lies near the bisector of two medoids, at distances
from it spread over six decades, so that rounding in the served path
shows in the labels. Each bucket program of the ladder is compiled and
run once in set-up.

The window is an open loop, one thread. Request i is due at a fixed time
whatever the service does; its rows are a slice of the pool. The gaps
between requests (exponential, mean 1 / ``rate_rps``) and their row counts
(Zipf(``zipf_a``) capped at ``max_rows``) are one fixed set drawn from
``schedule_seed``; the run's seed shuffles their order and picks the rows,
so every seed offers the same work. A request's latency runs from its due
time to its labels on the host. Requests due in the window are waited
for, up to ``drain_grace_s`` past its close; one refused or never answered
counts as failed.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from harness import data as bdata
from repro.obs.recorder import MetricsRecorder


class SpanRecorder(MetricsRecorder):
    """Keeps the service's ``serve/request`` events in memory."""
    enabled = True

    def __init__(self):
        self.requests: list = []

    def event(self, name: str, **fields) -> None:
        if name == "serve/request":
            self.requests.append(fields)


def schedule(traffic: dict, seconds: float, seed: int):
    """(due times [s], row counts) of the requests due in the window."""
    rate = float(traffic["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    base = np.random.default_rng(int(traffic["schedule_seed"]))
    gaps = base.exponential(1.0 / rate, n)
    gaps *= seconds * n / (n + 1) / gaps.sum()   # the last due inside
    sizes = np.minimum(base.zipf(float(traffic["zipf_a"]), n),
                       int(traffic["max_rows"]))
    run = np.random.default_rng(bdata.seed_words(seed, 5))
    return np.cumsum(run.permutation(gaps)), run.permutation(sizes)


def boundary_rows(medoids: np.ndarray, n: int, rng) -> np.ndarray:
    """Points on the segment between two medoids, off its middle by a
    share spread log-uniformly over [1e-7, 1e-1]."""
    c = len(medoids)
    a = rng.integers(0, c, n)
    b = (a + rng.integers(1, c, n)) % c
    off = 10.0 ** rng.uniform(-7, -1, n) * rng.choice([-1.0, 1.0], n)
    t = (0.5 + off)[:, None]
    return ((1.0 - t) * medoids[a] + t * medoids[b]).astype(np.float32)


class Driver:

    def __init__(self, ctx):
        self.ctx = ctx
        self.traffic = ctx.cell.traffic
        cfg = ctx.cell.config
        self.classes, self.dim = int(cfg["clusters"]), int(cfg["dim"])

    def setup(self):
        from repro.core import KernelSpec
        from repro.core.minibatch import FitResult, GlobalState
        from repro.serving import AssignServeConfig, AssignService, freeze
        cfg, seed, tr = self.ctx.cell.config, self.ctx.seed, self.traffic
        params = bdata.class_params(bdata.key(seed, 1), cfg["generator"],
                                    self.dim, int(cfg["classes"]))
        pool, y = bdata.rows(bdata.key(seed, 2), params,
                             int(tr["pool_rows"]), cfg["generator"])
        rng = np.random.default_rng(bdata.seed_words(seed, 6))
        pick = [rng.choice(np.flatnonzero(y == c)) for c in range(
            self.classes)]
        self.medoids = pool[pick].copy()
        nb = int(round(float(tr["boundary_share"]) * len(pool)))
        where = rng.choice(len(pool), nb, replace=False)
        pool[where] = boundary_rows(self.medoids, nb, rng)
        self.pool = pool
        self.gamma = bdata.gamma_sigma_rule(pool, float(cfg["sigma_factor"]))
        spec = KernelSpec("rbf", gamma=self.gamma)
        m = jax.numpy.asarray(self.medoids)
        state = GlobalState(m, spec.diag(m),
                            jax.numpy.ones((self.classes,)),
                            jax.numpy.array(1, jax.numpy.int32))
        art = freeze(FitResult(state, [], spec=spec))
        self.rec = SpanRecorder() if self.ctx.trace else None
        self.svc = AssignService(
            art, AssignServeConfig(buckets=tuple(tr["buckets"]),
                                   max_queue_rows=int(tr["max_queue_rows"])),
            recorder=self.rec)
        for b in self.svc.cfg.buckets:          # run every program once
            self.svc.predict(pool[:b])
        if self.rec is not None:
            self.rec.requests.clear()
        self.ctx.log(f"served model: exact rbf, {self.classes} medoids, "
                     f"gamma {self.gamma:.6e}, buckets "
                     f"{self.svc.cfg.buckets}, pool {pool.shape}, "
                     f"{nb} boundary rows")

    def window(self, seconds: float):
        from repro.serving.assign import QueueFull
        due, sizes = schedule(self.traffic, seconds, self.ctx.seed)
        rng = np.random.default_rng(bdata.seed_words(self.ctx.seed, 8))
        starts = rng.integers(0, len(self.pool) - sizes.max(), len(due))
        n = len(due)
        grace = float(self.traffic["drain_grace_s"])
        self.lat = np.full(n, np.inf)
        self.labels: dict = {}
        self.starts, self.sizes = starts, sizes
        late, pending, refused = [], {}, 0
        svc, i = self.svc, 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                try:
                    pending[svc.submit(self.pool[starts[i]:starts[i]
                                                 + sizes[i]])] = i
                except QueueFull:
                    refused += 1
                late.append(now - due[i])
                i += 1
            if pending:
                out = svc.step()
                t = time.perf_counter() - t0
                for uid, lab in out.items():
                    j = pending.pop(uid)
                    self.lat[j] = t - due[j]
                    self.labels[j] = lab
            elif i < n:
                time.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
            else:
                break
            if now > seconds + grace:
                break
        self.elapsed = time.perf_counter() - t0
        late = np.asarray(late)
        self.ctx.log(f"open loop: {n} requests due in {seconds:g}s "
                     f"({sizes.sum()} rows), {len(self.labels)} answered, "
                     f"{refused} refused, loop {self.elapsed:.2f}s; "
                     f"generator late p50 {np.median(late) * 1e3:.3f} ms, "
                     f"max {late.max() * 1e3:.3f} ms")
        return self

    def attempted(self) -> tuple[int, int]:
        n = len(self.lat)
        return n, n - len(self.labels)

    def end_to_end(self) -> dict:
        # a failed request misses every limit: its latency is +inf
        return {"assign_p95_ms": float(np.percentile(self.lat, 95)) * 1e3}

    def sweep_info(self) -> dict:
        """Latency percentiles, and the p95 of the window's second half
        against its first: a growing backlog shows as a ratio above 1."""
        half = len(self.lat) // 2
        p = np.percentile(self.lat, [50, 95, 99]) * 1e3
        return {"p50_ms": p[0], "p95_ms": p[1], "p99_ms": p[2],
                "p95_late_over_early": float(
                    np.percentile(self.lat[half:], 95)
                    / np.percentile(self.lat[:half], 95)),
                "loop_s": self.elapsed}

    def counters(self) -> dict:
        return {"serve_requests": list(self.rec.requests)
                if self.rec is not None else []}

    def release(self):
        self.svc = None

    def checks(self) -> dict:
        """Every answered request's labels against the reference's nearest
        medoid, or a sample of ``checked_requests`` drawn from the seed."""
        ref = self.ctx.reference
        gaps, scale, wrong = [], [], 0
        done = sorted(self.labels)
        k = int(self.traffic["checked_requests"])
        if len(done) > k:
            rng = np.random.default_rng(bdata.seed_words(self.ctx.seed, 9))
            done = sorted(rng.choice(done, k, replace=False).tolist())
        for j in done:
            lab = self.labels[j]
            x = self.pool[self.starts[j]:self.starts[j] + self.sizes[j]]
            d2, best = ref.nearest(x, self.medoids)
            rows = np.arange(len(x))
            gaps.append((d2[rows, lab] - d2[rows, best]).max())
            scale.append(d2[rows, best])
            wrong += int(np.sum(lab != best))
        unit = float(np.median(np.concatenate(scale)))
        self.ctx.log(f"checked {len(done)} of {len(self.labels)} answered "
                     f"requests: {wrong} rows labelled off the reference's "
                     f"nearest medoid; unit {unit:.6e}")
        return {"missing_requests": float(len(self.lat) - len(self.labels)),
                "label_gap": float(max(gaps) / unit)}
