"""Distributed mini-batch outer loop (paper Alg.1 end-to-end on a mesh).

Host-side orchestration identical to ``repro.core.minibatch`` but every
O(N/B) step runs sharded:

  * Eq.8 init + K~^i           -> row-sharded kernel vs C global medoids
  * inner GD loop              -> repro.distributed.inner (Alg.1 lines 9-16)
  * Eq.7 medoids               -> local argmin + cross-shard min-reduce
                                  (paper line 18 "allreduce min M^i")
  * Eq.12 merge                -> row-sharded score + same min-reduce
                                  (paper line 20 "allreduce min M")

Only O(C*d) state (medoid coordinates, diag, cardinalities) crosses batches,
so checkpoint/restart and elastic re-meshing are trivial: the state is mesh-
independent (repro.ft).

Non-divisible batches are padded with modulo-replicated ghost rows, exactly
like the embedded path — and, like there, the ghosts are weight-masked:
they are never landmark candidates (selection runs over the unpadded rows,
strategy-dispatched via ``cfg.selector`` — uniform / rls / kpp,
``repro.approx.selectors``), never win a medoid/merge argmin, and never
count in the cost, so a P∤(N/B) distributed fit reproduces the single-host
cardinalities and Eq.12 alphas exactly.

Every step of a batch that touches the rows on the device is one compiled
program, built once and reused by every later batch, fit and instance: the
diag and landmark gather (``_diag_program``), Eq.8 (``_init_program``),
the inner loop (``inner._mesh_program``) and Eq.7 + Eq.12
(``_merge_program``, one program for a first batch and one after it).
Arrays are operands, never captured, and the static arguments are the mesh,
the row axes, the ``KernelSpec`` and ``first`` alone; so a program is keyed
on the batch's shapes (C among them), those, and the caller's matmul
precision, never on the fit's seed or ``MiniBatchConfig``. Between them the
host draws the landmarks and, in a first batch, the k-means++ seeds.
"""
from __future__ import annotations

import math
import time
from functools import partial
from typing import Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import resolve_engine
from repro.core.init import kmeans_pp_indices
from repro.core.kernels import KernelSpec
from repro.core.kkmeans import BIG
from repro.core.landmarks import (choose_landmarks, num_landmarks,
                                  select_landmark_indices)
from repro.core.minibatch import BatchStats, FitResult, GlobalState, MiniBatchConfig
from repro.obs import memory as obs_memory
from repro.obs import resolve as resolve_recorder
from repro.obs.trace import span

from .compat import shard_map
from .inner import (DistributedInnerConfig, collectives_per_iteration,
                    distributed_kkmeans_fit)
from .mesh import auto_axes, ghost_row_ids

Array = jax.Array


def _dist_argmin_rows(mesh: Mesh, row_axes, score: Array) -> Array:
    """argmin over the (row-sharded) axis 0 of ``score`` [n, C] -> [C] global
    row indices. Local argmin then a gather+min over shards (the paper's
    allreduce-min with index payload)."""

    def shard_fn(score_local):
        idx = jnp.argmin(score_local, axis=0)                      # [C] local
        val = jnp.min(score_local, axis=0)                         # [C]
        # global row index = shard offset + local index.
        row_rank = jax.lax.axis_index(row_axes)
        gidx = row_rank * score_local.shape[0] + idx
        vals = jax.lax.all_gather(val, row_axes)                   # [D, C]
        gidxs = jax.lax.all_gather(gidx, row_axes)                 # [D, C]
        best = jnp.argmin(vals, axis=0)                            # [C]
        return jnp.take_along_axis(gidxs, best[None, :], axis=0)[0]

    return shard_map(
        shard_fn, mesh=mesh, in_specs=P(row_axes, None), out_specs=P(),
        check_vma=False)(score)


# The batch step's programs (module docstring): static arguments are the
# mesh, the row axes, the kernel and ``first`` only.
_STATIC = ("mesh", "spec", "row_axes")


@partial(jax.jit, static_argnames=_STATIC)
def _diag_program(x, l_idx, *, mesh: Mesh, spec: KernelSpec, row_axes):
    """K(x_i, x_i) of the row-sharded batch, and its landmark rows [L, d]."""
    diag = shard_map(spec.diag, mesh=mesh, in_specs=P(row_axes, None),
                     out_specs=P(row_axes), check_vma=False)(x)
    return diag, jnp.take(x, l_idx, axis=0)


@partial(jax.jit, static_argnames=_STATIC)
def _init_program(x, diag, medoids, mdiag, *, mesh: Mesh, spec: KernelSpec,
                  row_axes):
    """Eq.8 on the mesh: labels u0 against the C medoids, and the
    row-sharded K~^i [n, C] the Eq.12 merge reuses."""

    def shard_fn(x_local, diag_local, medoids, mdiag):
        kt = spec(x_local, medoids).astype(jnp.float32)
        d2 = diag_local.astype(jnp.float32)[:, None] + mdiag[None, :] \
            - 2.0 * kt
        return jnp.argmin(d2, axis=1).astype(jnp.int32), kt

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(row_axes, None), P(row_axes), P(), P()),
        out_specs=(P(row_axes), P(row_axes, None)),
        check_vma=False)(x, diag, medoids, mdiag)


@partial(jax.jit, static_argnames=_STATIC + ("first",))
def _merge_program(x, diag, f, counts, k_tilde, wgt, state: GlobalState, *,
                   mesh: Mesh, spec: KernelSpec, row_axes, first: bool):
    """Eq.7 batch medoids + Eq.12 merge -> (new state, displacement [C]).
    A first batch's medoids are its batch medoids, and ``state`` and
    ``k_tilde`` go unread."""
    ghost = (1.0 - wgt)[:, None] * BIG                        # sharded
    # Eq.7: batch medoid scores.
    score7 = diag.astype(jnp.float32)[:, None] - 2.0 * f + ghost
    batch_medoids = jnp.take(x, _dist_argmin_rows(mesh, row_axes, score7),
                             axis=0)                          # replicated
    if first:
        return (GlobalState(batch_medoids, spec.diag(batch_medoids), counts,
                            jnp.array(1, jnp.int32)),
                jnp.zeros(counts.shape, jnp.float32))
    alpha = counts / jnp.maximum(counts + state.cardinalities, 1.0)

    def score_fn(x_local, diag_local, kt_local, batch_medoids, alpha):
        kxm = spec(x_local, batch_medoids).astype(jnp.float32)
        return (diag_local.astype(jnp.float32)[:, None]
                - 2.0 * (1.0 - alpha)[None, :] * kt_local
                - 2.0 * alpha[None, :] * kxm)

    score12 = shard_map(
        score_fn, mesh=mesh,
        in_specs=(P(row_axes, None), P(row_axes), P(row_axes, None), P(),
                  P()),
        out_specs=P(row_axes, None), check_vma=False)(
            x, diag, k_tilde, batch_medoids, alpha) + ghost
    merged = jnp.take(x, _dist_argmin_rows(mesh, row_axes, score12), axis=0)
    keep = counts == 0
    medoids = jnp.where(keep[:, None], state.medoids, merged)
    mdiag = jnp.where(keep, state.medoid_diag, spec.diag(merged))
    cross = jax.vmap(lambda a, b: spec(a[None], b[None])[0, 0])(
        medoids, state.medoids)
    disp = jnp.maximum(mdiag + state.medoid_diag - 2.0 * cross, 0.0)
    return (GlobalState(medoids, mdiag, state.cardinalities + counts,
                        state.batches_done + 1), disp)


class DistributedMiniBatchKMeans:
    """Mesh-resident mini-batch kernel k-means (the production entry point)."""

    def __init__(self, mesh: Mesh, cfg: MiniBatchConfig, *,
                 mode: object = None, recorder=None):
        """``mode`` names the GramEngine of the inner loop — "materialize" |
        "fused" | "tiled" or a ``repro.core.engine.GramEngine`` instance;
        default: whatever ``cfg.engine`` says (itself "materialize" unless
        the planner picked otherwise). ``recorder`` is a ``repro.obs``
        flight recorder; all its hooks run host-side between the jitted
        mesh programs (the collective bill inside the inner while_loop is
        counted *statically* — ``repro.analysis.collective_bill`` walks the
        traced jaxpr once per batch shape and the recorder multiplies the
        per-iteration count by the returned n_iter, plus the audited
        outside-the-loop prologue sync — never by instrumenting the traced
        body; ``inner.collectives_per_iteration`` stays as the analytic
        cross-check the audit must agree with)."""
        mesh = self.mesh = auto_axes(mesh)
        self.cfg = cfg
        self.rec = resolve_recorder(recorder)
        row_axes = tuple(n for n in mesh.axis_names if n != "model")
        col_axis = "model" if "model" in mesh.axis_names else None
        self.row_axes = row_axes
        self.col_axis = col_axis
        self.d_size = math.prod(mesh.shape[a] for a in row_axes)
        self.m_size = mesh.shape[col_axis] if col_axis else 1
        self.inner_cfg = DistributedInnerConfig(
            n_clusters=cfg.n_clusters, kernel=cfg.kernel,
            max_iters=cfg.max_inner_iters,
            engine=resolve_engine(cfg.engine if mode is None else mode),
            precision=getattr(cfg, "precision", "f32"),
            row_axes=row_axes, col_axis=col_axis,
            s_step=getattr(cfg, "s_step", 1))
        self._row_sharding = NamedSharding(mesh, P(row_axes, None))
        self._bill_cache: dict = {}
        self._fits = 0                 # serial of the next fit (span tag)

    # -- helpers -----------------------------------------------------------

    def _audited_bill(self, x, landmarks, l_idx, diag, u0, wgt):
        """Statically-audited collective bill of the inner mesh program
        (``repro.analysis.collective_bill`` over the traced jaxpr), cached
        per batch shape. Falls back to the analytic
        ``collectives_per_iteration`` (recording an ``audit_error`` event)
        if tracing fails — billing must never take the fit down."""
        key = (x.shape, landmarks.shape, str(x.dtype))
        bill = self._bill_cache.get(key)
        if bill is None:
            from repro.analysis import collective_bill
            try:
                bill = collective_bill(
                    lambda xa, lm, li, dg, u, w: distributed_kkmeans_fit(
                        self.mesh, xa, lm, li, dg, u, cfg=self.inner_cfg,
                        wgt=w),
                    x, landmarks, l_idx, diag, u0, wgt,
                    name="distributed_inner")
            except Exception as e:   # pragma: no cover - defensive
                self.rec.event("audit_error", where="distributed_inner",
                               error=repr(e))
                analytic = collectives_per_iteration(
                    self.inner_cfg, x.shape[0] // self.d_size)
                # analytic equivalent of the audited bill: one fused
                # allgather+psum sync per while-loop body, and the same
                # pair once outside (the prologue that seeds the carry —
                # there is no fixpoint epilogue any more).
                bill = {
                    "per_iteration": {"psum": analytic["psum"],
                                      "all_gather": analytic["allgather"]},
                    "outside": {"psum": analytic["psum"],
                                "all_gather": analytic["allgather"]},
                    "per_iteration_bytes": {"psum": analytic["psum_bytes"]},
                    "outside_bytes": {"psum": analytic["psum_bytes"]},
                }
            self._bill_cache[key] = bill
        return bill

    def _put_rows(self, x: np.ndarray) -> Array:
        return jax.device_put(jnp.asarray(x), self._row_sharding)

    def _landmark_count(self, n: int) -> int:
        return num_landmarks(
            n, self.cfg.s, n_clusters=self.cfg.n_clusters,
            multiple_of=int(np.lcm(self.d_size, self.m_size)))

    def _choose_landmarks(self, key, xb: np.ndarray, n_pad: int):
        """(l_idx, |L|) for one batch of ``n = len(xb)`` real rows padded
        by ``n_pad`` ghost rows.

        Landmarks are selected over the UNPADDED rows (strategy-dispatched:
        ``cfg.selector``): a ghost row is a modulo-replicated real row, and
        letting it into the landmark set double-counts its point in the
        Eq.14 expansion, the cardinalities and the Eq.12 alpha — the old
        O(P/(N/B)) ghost-row bias. Only when the batch is smaller than the
        landmark alignment itself (a tail batch under the mesh size) do we
        fall back to the padded row space, where the <= P-1 duplicated
        landmarks are unavoidable (documented residual bias).
        """
        n = len(xb)
        mult = int(np.lcm(self.d_size, self.m_size))
        if n >= mult:
            n_l = self._landmark_count(n)
            l_idx = select_landmark_indices(
                key, jnp.asarray(xb, jnp.float32), n_l, self.cfg.kernel,
                selector=self.cfg.selector)
        else:
            n_l = self._landmark_count(n + n_pad)
            l_idx = choose_landmarks(key, n + n_pad, n_l)
        return l_idx, n_l

    def _medoid_merge(self, x: Array, diag: Array, res, k_tilde, state,
                      first: bool, wgt: Array):
        """Eq.7 batch medoids + Eq.12 merge, both via distributed argmin
        (``_merge_program``).

        ``wgt`` is 0 on ghost rows: masking them out of both argmins keeps
        the selected row *indices* identical to the single-host run (a
        ghost duplicate would otherwise be able to win a tie at a higher
        index).
        """
        return _merge_program(x, diag, res.f, res.counts, k_tilde, wgt, state,
                              mesh=self.mesh, spec=self.cfg.kernel,
                              row_axes=self.row_axes, first=first)

    # -- driver -------------------------------------------------------------

    def fit(self, batches: Iterable[np.ndarray], *,
            state: Optional[GlobalState] = None,
            checkpoint_cb=None) -> FitResult:
        """Run the outer loop over ``batches``. Host spans name its steps
        on the profiler's clock (``repro.obs.trace.span``; records too
        when the recorder is enabled): ``outer/fit`` (tag ``fit``, this
        instance's fit serial), ``outer/batch`` (``fit``, ``batch``,
        ``rows``) and, covering each batch in program order,
        ``outer/stage``, ``outer/landmarks``, ``outer/diag``,
        ``outer/seed`` (first batch only), ``outer/init``, ``outer/inner``,
        ``outer/merge``, ``outer/sync`` (the blocking reads),
        ``outer/checkpoint`` (with ``checkpoint_cb``) and ``outer/record``
        (recorder only)."""
        cfg = self.cfg
        rec = self.rec
        monitor = None
        if rec.enabled:
            from repro.ft.straggler import StragglerMonitor
            monitor = StragglerMonitor(rec)
        key = jax.random.PRNGKey(cfg.seed)
        history: list[BatchStats] = []
        start = int(state.batches_done) if state is not None else 0
        fit_no = self._fits
        self._fits += 1

        with span("outer/fit", rec, fit=fit_no):
            for i, xb in enumerate(batches, start=start):
                tags = dict(fit=fit_no, batch=i)
                with span("outer/batch", rec, rows=len(xb), **tags):
                    state = self._batch(i, xb, key, state, history,
                                        checkpoint_cb, monitor, tags)
                rec.batch_boundary(i)
            if state is None:
                raise ValueError("empty batch iterable")
        return FitResult(state, history, spec=cfg.kernel)

    def _batch(self, i: int, xb, key, state, history, checkpoint_cb,
               monitor, tags: dict) -> GlobalState:
        """One batch of ``fit``: each step under its own span."""
        cfg = self.cfg
        spec = cfg.kernel
        rec = self.rec
        t_batch = time.perf_counter()
        with span("outer/stage", rec, **tags):
            xb = unpadded = np.asarray(xb, np.float32)
            n = len(xb)
            idx = ghost_row_ids(n, self.d_size)
            if len(idx):
                # Replicate head rows so shapes divide the mesh; ``wgt``
                # masks them out of the cost and both medoid argmins, and
                # they can no longer be landmarks, so cardinalities and
                # the Eq.12 alpha match the single-host run exactly.
                xb = np.concatenate([xb, xb[idx]], axis=0)
            wgt_host = np.ones((len(xb),), np.float32)
            wgt_host[n:] = 0.0
            x = self._put_rows(xb)
            wgt = jax.device_put(wgt_host,
                                 NamedSharding(self.mesh, P(self.row_axes)))
        with span("outer/landmarks", rec, **tags):
            # pure per-batch schedule — batch i's draws depend only on
            # (cfg.seed, i), so a checkpoint-resumed fit replays the same
            # landmarks as the uninterrupted run (same fix as
            # core/minibatch.fit and the embedded path).
            k_lm, k_pp = jax.random.split(jax.random.fold_in(key, i))
            # landmark selection over the UNPADDED rows (ghost-bias fix;
            # see _choose_landmarks).
            l_idx, n_l = self._choose_landmarks(k_lm, unpadded, len(idx))
        progs = dict(mesh=self.mesh, spec=spec, row_axes=self.row_axes)
        with span("outer/diag", rec, **tags):
            diag, landmarks = _diag_program(x, l_idx, **progs)

        first = state is None
        if first:
            with span("outer/seed", rec, **tags):
                # distributed adaptation: k-means++ seeds FROM THE LANDMARK
                # SET (the subspace centroids live in anyway, §3.2) — keeps
                # the D^2 sampling single-pass and mesh-local.
                seeds = kmeans_pp_indices(
                    landmarks, spec.diag(landmarks), k_pp,
                    n_clusters=cfg.n_clusters, spec=spec)
                seed_x = jnp.take(landmarks, seeds, axis=0)
        with span("outer/init", rec, **tags):
            if first:
                state_in = GlobalState(seed_x, spec.diag(seed_x),
                                       jnp.zeros((cfg.n_clusters,)),
                                       jnp.array(0, jnp.int32))
            else:
                state_in = state
            u0, k_tilde = _init_program(x, diag, state_in.medoids,
                                        state_in.medoid_diag, **progs)

        with span("outer/inner", rec, **tags):
            res = distributed_kkmeans_fit(
                self.mesh, x, landmarks, l_idx, diag, u0, cfg=self.inner_cfg,
                wgt=wgt)
        with span("outer/merge", rec, **tags):
            state, disp = self._medoid_merge(x, diag, res, k_tilde, state_in,
                                             first, wgt)
        with span("outer/sync", rec, **tags):
            history.append(BatchStats(
                inner_iters=int(res.n_iter), cost=float(res.cost),
                displacement=np.asarray(disp),
                counts=np.asarray(res.counts)))
        if checkpoint_cb is not None:
            with span("outer/checkpoint", rec, **tags):
                checkpoint_cb(state, i)
        if rec.enabled:
            with span("outer/record", rec, **tags):
                dt = time.perf_counter() - t_batch
                n_iter = history[-1].inner_iters
                # statically-audited bill: per-sync count x n_iter loop
                # sweeps + the audited outside-the-loop collectives (the
                # prologue sync that seeds the s-step carry; the old
                # fixpoint epilogue is gone — the pipelined body syncs
                # the stats of the labels it just wrote).
                bill = self._audited_bill(x, landmarks, l_idx, diag, u0,
                                          wgt)
                per, out = bill["per_iteration"], bill["outside"]
                per_b = bill["per_iteration_bytes"]
                out_b = bill["outside_bytes"]
                rec.counter("collectives/psum",
                            per.get("psum", 0) * n_iter
                            + out.get("psum", 0), batch=i)
                rec.counter("collectives/allgather",
                            per.get("all_gather", 0) * n_iter
                            + out.get("all_gather", 0), batch=i)
                rec.counter("collectives/psum_bytes",
                            per_b.get("psum", 0) * n_iter
                            + out_b.get("psum", 0), batch=i)
                rec.series("batch/wall_seconds", dt, batch=i, rows=n)
                rec.series("inner/cost", history[-1].cost, batch=i)
                rec.series("inner/iters", n_iter, batch=i)
                obs_memory.watermark(
                    rec, batch=i, engine=self.inner_cfg.engine.mode,
                    predicted_bytes=obs_memory.predicted_batch_footprint(
                        cfg, len(xb), xb.shape[1], n_devices=self.d_size))
                # single controller: all devices advance in lock-step, so
                # the timing unit is this process (a multi-host launch
                # contributes one per host).
                monitor.observe(i, {jax.process_index(): dt}, n_rows=len(xb))
        return state
