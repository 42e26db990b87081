"""Distributed embedded-space (RFF/Nystrom/sketch) mini-batch k-means.

The explicit feature map makes the heavy step embarrassingly parallel: each
device embeds only its own rows, z = phi_m(x_local), and the Lloyd sweep
needs exactly ONE collective per iteration — a psum of the per-cluster
partial sums and counts, C*(m+1) floats. Compare Alg.1's inner loop, which
allgathers the full label vector U (N/(B*P) ints) *and* psums g every
iteration: the embedded path communicates O(C*m) independent of the batch
size, strictly less whenever C*m < N/B (always, in the paper's regimes).

Row padding (to divide the mesh) is weight-masked rather than replicated, so
padded rows never bias the centroid means.

Ingestion is staged: ``stage`` turns a raw host batch — dense [n, d] rows
OR a ``repro.data.sparse.CSRBatch`` — into a mesh-resident ``StagedBatch``
whose leaves were ``device_put`` with the row ``NamedSharding``, so the H2D
copy lands pre-sharded. A CSR batch is row-split on the host with the
``slice_rows``/``take_rows`` indptr surgery (``shard_csr`` is the tested
reference form of that split; ``_stage_csr`` additionally replicates
weight-masked ghost rows and writes shards straight into flat staging
buffers) and each device runs the O(nnz) count-sketch on its own shard
inside shard_map
(``repro.approx.sketch.count_sketch_features_csr`` — the jnp twin of the
Pallas scatter-add kernel in ``kernels/sketch_assign.py``, which consumes
dense row tiles and therefore serves the dense/predict path). No [n, d]
dense array exists anywhere between disk and device. ``source`` wraps a raw
batch iterable in a ``BatchSource`` that runs ``stage`` in a background
prefetch thread (the paper's §3.3 producer/consumer offload).

Host-side outer loop mirrors ``repro.approx.embed_kmeans.fit_embedded``:
O(C*m) state across batches, exact Eq.12-style convex merge (no medoid
re-approximation — centroids are explicit vectors here).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.approx.embed_kmeans import EmbedState, assign_embedded
from repro.core.init import kmeans_pp_indices
from repro.core.kernels import KernelSpec
from repro.core.kkmeans import BIG
from repro.core.minibatch import BatchStats, FitResult, MiniBatchConfig
from repro.data.loader import BatchSource
from repro.data.sparse import (CSRBatch, concat_csr, is_sparse, slice_rows,
                               take_rows)
from repro.obs import memory as obs_memory
from repro.obs import resolve as resolve_recorder
from repro.obs.trace import span

from .compat import shard_map
from .mesh import auto_axes, axis_size, ghost_row_ids, row_axes_of

Array = jax.Array

_LINEAR = KernelSpec("linear")


@dataclasses.dataclass(frozen=True)
class StagedBatch:
    """A mini-batch already resident on the mesh, row-sharded.

    Dense: ``x`` [P*rows, d] with spec (rows, None). CSR: the three CSR
    leaves flattened shard-major — device k owns shard k's slice of
    ``data``/``indices`` [P*cap] and ``indptr`` [P*(rows+1)] — so a
    shard_map body can rebuild its local ``CSRBatch`` with static shape
    (rows, d). ``wgt`` [P*rows] is 0 on padded rows (they never bias
    centroids). ``n`` is the logical (unpadded) row count.
    """

    wgt: Array
    n: int
    rows: int                 # rows per shard
    d: int
    x: Optional[Array] = None
    data: Optional[Array] = None
    indices: Optional[Array] = None
    indptr: Optional[Array] = None
    cap: int = 0              # nnz capacity per shard

    @property
    def sparse(self) -> bool:
        return self.x is None

    def __len__(self) -> int:
        return self.n


def _shard_lloyd(z_local, wgt_local, centroids0, mask0, *, row_axes,
                 n_clusters: int, max_iters: int):
    """Per-shard Lloyd body: local assign, ONE fused psum per iteration.

    The body is pipelined like ``distributed.inner``: it assigns from the
    CARRIED centroids/counts, then syncs the stats of the labels it just
    wrote — sums [C, m], counts [C], convergence flag and cost all ride a
    single flat ``concat`` psum of C*(m+1) + 2 floats. A prologue sync
    (same fused payload, dummy scalars) seeds the carry from the warm-start
    labels, so the stats in the carry always describe the final labels and
    no fixpoint ``means`` pass is needed after the loop.

    Deliberate semantic change vs. the pre-fused loop: the convergence
    count weights label flips by ``wgt_local``, so padded/ghost rows no
    longer count toward 'changed' (the historical count was unweighted).
    Ghost rows never enter the weighted stats, so a flip on one cannot
    move a centroid — stopping on real-row flips only can only end the
    loop earlier, never with different centroids."""
    m = z_local.shape[1]

    def sync(labels, changed_f, cost_loc):
        with jax.named_scope("obs:psum_fused"):
            h = jax.nn.one_hot(labels, n_clusters, dtype=jnp.float32)
            h = h * wgt_local[:, None]                   # padded rows -> 0
            sums_p = jax.lax.dot_general(h, z_local.astype(jnp.float32),
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
            counts_p = jnp.sum(h, axis=0)
            flat = jax.lax.psum(
                jnp.concatenate([sums_p.ravel(), counts_p,
                                 jnp.stack([changed_f, cost_loc])]),
                row_axes)                                # [C*(m+1) + 2]
        sums = flat[:n_clusters * m].reshape(n_clusters, m)
        counts = flat[n_clusters * m:-2]
        cents = sums / jnp.maximum(counts, 1.0)[:, None]
        return cents, counts, flat[-2] > 0, flat[-1]

    def body(state):
        labels, cents, counts, _, t, _ = state
        new_labels, mind = assign_embedded(z_local, cents, counts)
        changed_f = jnp.sum((new_labels != labels).astype(jnp.float32)
                            * wgt_local)
        cost_loc = jnp.sum(mind * wgt_local)
        cents, counts, changed, cost = sync(new_labels, changed_f, cost_loc)
        return new_labels, cents, counts, changed, t + 1, cost

    def cond(state):
        _, _, _, changed, t, _ = state
        return jnp.logical_and(changed, t < max_iters)

    # init: nearest centroid0 (masked like the single-device warm start).
    # f32 upcast: z_local may be a bf16 tile under the precision policy.
    z32 = z_local.astype(jnp.float32)
    d2 = (jnp.sum(z32 ** 2, axis=1)[:, None]
          + jnp.sum(centroids0 ** 2, axis=1)[None, :]
          - 2.0 * z32 @ centroids0.T)
    d2 = jnp.where(mask0[None, :], d2, BIG)
    labels0 = jnp.argmin(d2, axis=1).astype(jnp.int32)

    # prologue sync: seed the carry with means(labels0) — the dummy scalars
    # are overridden (changed := True, cost := inf) before the carry forms.
    cents0, counts0, _, _ = sync(labels0, jnp.float32(0), jnp.float32(0))
    init = (labels0, cents0, counts0, jnp.array(True),
            jnp.array(0, jnp.int32), jnp.array(jnp.inf, jnp.float32))
    labels, cents, counts, _, t, cost = jax.lax.while_loop(cond, body, init)
    return labels, cents, counts, t, cost


def collectives_per_iteration(n_clusters: int, m: int) -> dict:
    """Analytic per-Lloyd-iteration collective bill of ``_shard_lloyd``
    (the jit-safe count — see ``distributed.inner.collectives_per_iteration``
    for why it is computed instead of instrumented): ONE fused psum of
    sums + counts + convergence flag + cost, payload C*(m+1) + 2 floats.
    The prologue sync before the loop is the same fused payload
    (``final_psum`` keeps its historical name for the outside-the-loop
    slot in the audited bill)."""
    payload = 4 * (n_clusters * (m + 1) + 2)
    return {"psum": 1, "psum_bytes": payload,
            "final_psum": 1, "final_psum_bytes": payload}


def _nystrom_from_rows(x: jax.Array, idx: jax.Array, spec: KernelSpec):
    """Nystrom map from the rows ``idx`` of a mesh-resident batch. Only
    those m rows come through the host: the map is built on one device,
    since a Mosaic kernel cannot be partitioned by XLA."""
    from repro.approx import nystrom_from_landmarks
    landmarks = jax.device_get(jnp.take(x, idx, axis=0))
    return nystrom_from_landmarks(jnp.asarray(landmarks), spec)


class DistributedEmbedKMeans:
    """Mesh-resident embedded-space mini-batch k-means.

    ``fmap`` may be passed pre-sampled (resume / multi-host determinism) or
    is sampled from the first batch per ``cfg.method`` / ``cfg.embed_dim``.
    """

    def __init__(self, mesh: Mesh, cfg: MiniBatchConfig, *, fmap=None,
                 recorder=None):
        if cfg.method == "exact":
            raise ValueError("DistributedEmbedKMeans needs an embedded "
                             "cfg.method ('rff', 'nystrom', 'sketch', "
                             "'tensorsketch'); use "
                             "DistributedMiniBatchKMeans for 'exact'")
        mesh = self.mesh = auto_axes(mesh)
        self.cfg = cfg
        self.fmap = fmap
        # repro.obs flight recorder; hooks are host-side only. ``stage``
        # also records through it FROM THE PREFETCH PRODUCER THREAD, which
        # is why JsonlRecorder takes a lock.
        self.rec = resolve_recorder(recorder)
        self.row_axes = row_axes_of(mesh)
        self.d_size = axis_size(mesh, self.row_axes)
        self._row_sharding = NamedSharding(mesh, P(self.row_axes, None))
        self._vec_sharding = NamedSharding(mesh, P(self.row_axes))
        # mesh programs are built once and jitted: a streaming fit calls
        # them once per mini-batch, and rebuilding the shard_map wrapper
        # each call would re-trace (and re-compile) every batch.
        self._embed_fns: dict = {}
        self._bill_cache: dict = {}
        fn = partial(_shard_lloyd, row_axes=self.row_axes,
                     n_clusters=cfg.n_clusters,
                     max_iters=cfg.max_inner_iters)
        rowspec = P(self.row_axes)
        self._lloyd_fn = jax.jit(shard_map(
            lambda z, w, c, mk: fn(z, w, c, mk),
            mesh=self.mesh,
            in_specs=(P(self.row_axes, None), rowspec, P(None, None), P()),
            out_specs=(rowspec, P(), P(), P(), P()),
            check_vma=False))

    def _ensure_fmap(self, sample):
        """Sample the feature map from a batch (dense rows or CSRBatch); a
        pre-staged first batch passes a structural sample instead — enough
        for the data-oblivious maps (sketch/tensorsketch/RFF read only d).

        Nystrom on a staged dense batch selects its landmarks on the mesh,
        over the UNPADDED rows, so it sees exactly what the inline path's
        raw batch gives it (ghost rows must not alter the model): for
        ``selector="rls"`` from per-device partial leverage sketches and
        one psum (``_make_nystrom_rls``). Only the m landmark rows leave
        the mesh — no second pass over the stream, no host-side gather of
        the batch.
        """
        if self.fmap is None:
            from repro import approx
            from repro.approx import selectors
            cfg = self.cfg
            m = cfg.embed_dim or approx.default_embed_dim(cfg.n_clusters)
            if isinstance(sample, StagedBatch):
                if cfg.method == "nystrom" and not sample.sparse:
                    if selectors.name_of(cfg.selector) == "rls":
                        self.fmap = self._make_nystrom_rls(sample, m)
                    else:
                        idx = selectors.resolve(cfg.selector).select_indices(
                            jax.random.PRNGKey(cfg.seed),
                            sample.x[:sample.n], m, cfg.kernel)
                        self.fmap = _nystrom_from_rows(sample.x, idx,
                                                       cfg.kernel)
                    return self.fmap
                # the data-oblivious maps read only the column count
                sample = (CSRBatch(data=np.zeros((0,), np.float32),
                                   indices=np.zeros((0,), np.int32),
                                   indptr=np.zeros((1,), np.int32),
                                   shape=(0, sample.d))
                          if sample.sparse
                          else jax.ShapeDtypeStruct((sample.n, sample.d),
                                                    jnp.float32))
            self.fmap = approx.make_feature_map(
                cfg.method, jax.random.PRNGKey(cfg.seed), sample, m,
                cfg.kernel, orthogonal=cfg.rff_orthogonal,
                selector=cfg.selector)
        return self.fmap

    def _make_nystrom_rls(self, st: "StagedBatch", m: int):
        """Mesh-native ridge-leverage-score Nystrom from a staged batch.

        Same draws and estimator as the single-host ``RLSSelector`` (pilot
        and Gumbel keys are fold_in-keyed per global row id), but the
        [m, m] leverage sketch G = C^T diag(wgt) C is assembled from
        per-device partials with ONE psum and the scores are computed
        shard-locally — no device ever sees another shard's rows, and the
        already-staged batch is reused for the embedding right after.
        """
        from repro.approx import selectors

        cfg = self.cfg
        spec = cfg.kernel
        sel = selectors.resolve(cfg.selector)
        key = jax.random.PRNGKey(cfg.seed)
        gids = jnp.arange(st.n, dtype=jnp.int32)
        pilot = jnp.take(st.x, sel.pilot_indices(key, gids, m), axis=0)
        whiten = selectors.pilot_whitening(pilot, spec, eps=sel.eps)

        def shard_fn(x_local, wgt_local, pilot, whiten):
            c = jnp.dot(spec(x_local, pilot).astype(jnp.float32), whiten,
                        preferred_element_type=jnp.float32)   # [rows, m]
            # per-device partial leverage sketch, combined with one psum
            g = jax.lax.psum(
                jax.lax.dot_general(c, c * wgt_local[:, None],
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32),
                self.row_axes)                                # [m, m]
            scores = selectors.rls_scores(c, spec.diag(x_local), g,
                                          delta=sel.delta)
            return jnp.where(wgt_local > 0, scores, 0.0)      # mask ghosts

        scores = jax.jit(shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(P(self.row_axes, None), P(self.row_axes),
                      P(None, None), P(None, None)),
            out_specs=P(self.row_axes), check_vma=False))(
                st.x, st.wgt, pilot, whiten)
        idx = sel.gumbel_top_m(key, scores[:st.n], gids, m)
        return _nystrom_from_rows(st.x, idx, spec)

    # -- staging: host batch -> mesh-resident, pre-sharded -----------------

    def stage(self, xb) -> "StagedBatch":
        """Pad + shard + device_put one raw batch (dense or CSR). Runs on
        the host (a PrefetchLoader producer thread via ``source``, or inline
        in ``fit``); the H2D copies land pre-sharded on the mesh."""
        if isinstance(xb, StagedBatch):
            return xb
        # host span (and a ``span`` record when recording): staging usually
        # runs on the prefetch producer thread, so the trace shows whether
        # H2D staging overlaps the consumer's compute (the point of §3.3).
        with span("obs:stage", self.rec):
            if is_sparse(xb):
                return self._stage_csr(xb)
            return self._stage_dense(np.asarray(xb, np.float32))

    def _wgt(self, n: int, pad: int) -> np.ndarray:
        wgt = np.ones((n + pad,), np.float32)
        if pad:
            wgt[n:] = 0.0
        return wgt

    def _stage_dense(self, xb: np.ndarray) -> "StagedBatch":
        n = len(xb)
        idx = ghost_row_ids(n, self.d_size)
        pad = len(idx)
        if pad:   # replicate head rows so ghost rows are real points ...
            xb = np.concatenate([xb, xb[idx]], axis=0)
        wgt = self._wgt(n, pad)   # ... but weight-masked out of the means
        # device_put straight from the HOST array: routing through
        # jnp.asarray would commit the whole batch to the default device
        # first and reshard device-to-device — the copy must land sharded.
        x = jax.device_put(xb, self._row_sharding)
        return StagedBatch(
            wgt=jax.device_put(wgt, self._vec_sharding),
            n=n, rows=(n + pad) // self.d_size, d=xb.shape[1], x=x)

    def _stage_csr(self, xb: CSRBatch) -> "StagedBatch":
        n, d = xb.shape
        idx = ghost_row_ids(n, self.d_size)
        pad = len(idx)
        wgt = self._wgt(n, pad)
        # Shard the PADDED row space [batch ++ ghost rows] directly: pieces
        # are views (slice_rows) except where a shard straddles the ghost
        # boundary — this is the prefetch producer's hot path, and a
        # concat-then-reshard would copy every stored value twice.
        rows = (n + pad) // self.d_size
        pieces = []
        for k in range(self.d_size):
            a, z = k * rows, (k + 1) * rows
            if z <= n:
                pieces.append(slice_rows(xb, a, z))
            elif a >= n:
                pieces.append(take_rows(xb, idx[a - n:z - n]))
            else:
                pieces.append(concat_csr([slice_rows(xb, a, n),
                                          take_rows(xb, idx[:z - n])]))
        # nnz capacity quantized (geometric, ~12.5% max slack) so a long
        # stream of ragged batches maps to a handful of leaf shapes — each
        # distinct cap is a fresh trace + compile of the memoized embed
        # program otherwise.
        est = max(256, xb.nnz // self.d_size)   # lower bound on shard cap
        quantum = max(256, 1 << max(0, est.bit_length() - 3))
        stored = [int(np.asarray(p.indptr)[-1]) for p in pieces]
        cap = -(-max(stored) // quantum) * quantum
        # shard payloads are written straight into the flat [P*cap] staging
        # buffers — the one O(nnz) copy this path pays.
        p_ = self.d_size
        data_g = np.zeros((p_ * cap,), np.float32)
        idx_g = np.zeros((p_ * cap,), np.int32)
        ptr_g = np.empty((p_ * (rows + 1),), np.int32)
        for k, p in enumerate(pieces):
            s = stored[k]
            data_g[k * cap:k * cap + s] = np.asarray(p.data)[:s]
            idx_g[k * cap:k * cap + s] = np.asarray(p.indices)[:s]
            ptr_g[k * (rows + 1):(k + 1) * (rows + 1)] = \
                np.asarray(p.indptr, dtype=np.int32)
        put = lambda a: jax.device_put(a,   # noqa: E731  (host array in:
            self._vec_sharding)             # the H2D copy lands sharded)
        return StagedBatch(
            wgt=put(wgt), n=n, rows=rows, d=d,
            data=put(data_g), indices=put(idx_g),
            indptr=put(ptr_g), cap=cap)

    def source(self, batches: Iterable, *, depth: int = 2,
               skip: int = 0) -> BatchSource:
        """Wrap raw batches in a ``BatchSource`` whose background producer
        stages each one onto this mesh (pre-sharded H2D overlap, §3.3)."""
        return BatchSource(batches, stage=self.stage, prefetch=depth,
                           skip=skip, recorder=self.rec)

    # -- per-device embedding ----------------------------------------------

    def _embed_fn(self, kind_key):
        """Memoized jitted shard_map program for one batch geometry. The
        feature map rides in as a (replicated) pytree ARGUMENT, not a
        closure, so the callable — and its compile cache — survives across
        batches and fmap updates."""
        if kind_key not in self._embed_fns:
            rowvec = P(self.row_axes)
            if kind_key[0] == "csr":
                _, rows, d = kind_key

                def shard_fn(fmap, data, indices, indptr):
                    local = CSRBatch(data=data, indices=indices,
                                     indptr=indptr, shape=(rows, d))
                    return fmap(local).astype(jnp.float32)

                in_specs = (P(), rowvec, rowvec, rowvec)
            else:
                shard_fn = lambda fmap, xl: (  # noqa: E731
                    fmap(xl).astype(jnp.float32))
                in_specs = (P(), P(self.row_axes, None))
            self._embed_fns[kind_key] = jax.jit(shard_map(
                shard_fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=P(self.row_axes, None), check_vma=False))
        return self._embed_fns[kind_key]

    def _embed(self, st: "StagedBatch") -> Array:
        """z = phi_m(rows) shard-locally; CSR shards run the O(nnz) sketch
        on their own (data, indices, indptr) slices — the embedding is the
        only dense array ever built from a sparse batch, and it is [rows, m]
        per device, never [n, d]."""
        from repro.kernels.precision import resolve_precision
        prec = resolve_precision(self.cfg.precision)
        with span("obs:embed_phi"):
            if st.sparse:
                fn = self._embed_fn(("csr", st.rows, st.d))
                z = fn(self.fmap, st.data, st.indices, st.indptr)
            else:
                z = self._embed_fn(("dense",))(self.fmap, st.x)
        # tile-dtype policy: the mesh-resident [rows, m] shard is the
        # dominant HBM term of this path — bf16 halves it; every Lloyd
        # contraction upcasts to f32 (see _shard_lloyd).
        return prec.cast_tiles(z)

    def _batch_step(self, x: Array, wgt: Array, centroids0: Array,
                    mask0: Array):
        return self._lloyd_fn(x, wgt, centroids0, mask0)

    def _audited_bill(self, z, wgt, centroids0, mask0):
        """Statically-audited collective bill of ``_lloyd_fn`` (see
        ``repro.analysis.collective_bill``), cached per embedded-batch
        shape; analytic fallback (+ ``audit_error`` event) if tracing
        fails — billing must never take the fit down."""
        key = (z.shape, centroids0.shape, str(z.dtype))
        bill = self._bill_cache.get(key)
        if bill is None:
            from repro.analysis import collective_bill
            try:
                bill = collective_bill(self._lloyd_fn, z, wgt, centroids0,
                                       mask0, name="embed_lloyd")
            except Exception as e:   # pragma: no cover - defensive
                self.rec.event("audit_error", where="embed_lloyd",
                               error=repr(e))
                m = getattr(self.fmap, "dim", 0)
                analytic = collectives_per_iteration(self.cfg.n_clusters, m)
                bill = {
                    "per_iteration": {"psum": analytic["psum"]},
                    "outside": {"psum": analytic["final_psum"]},
                    "per_iteration_bytes":
                        {"psum": analytic["psum_bytes"]},
                    "outside_bytes":
                        {"psum": analytic["final_psum_bytes"]},
                }
            self._bill_cache[key] = bill
        return bill

    def fit(self, batches: Iterable, *,
            state: Optional[EmbedState] = None,
            checkpoint_cb=None) -> FitResult:
        """Run the outer loop. ``batches`` may yield raw host batches (dense
        rows or ``CSRBatch`` — staged inline) or pre-staged ``StagedBatch``es
        (a ``source``/``BatchSource`` with the background producer). A
        closable source is closed on exit, success or failure, so an early
        error never leaks the producer thread."""
        from repro.data.loader import closing_source
        with closing_source(batches):
            return self._fit(batches, state=state,
                             checkpoint_cb=checkpoint_cb)

    def _fit(self, batches: Iterable, *, state, checkpoint_cb) -> FitResult:
        import time

        cfg = self.cfg
        rec = self.rec
        key = jax.random.PRNGKey(cfg.seed)
        history: list[BatchStats] = []
        start = int(state.batches_done) if state is not None else 0
        if state is not None and self.fmap is None:
            raise ValueError("resuming requires the original fmap")

        for i, xb in enumerate(batches, start=start):
            t_batch = time.perf_counter()
            self._ensure_fmap(xb)
            st = self.stage(xb)
            wgt = st.wgt
            # embed rows shard-locally (embarrassingly parallel, O(nnz) on
            # CSR shards).
            z = self._embed(st)

            sub = jax.random.fold_in(key, i)
            if state is None:
                # k-means++ seeds in embedded space (replicated, O(n*C)) —
                # over the UNPADDED rows only: ghost rows would double some
                # points' D^2 mass and shift every categorical draw, and the
                # seeding must match the single-host oracle bit-for-bit.
                zn = z[:st.n]
                zsq = jnp.sum(zn ** 2, axis=1)
                seeds = kmeans_pp_indices(zn, zsq, sub,
                                          n_clusters=cfg.n_clusters,
                                          spec=_LINEAR)
                centroids0 = jnp.take(zn, seeds, axis=0)
                mask0 = jnp.ones((cfg.n_clusters,), bool)
                cards = jnp.zeros((cfg.n_clusters,), jnp.float32)
            else:
                centroids0 = state.centroids
                mask0 = state.cardinalities > 0
                cards = state.cardinalities

            labels, cents, counts, t, cost = self._batch_step(
                z, wgt, centroids0, mask0)

            if state is None:
                new_centroids = cents
                disp = jnp.zeros((cfg.n_clusters,), jnp.float32)
                batches_done = jnp.array(1, jnp.int32)
            else:
                alpha = counts / jnp.maximum(counts + cards, 1.0)
                merged = ((1.0 - alpha)[:, None] * state.centroids
                          + alpha[:, None] * cents)
                keep = (counts == 0)[:, None]
                new_centroids = jnp.where(keep, state.centroids, merged)
                disp = jnp.sum((new_centroids - state.centroids) ** 2, axis=1)
                batches_done = state.batches_done + 1
            state = EmbedState(centroids=new_centroids,
                               cardinalities=cards + counts,
                               batches_done=batches_done)
            history.append(BatchStats(
                inner_iters=int(t), cost=float(cost),
                displacement=np.asarray(disp), counts=np.asarray(counts)))
            if checkpoint_cb is not None:
                checkpoint_cb(state, i)
            if rec.enabled:
                n_iter = history[-1].inner_iters
                # statically-audited bill (repro.analysis): per-iteration
                # while-body count x n_iter + the audited prologue sync
                # (the fixpoint ``means`` epilogue is gone — the pipelined
                # body syncs the stats of the labels it just wrote);
                # `collectives_per_iteration` remains the analytic
                # cross-check the audit must agree with.
                bill = self._audited_bill(z, wgt, centroids0, mask0)
                per, out = bill["per_iteration"], bill["outside"]
                rec.counter("collectives/psum",
                            per.get("psum", 0) * n_iter
                            + out.get("psum", 0), batch=i)
                rec.counter("collectives/psum_bytes",
                            bill["per_iteration_bytes"].get("psum", 0)
                            * n_iter
                            + bill["outside_bytes"].get("psum", 0),
                            batch=i)
                rec.series("batch/wall_seconds",
                           time.perf_counter() - t_batch, batch=i,
                           rows=st.n)
                rec.series("inner/cost", history[-1].cost, batch=i)
                rec.series("inner/iters", n_iter, batch=i)
                density = 1.0
                if st.sparse:
                    # indptr is shard-major [P*(rows+1)]; each shard's last
                    # entry is its stored nnz.
                    ptr = np.asarray(st.indptr).reshape(self.d_size,
                                                        st.rows + 1)
                    density = float(ptr[:, -1].sum()) / max(st.n * st.d, 1)
                obs_memory.watermark(
                    rec, batch=i, predicted_bytes=(
                        obs_memory.predicted_embed_footprint(
                            st.n, cfg.n_clusters, self.fmap,
                            sparse=st.sparse, density=density,
                            n_devices=self.d_size)))
                rec.batch_boundary(i)
        if state is None:
            raise ValueError("empty batch iterable")
        return FitResult(state, history, fmap=self.fmap, spec=cfg.kernel)
