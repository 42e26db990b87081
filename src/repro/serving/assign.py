"""Continuous-batching assignment service over a frozen predict artifact.

The serving problem is ragged: requests arrive with 1..N query rows, and a
naive ``jit(predict)`` retraces (and recompiles) per distinct row count —
unbounded compile amplification on the hottest path. This engine applies
the PR 3 memoized-mesh-program trick to inference: every request is padded
into a SMALL FIXED LADDER of shape buckets (rows in ``DEFAULT_BUCKETS``),
so the whole service runs on ``len(buckets)`` compiled programs, total.
Those programs are AOT-compiled at load time (``AssignService.warm`` via
``jax.jit(...).lower(...).compile()``) so the first request pays zero
compile; ``AssignService.compiled_programs`` is the literal program count
the bucket audit (``launch/audit.py``) pins to the ladder size.

Padding safety: padded rows are zeros and the per-row argmin is row-
independent (``score_ij = |c_j|^2 - 2 z_i . c_j`` — no cross-row term), so
a padded row can never perturb a real row's assignment; real labels are
sliced back out before they leave the engine (booby-trapped test in
tests/test_serving_assign.py feeds garbage padding and asserts identity).

Ingestion:
  * dense rows -> the AOT bucket program over ``ops.predict_assign``
    (fused Pallas pass on TPU/GPU — Z never in HBM — jnp oracle math off-
    accelerator; one program per bucket either way);
  * CSR rows (sketch kinds) -> per-request O(nnz) path: rows pad to the
    bucket, stored slots pad to a power-of-two nnz ladder
    (``data.sparse.pad_csr_capacity``), so the jit cache stays bounded by
    buckets x nnz-rungs. rff/nystrom/exact artifacts have no O(nnz)
    embedding — CSR requests densify at ingestion (row-local, documented);
  * tensorsketch dense -> the documented jnp FFT program (no fused tile
    kernel), still one program per bucket.

Per-request queue/compute latency lands in ``repro.obs``
(serve/queue_seconds, serve/compute_seconds, serve/request events);
``benchmarks/serve_bench.py`` drives an offered-QPS open loop over this
engine and records p50/p99 into BENCH_serve.json.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import (CSRBatch, is_sparse, pad_csr_capacity,
                               slice_rows, to_dense)
from repro.kernels import ops
from repro.obs import resolve

from .artifact import FUSED_KINDS, FrozenArtifact

Array = jax.Array

#: the shape ladder: requests pad to the smallest bucket that fits; bigger
#: requests chunk by the largest. 4 buckets == 4 compiled programs, total.
DEFAULT_BUCKETS = (1, 8, 64, 512)

#: kinds whose artifact carries an O(nnz) sketch map for CSR ingestion.
SKETCH_KINDS = ("sketch", "tensorsketch")


class QueueFull(RuntimeError):
    """Admission control: the queue holds ``max_queue_rows`` already."""


def bucket_for(n: int, buckets: tuple[int, ...]) -> int:
    """Smallest ladder bucket holding ``n`` rows (callers chunk by the
    largest bucket first, so ``n <= buckets[-1]`` always)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} rows exceed the largest bucket {buckets[-1]}")


def _resolve_runtime(art: FrozenArtifact, fused=None, interpret=None,
                     backend=None):
    """Fill the runtime knobs from the live jax backend (cpu CI defaults:
    fused=False / interpret=True; TPU/GPU: fused Pallas, native lowering)."""
    from repro.kernels.backend import kernel_backend
    platform = jax.default_backend()
    if fused is None:
        fused = ops.use_pallas() and art.kind in FUSED_KINDS
    elif fused and art.kind not in FUSED_KINDS:
        raise ValueError(
            f"kind {art.kind!r} has no fused kernel (FUSED_KINDS="
            f"{FUSED_KINDS}); its documented jnp program serves instead")
    if interpret is None:
        interpret = platform not in ("tpu", "gpu")
    if backend is None or backend == "auto":
        backend = kernel_backend()
    return bool(fused), bool(interpret), backend


def _statics(art: FrozenArtifact) -> dict:
    """The jit-static kwargs of ``ops.predict_assign`` for this artifact."""
    s = art.statics
    if art.kind == "sketch":
        return dict(map_kind="sketch")
    return dict(map_kind=s["map_kind"], gamma=float(s["gamma"]),
                coef0=float(s["coef0"]), degree=int(s["degree"]),
                scale=float(s["scale"]))


@jax.jit
def _score_assign(z: Array, v: Array, csq: Array) -> Array:
    """argmin_j csq_j - 2 z.v_j over an already-embedded bucket."""
    f = jax.lax.dot_general(z, v.astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    score = csq[None, :].astype(jnp.float32) - 2.0 * f
    return jnp.argmin(score, axis=1).astype(jnp.int32)


@jax.jit
def _ts_assign(x: Array, fmap, v: Array, csq: Array) -> Array:
    """TensorSketch bucket program (documented jnp FFT path — the map has
    no Pallas tile lowering; see kernels/ops.embed_assign)."""
    return _score_assign(fmap(x), v, csq)


@partial(jax.jit, static_argnames=("precision",))
def _csr_assign(batch: CSRBatch, fmap, v: Array, csq: Array, *,
                precision: str = "f32") -> Array:
    """Per-request O(nnz) CSR bucket program (sketch kinds).

    The stored values are the tile operand: rounded to the policy dtype
    then accumulated f32, matching the dense sketch path's semantics.
    Slack slots beyond ``indptr[-1]`` hold zeros (capacity contract) and
    scatter nothing.
    """
    if precision != "f32":
        from repro.kernels.precision import resolve_precision
        p = resolve_precision(precision)
        batch = dataclasses.replace(
            batch, data=p.cast_tiles(jnp.asarray(batch.data))
            .astype(jnp.float32))
    return _score_assign(fmap(batch), v, csq)


def _predict_padded(art: FrozenArtifact, xp: Array, *, fused: bool,
                    interpret: bool, backend: str) -> Array:
    """One already-padded dense bucket -> labels (jit-cached per bucket)."""
    a = art.arrays
    if art.kind == "exact":
        from repro.core.minibatch import predict as exact_predict
        return exact_predict(xp, a["medoids"], a["medoid_diag"],
                             spec=art.kernel_spec())
    if art.kind == "tensorsketch":
        # precision: TS has no tile knob (documented f32 FFT fallback)
        return _ts_assign(xp, art.feature_map(), a["v"], a["csq"])
    w_key, aux_key = ("h", "sign") if art.kind == "sketch" else ("w", "aux")
    labels, _ = ops.predict_assign(
        xp, a[w_key], a[aux_key], a["v"], a["csq"], fused=fused,
        interpret=interpret, precision=art.precision, backend=backend,
        **_statics(art))
    return labels


def _pad_csr(piece: CSRBatch, rows: int) -> CSRBatch:
    """Pad a CSR piece to ``rows`` bucket rows and a power-of-two stored-
    slot capacity, bounding the jit cache to buckets x nnz-rungs."""
    stored = int(np.asarray(piece.indptr)[-1])
    cap = 1 << max(0, (max(stored, 1) - 1).bit_length())
    return pad_csr_capacity([piece], rows=rows, nnz_multiple=cap)[0]


def predict(art: FrozenArtifact, x, *,
            buckets: tuple[int, ...] = DEFAULT_BUCKETS,
            fused: bool | None = None, interpret: bool | None = None,
            backend: str | None = None) -> Array:
    """Offline bucket-routed prediction (the ``FitResult.predict`` path).

    Chunks ``x`` by the largest bucket, zero-pads the remainder to the
    smallest bucket that fits, runs the per-bucket compiled program and
    slices the real labels back — so ANY query count reuses the same
    ``len(buckets)`` programs instead of retracing per shape.
    """
    buckets = tuple(sorted({int(b) for b in buckets}))
    fused, interpret, backend = _resolve_runtime(art, fused, interpret,
                                                 backend)
    if is_sparse(x):
        return _predict_csr(art, x, buckets, fused=fused,
                            interpret=interpret, backend=backend)
    xh = np.asarray(x, np.float32)
    if xh.ndim != 2 or xh.shape[1] != art.in_dim:
        raise ValueError(f"queries must be [n, {art.in_dim}], "
                         f"got {xh.shape}")
    n, d = xh.shape
    out = np.empty((n,), np.int32)
    start, bmax = 0, buckets[-1]
    while start < n:
        take = min(bmax, n - start)
        b = bucket_for(take, buckets)
        xp = np.zeros((b, d), np.float32)
        xp[:take] = xh[start:start + take]
        labels = _predict_padded(art, jnp.asarray(xp), fused=fused,
                                 interpret=interpret, backend=backend)
        out[start:start + take] = np.asarray(labels)[:take]
        start += take
    return jnp.asarray(out)


def _predict_csr(art: FrozenArtifact, batch: CSRBatch,
                 buckets: tuple[int, ...], *, fused: bool, interpret: bool,
                 backend: str) -> Array:
    if art.kind not in SKETCH_KINDS:
        # no O(nnz) embedding for these maps — densify (row-local; the
        # documented CSR story for rff/nystrom/exact artifacts)
        return predict(art, to_dense(batch), buckets=buckets, fused=fused,
                       interpret=interpret, backend=backend)
    fmap = art.feature_map()
    a = art.arrays
    n = batch.shape[0]
    out = np.empty((n,), np.int32)
    start, bmax = 0, buckets[-1]
    while start < n:
        take = min(bmax, n - start)
        b = bucket_for(take, buckets)
        piece = _pad_csr(slice_rows(batch, start, start + take), b)
        labels = _csr_assign(piece, fmap, a["v"], a["csq"],
                             precision=art.precision)
        out[start:start + take] = np.asarray(labels)[:take]
        start += take
    return jnp.asarray(out)


@dataclasses.dataclass(frozen=True)
class AssignServeConfig:
    """Knobs of the continuous-batching engine.

    ``fused``/``interpret``/``backend`` default to the live jax platform
    (``None`` -> auto); ``warm`` AOT-compiles every bucket program at
    construction so the first request pays no compile.
    """
    buckets: tuple[int, ...] = DEFAULT_BUCKETS
    max_queue_rows: int = 4096
    fused: bool | None = None
    interpret: bool | None = None
    backend: str | None = None
    warm: bool = True

    def __post_init__(self):
        if not self.buckets:
            raise ValueError("need at least one bucket")
        object.__setattr__(self, "buckets",
                           tuple(sorted({int(b) for b in self.buckets})))


@dataclasses.dataclass
class _Request:
    uid: int
    x: object            # np dense [n, d] or CSRBatch
    n: int
    t_submit: float
    labels: np.ndarray   # [n] int32, filled as ticks complete rows
    filled: int = 0


class AssignService:
    """Continuous-batching assignment server over a ``FrozenArtifact``.

    ``submit`` enqueues a request (admission-controlled); ``step`` packs
    the FIFO head into the smallest bucket that fits, runs ONE compiled
    program, and scatters labels back to their requests — partial
    consumption lets a 512-row request drain across ticks while 1-row
    requests ride along in the same bucket. ``drain`` ticks until empty.
    """

    def __init__(self, artifact: FrozenArtifact,
                 cfg: AssignServeConfig = AssignServeConfig(), *,
                 recorder=None):
        self.artifact = artifact
        self.cfg = cfg
        self.rec = resolve(recorder)
        self._fused, self._interpret, self._backend = _resolve_runtime(
            artifact, cfg.fused, cfg.interpret, cfg.backend)
        self._queue: collections.deque[_Request] = collections.deque()
        self._pending_rows = 0
        self._uid = 0
        # bucket -> (compiled program, trailing dynamic args, postprocessor)
        self._programs: dict[int, tuple] = {}
        self._fmap = (artifact.feature_map()
                      if artifact.kind in SKETCH_KINDS else None)
        if cfg.warm:
            self.warm()

    # -- programs -----------------------------------------------------------

    @property
    def compiled_programs(self) -> int:
        """Resident program count — the audit pins this to len(buckets)."""
        return len(self._programs)

    def warm(self) -> None:
        """AOT-compile one program per bucket (compile only, nothing
        executes) so the first request pays zero compile."""
        t0 = time.perf_counter()
        for b in self.cfg.buckets:
            self._program(b)
        self.rec.event("serve/warm", seconds=time.perf_counter() - t0,
                       programs=len(self._programs))

    def _entry(self, bucket: int):
        """(jitted fn, abstract x, trailing dynamic args, static kwargs,
        output postprocessor) for one dense bucket program."""
        art = self.artifact
        a = art.arrays
        x0 = jax.ShapeDtypeStruct((bucket, art.in_dim), jnp.float32)
        if art.kind == "exact":
            from repro.core.minibatch import predict as exact_predict
            return (exact_predict, x0, (a["medoids"], a["medoid_diag"]),
                    dict(spec=art.kernel_spec()), lambda out: out)
        if art.kind == "tensorsketch":
            return (_ts_assign, x0, (art.feature_map(), a["v"], a["csq"]),
                    {}, lambda out: out)
        w_key, aux_key = ("h", "sign") if art.kind == "sketch" \
            else ("w", "aux")
        kw = dict(fused=self._fused, interpret=self._interpret,
                  precision=art.precision, backend=self._backend,
                  **_statics(art))
        return (ops.predict_assign, x0,
                (a[w_key], a[aux_key], a["v"], a["csq"]), kw,
                lambda out: out[0])

    def _program(self, bucket: int) -> tuple:
        if bucket not in self._programs:
            jitfn, x0, args, kw, post = self._entry(bucket)
            self._programs[bucket] = (
                jitfn.lower(x0, *args, **kw).compile(), args, post)
        return self._programs[bucket]

    def program_text(self, bucket: int) -> str:
        """Optimized HLO of one bucket's compiled program — shows which
        kernels it runs (a Mosaic kernel is a ``tpu_custom_call``)."""
        return self._program(bucket)[0].as_text()

    # -- queue --------------------------------------------------------------

    def submit(self, x) -> int:
        """Enqueue one request; returns its uid. Raises ``QueueFull`` when
        admission would exceed ``max_queue_rows`` pending rows."""
        if is_sparse(x):
            if self.artifact.kind not in SKETCH_KINDS:
                x = to_dense(x)
        if not is_sparse(x):
            x = np.asarray(x, np.float32)
            if x.ndim != 2 or x.shape[1] != self.artifact.in_dim:
                raise ValueError(
                    f"queries must be [n, {self.artifact.in_dim}], "
                    f"got {x.shape}")
        n = x.shape[0]
        if n == 0:
            raise ValueError("empty request")
        if self._pending_rows + n > self.cfg.max_queue_rows:
            self.rec.counter("serve/rejected", rows=n)
            raise QueueFull(
                f"{self._pending_rows} rows pending + {n} > "
                f"max_queue_rows={self.cfg.max_queue_rows}")
        self._uid += 1
        self._queue.append(_Request(self._uid, x, n, time.perf_counter(),
                                    np.empty((n,), np.int32)))
        self._pending_rows += n
        self.rec.counter("serve/submitted", rows=n)
        self.rec.gauge("serve/queue_rows", self._pending_rows)
        return self._uid

    def step(self) -> dict[int, np.ndarray]:
        """One scheduler tick -> {uid: labels} for requests completed now."""
        if not self._queue:
            return {}
        if is_sparse(self._queue[0].x):
            return self._step_csr()
        bmax = self.cfg.buckets[-1]
        # pack consecutive dense FIFO heads (partial consumption allowed)
        items, total = [], 0
        for req in self._queue:
            if is_sparse(req.x) or total >= bmax:
                break
            take = min(req.n - req.filled, bmax - total)
            items.append((req, req.filled, take))
            total += take
        bucket = bucket_for(total, self.cfg.buckets)
        xp = np.zeros((bucket, self.artifact.in_dim), np.float32)
        ofs = 0
        for req, s, t in items:
            xp[ofs:ofs + t] = req.x[s:s + t]
            ofs += t
        t0 = time.perf_counter()
        compiled, args, post = self._program(bucket)
        labels = post(compiled(jnp.asarray(xp), *args))
        labels = np.asarray(jax.block_until_ready(labels))[:total]
        compute_s = time.perf_counter() - t0
        ofs = 0
        for req, s, t in items:
            req.labels[s:s + t] = labels[ofs:ofs + t]
            ofs += t
            req.filled += t
            self._pending_rows -= t
        return self._complete(t0, compute_s, bucket)

    def _step_csr(self) -> dict[int, np.ndarray]:
        """One tick over the CSR head request (per-request O(nnz) path)."""
        req = self._queue[0]
        bmax = self.cfg.buckets[-1]
        take = min(req.n - req.filled, bmax)
        bucket = bucket_for(take, self.cfg.buckets)
        piece = _pad_csr(slice_rows(req.x, req.filled, req.filled + take),
                         bucket)
        a = self.artifact.arrays
        t0 = time.perf_counter()
        labels = _csr_assign(piece, self._fmap, a["v"], a["csq"],
                             precision=self.artifact.precision)
        labels = np.asarray(jax.block_until_ready(labels))[:take]
        compute_s = time.perf_counter() - t0
        req.labels[req.filled:req.filled + take] = labels
        req.filled += take
        self._pending_rows -= take
        return self._complete(t0, compute_s, bucket)

    def _complete(self, t0: float, compute_s: float,
                  bucket: int) -> dict[int, np.ndarray]:
        done = {}
        now = time.perf_counter()
        while self._queue and self._queue[0].filled == self._queue[0].n:
            req = self._queue.popleft()
            done[req.uid] = req.labels
            queue_s = t0 - req.t_submit
            self.rec.series("serve/queue_seconds", queue_s, uid=req.uid)
            self.rec.series("serve/compute_seconds", compute_s, uid=req.uid)
            self.rec.event("serve/request", uid=req.uid, rows=req.n,
                           bucket=bucket, queue_seconds=queue_s,
                           compute_seconds=compute_s,
                           total_seconds=now - req.t_submit)
        self.rec.gauge("serve/queue_rows", self._pending_rows)
        return done

    def drain(self) -> dict[int, np.ndarray]:
        """Tick until the queue is empty; returns every completed request."""
        done = {}
        while self._queue:
            done.update(self.step())
        return done

    def predict(self, x) -> Array:
        """Synchronous convenience: submit + drain one request."""
        uid = self.submit(x)
        out = self.drain()
        return jnp.asarray(out[uid])
