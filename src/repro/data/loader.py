"""Sharded H2D staging: the host side of the paper's producer/consumer
offload scheme (§3.3, Fig.3), generalized to batch *pytrees*.

On the paper's CPU+GPU node, a dedicated thread feeds the GPU so that
K^{i+1} is produced while the host consumes K^i. On TPU the kernel matrix is
produced by the same chip that consumes it, so the equivalent overlap is
host-side: a background thread stages batch i+1 (disk fetch, dtype cast,
device put) while the device iterates the inner loop on batch i.

What "stage" means here is richer than the paper's memcpy leg: the hook may
pad a batch to divide the mesh, row-split a ``CSRBatch`` into per-device
shards (the ``repro.data.sparse`` indptr surgery — ``slice_rows``/
``shard_csr``; see ``DistributedEmbedKMeans._stage_csr``), and
``jax.device_put`` the resulting pytree onto a ``NamedSharding`` so the
async H2D copy lands *pre-sharded* on the mesh — the consumer never touches
a single-host [n, d] array, dense or sparse. That is this runtime's version
of Fig.3's 3-stage H2D/compute/D2H pipeline: the H2D leg overlaps the inner
loop, the D2H leg was removed by fusion (DESIGN.md §2), and with CSR shards
the bytes crossing the bus are O(nnz), not O(n*d).

Lifecycle: the producer is a daemon thread feeding a bounded queue. A
consumer that stops early (elastic re-mesh, error, ``break``) MUST call
``close()`` (or use the context manager) — otherwise the producer blocks
forever on the full queue. ``close()`` sets a stop flag and drains the
queue until the thread exits; it is idempotent.

``BatchSource`` is the one handle the fit loops consume: any iterable of
dense blocks or CSR mini-batches (list, generator, or a raw chunk stream
via ``from_stream``), with optional host-side ``skip`` (checkpoint resume —
skipped batches are never staged) and optional prefetch+stage.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional

import jax
import numpy as np


@contextlib.contextmanager
def closing_source(batches):
    """The fit loops' consume rule, in one place: whatever happens inside,
    a closable batch source (BatchSource / PrefetchLoader) is closed on
    exit so its producer thread never outlives the fit. ``close()`` is
    idempotent, so nested fit entry points may each apply this."""
    try:
        yield batches
    finally:
        close = getattr(batches, "close", None)
        if callable(close):
            close()


class PrefetchLoader:
    """Wrap a mini-batch iterable with ``depth`` batches of lookahead.

    ``stage`` maps a raw host batch to its device-resident form inside the
    producer thread; the default casts dense ndarrays to ``dtype`` and
    ``jax.device_put``s them (any other pytree — e.g. a ``CSRBatch`` — is
    device_put leaf-wise). Pass a mesh-aware hook (e.g.
    ``DistributedEmbedKMeans.stage``) to land batches pre-sharded.

    ``recorder`` (``repro.obs``) watches pipeline health from both sides:
    the producer thread times each stage call (``prefetch/stage_seconds``)
    and gauges the queue depth after every put, the consumer records how
    long it sat starved waiting for an item (``prefetch/starve_seconds``).
    A persistently shallow queue + starved consumer means ingestion is the
    bottleneck, not the mesh.
    """

    _SENTINEL = object()

    def __init__(self, batches: Iterable, *, depth: int = 2,
                 device: Optional[jax.Device] = None, dtype=np.float32,
                 stage: Optional[Callable] = None, recorder=None):
        from repro.obs import resolve
        self._src = iter(batches)
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._device = device
        self._dtype = dtype
        self._stage = stage if stage is not None else self._default_stage
        self._rec = resolve(recorder)
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _default_stage(self, batch):
        # array-like batches (ndarray, jax array, nested lists) keep the
        # historical coercion to one ``dtype`` device array; genuine batch
        # pytrees (CSRBatch, dicts) are device_put leaf-wise instead.
        if jax.tree_util.all_leaves([batch]) or \
                isinstance(batch, (list, tuple)):
            return jax.device_put(np.asarray(batch, dtype=self._dtype),
                                  self._device)  # async H2D
        return jax.device_put(batch, self._device)

    def _put(self, item) -> bool:
        """Blocking put that stays interruptible by ``close()``.

        The timeout only bounds how long the thread parks before re-checking
        the stop flag — a consumer freeing a slot wakes the put immediately
        regardless — so the backoff costs no throughput; it just keeps an
        abandoned (never-closed) loader's producer from waking 20x/s
        forever."""
        delay = 0.05
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=delay)
                return True
            except queue.Full:
                delay = min(2.0 * delay, 0.5)
        return False

    def _produce(self) -> None:
        from repro.obs.trace import span
        rec = self._rec
        try:
            for k, batch in enumerate(self._src):
                if self._stop.is_set():
                    return
                t0 = time.perf_counter()
                with span("obs:stage"):
                    staged = self._stage(batch)
                if rec.enabled:
                    rec.series("prefetch/stage_seconds",
                               time.perf_counter() - t0, index=k)
                if not self._put(staged):
                    return
                if rec.enabled:
                    rec.gauge("prefetch/queue_depth", self._q.qsize(),
                              index=k)
        except BaseException as e:  # surfaced on the consumer side
            self._err = e
        finally:
            self._put(self._SENTINEL)

    def __iter__(self) -> Iterator:
        rec = self._rec
        t_wait = None   # set when the consumer starts waiting for an item
        while True:
            if rec.enabled and t_wait is None:
                t_wait = time.perf_counter()
            try:
                item = self._q.get(timeout=0.05)
            except queue.Empty:
                # a closed (or crashed-without-sentinel) producer enqueues
                # nothing more — an untimed get would hang the consumer
                if self._stop.is_set() or not self._thread.is_alive():
                    if self._err is not None:
                        raise self._err
                    return
                continue
            if item is self._SENTINEL:
                if self._err is not None:
                    raise self._err
                return
            if rec.enabled:
                rec.series("prefetch/starve_seconds",
                           time.perf_counter() - t_wait)
                t_wait = None
            yield item

    def close(self, timeout: float = 10.0) -> None:
        """Stop the producer and release it (drain-on-close). Safe to call
        from a consumer that broke out mid-stream; idempotent."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:                     # unblock a producer stuck in put()
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.02)

    def __enter__(self) -> "PrefetchLoader":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class BatchSource:
    """One handle over the whole ingestion pipeline, disk -> host -> mesh.

    Wraps ANY mini-batch iterable — a list (stride split of a resident
    dataset), a generator (block sampling over a live stream), dense [n, d]
    blocks or ``CSRBatch``es — behind one lifecycle:

    * ``skip(k)`` — drop the first k batches host-side before staging
      anything (checkpoint resume: the committed prefix is never paid for);
    * ``stage=`` + ``prefetch=`` — background-thread staging onto the mesh
      (see ``PrefetchLoader``); with ``prefetch=0`` the stage hook still
      runs, synchronously;
    * ``close()`` / context manager — releases the producer thread; the fit
      loops call it when they finish or fail, so a source is single-use.

    Constructors: ``from_dataset`` stride/block-splits a resident dense
    array or CSR dataset; ``from_stream`` re-chunks a ragged dense/CSR
    chunk stream (``repro.data.sampling.stream_blocks``).
    """

    def __init__(self, batches: Iterable, *, stage: Optional[Callable] = None,
                 prefetch: int = 0, skip: int = 0, recorder=None):
        from repro.obs import resolve
        self._batches = batches
        self._stage = stage
        self._prefetch = prefetch
        self._skip = skip
        self._rec = resolve(recorder)
        self._loader: Optional[PrefetchLoader] = None

    @classmethod
    def from_dataset(cls, x, n_batches: int, strategy: str = "stride",
                     **kw) -> "BatchSource":
        """Split a resident dataset (dense [n, d] or CSRBatch)."""
        from .sampling import split_batches
        from .sparse import is_sparse, split_csr
        if is_sparse(x):
            parts = split_csr(x, n_batches, strategy=strategy)
        else:
            parts = split_batches(np.asarray(x), n_batches, strategy=strategy)
        return cls(parts, **kw)

    @classmethod
    def from_stream(cls, chunks: Iterable, batch_size: int,
                    **kw) -> "BatchSource":
        """Re-chunk a ragged dense/CSR chunk stream into block batches."""
        from .sampling import stream_blocks
        return cls(stream_blocks(iter(chunks), batch_size), **kw)

    def skip(self, n_batches: int) -> "BatchSource":
        """Drop the first ``n_batches`` host-side (resume). Returns self."""
        self._skip += int(n_batches)
        return self

    def __iter__(self) -> Iterator:
        it = iter(self._batches)
        try:
            for _ in range(self._skip):
                next(it)
        except StopIteration:
            return
        if self._prefetch > 0:
            self.close()   # re-iteration must not orphan a live producer
            self._loader = PrefetchLoader(it, depth=self._prefetch,
                                          stage=self._stage,
                                          recorder=self._rec)
            yield from self._loader
        elif self._stage is not None:
            for k, b in enumerate(it):
                if self._rec.enabled:
                    t0 = time.perf_counter()
                    staged = self._stage(b)
                    self._rec.series("prefetch/stage_seconds",
                                     time.perf_counter() - t0, index=k,
                                     sync=True)
                    yield staged
                else:
                    yield self._stage(b)
        else:
            yield from it

    def close(self) -> None:
        if self._loader is not None:
            self._loader.close()
            self._loader = None

    def __enter__(self) -> "BatchSource":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
