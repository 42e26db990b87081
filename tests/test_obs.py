"""Flight-recorder (repro.obs) tests: recorder contract, JSONL content,
jit-safety (enabling metrics adds ZERO compilations and changes no labels),
pipeline/straggler instrumentation, and the export summary."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import KernelSpec, MiniBatchConfig
from repro.core.minibatch import (_first_batch_step, _next_batch_step,
                                  fit, fit_dataset)
from repro.data.synthetic import make_blobs
from repro.obs import (NULL, JsonlRecorder, MetricsRecorder, NullRecorder,
                       export, resolve, span)


def _events(path, kind=None, name=None):
    out = export.read_events(path)
    if kind is not None:
        out = [e for e in out if e.get("kind") == kind]
    if name is not None:
        out = [e for e in out if e.get("name") == name]
    return out


def test_null_recorder_contract():
    """NULL is the zero-overhead default: disabled, every hook a no-op,
    resolve(None) hands it back."""
    assert resolve(None) is NULL
    assert isinstance(NULL, NullRecorder)
    assert NULL.enabled is False
    r = resolve(NULL)
    r.counter("c", 3, batch=0)
    r.gauge("g", 1.0)
    r.series("s", jnp.float32(1.0))
    r.event("e", detail="x")
    with span("t", r, batch=0):
        pass
    r.batch_boundary(0)
    r.close()
    # a custom recorder passes through resolve untouched
    mine = JsonlRecorder.__new__(JsonlRecorder)
    assert resolve(mine) is mine


def test_jsonl_recorder_roundtrip(tmp_path):
    path = str(tmp_path / "log.jsonl")
    rec = JsonlRecorder(path, header=export.run_header(case="unit"))
    assert rec.enabled is True
    rec.counter("collectives/psum", 5, batch=0)
    rec.counter("collectives/psum", 7, batch=1)
    rec.gauge("queue", 2, batch=0)
    rec.series("wall", 0.25, batch=0)
    rec.series("cost", jnp.float32(3.5), batch=0)   # deferred device value
    with span("stage", rec, batch=0) as t:
        with span("stage/inner", rec) as u:
            pass
    rec.event("hbm_watermark", batch=0, source="host_rss",
              measured_bytes=100, peak_bytes=100, predicted_bytes=80.0)
    rec.batch_boundary(0)
    rec.close()

    header = _events(path, kind="header")
    assert len(header) == 1
    assert header[0]["backend"] == jax.default_backend()
    assert header[0]["case"] == "unit"
    # counter totals accumulate
    counters = _events(path, kind="counter")
    assert counters[-1]["total"] == 12
    # the deferred jax scalar was drained to a plain float at the boundary
    cost = _events(path, kind="series", name="cost")
    assert len(cost) == 1 and cost[0]["value"] == pytest.approx(3.5)
    assert t.seconds >= u.seconds >= 0.0
    outer, = _events(path, kind="span", name="stage")
    inner, = _events(path, kind="span", name="stage/inner")
    assert outer["parent"] is None and inner["parent"] == outer["id"]
    assert outer["batch"] == 0 and outer["seconds"] == t.seconds
    assert outer["lowerings"] == inner["lowerings"] == 0
    # every line is valid JSON (numpy/jax leak would have raised in dumps)
    with open(path) as f:
        for line in f:
            json.loads(line)

    s = export.summarize(path)
    assert s["events"] == len(export.read_events(path))
    assert s["counters"]["collectives/psum"] == 12
    assert s["stats"]["wall"]["count"] == 1
    assert s["stats"]["stage"]["total"] == pytest.approx(t.seconds)
    assert s["last_watermark"]["predicted_bytes"] == 80.0


def _exact_cfg(c=4, b=2):
    return MiniBatchConfig(n_clusters=c, n_batches=b, s=1.0,
                           kernel=KernelSpec("rbf", gamma=0.5), seed=0)


def test_recorder_is_jit_safe_exact(tmp_path):
    """THE acceptance criterion: running the exact fit with the JSONL
    recorder compiles nothing beyond what the NullRecorder run compiled,
    and produces bit-identical results."""
    x, _ = make_blobs(160, 8, 4, sep=6.0, seed=0)
    cfg = _exact_cfg()

    res_null = fit_dataset(x, cfg)                    # warm the caches
    first0 = _first_batch_step._cache_size()
    next0 = _next_batch_step._cache_size()

    path = str(tmp_path / "exact.jsonl")
    with JsonlRecorder(path) as rec:
        res_obs = fit_dataset(x, cfg, recorder=rec)

    assert _first_batch_step._cache_size() == first0
    assert _next_batch_step._cache_size() == next0
    np.testing.assert_array_equal(np.asarray(res_null.state.medoids),
                                  np.asarray(res_obs.state.medoids))
    assert [h.cost for h in res_null.history] == \
        [h.cost for h in res_obs.history]

    # per-batch wall times, one per batch
    walls = _events(path, kind="series", name="batch/wall_seconds")
    assert len(walls) == cfg.n_batches
    assert all(w["value"] > 0 for w in walls)
    # deferred cost/iter series drained and matching the history
    costs = _events(path, kind="series", name="inner/cost")
    assert [c["value"] for c in costs] == \
        pytest.approx([h.cost for h in res_obs.history])
    # measured-vs-predicted watermark pair on every batch
    marks = _events(path, kind="event", name="hbm_watermark")
    assert len(marks) == cfg.n_batches
    for m in marks:
        assert m["measured_bytes"] is not None and m["measured_bytes"] > 0
        assert m["predicted_bytes"] is not None and m["predicted_bytes"] > 0
        assert m["source"] in ("device", "host_rss")
        assert m["engine"] == "materialize"
    # boundaries flushed per batch + the close() drain
    assert len(_events(path, kind="boundary")) == cfg.n_batches + 1


def test_recorder_is_jit_safe_embedded(tmp_path):
    """Same contract for the embedded path (method != 'exact')."""
    from repro.approx import embed_kmeans
    x, _ = make_blobs(192, 8, 4, sep=6.0, seed=1)
    cfg = MiniBatchConfig(n_clusters=4, n_batches=2, kernel=KernelSpec("rbf",
                          gamma=0.5), seed=0, method="rff", embed_dim=32)

    res_null = fit_dataset(x, cfg)
    first0 = embed_kmeans._first_batch_step._cache_size()
    next0 = embed_kmeans._next_batch_step._cache_size()

    path = str(tmp_path / "embed.jsonl")
    with JsonlRecorder(path) as rec:
        res_obs = fit_dataset(x, cfg, recorder=rec)

    assert embed_kmeans._first_batch_step._cache_size() == first0
    assert embed_kmeans._next_batch_step._cache_size() == next0
    np.testing.assert_array_equal(np.asarray(res_null.state.centroids),
                                  np.asarray(res_obs.state.centroids))

    marks = _events(path, kind="event", name="hbm_watermark")
    assert len(marks) == cfg.n_batches
    assert all(m["predicted_bytes"] > 0 for m in marks)


def test_distributed_exact_recorder_identity(tmp_path):
    """Mesh path: recorder on vs off — identical medoids, and the log
    carries the statically-audited collective bill (analytic == static ==
    recorded) + straggler timing events."""
    from repro.distributed.mesh import make_test_mesh
    from repro.distributed.outer import DistributedMiniBatchKMeans

    x, _ = make_blobs(128, 6, 3, sep=6.0, seed=2)
    cfg = _exact_cfg(c=3, b=2)
    mesh = make_test_mesh({"data": 1})
    batches = [x[:64], x[64:]]

    res_off = DistributedMiniBatchKMeans(mesh, cfg).fit(list(batches))
    path = str(tmp_path / "dist.jsonl")
    with JsonlRecorder(path) as rec:
        km_on = DistributedMiniBatchKMeans(mesh, cfg, recorder=rec)
        res_on = km_on.fit(list(batches))

    np.testing.assert_array_equal(np.asarray(res_off.state.medoids),
                                  np.asarray(res_on.state.medoids))

    psums = _events(path, kind="counter", name="collectives/psum")
    gathers = _events(path, kind="counter", name="collectives/allgather")
    assert len(psums) == 2 and len(gathers) == 2

    # analytic == static: the audited per-sync while-body counts must
    # equal the hand-derived bill exactly — ONE fused psum and ONE
    # allgather per sync.
    from repro.distributed.inner import collectives_per_iteration
    analytic = collectives_per_iteration(km_on.inner_cfg)
    (static,) = km_on._bill_cache.values()   # both batches share one shape
    per, out = static["per_iteration"], static["outside"]
    assert analytic["psum"] == 1 and analytic["allgather"] == 1
    assert per["psum"] == analytic["psum"]
    assert per["all_gather"] == analytic["allgather"]

    # static == recorded: per-sync x n_iter + the audited prologue sync
    # (which pays the identical fused pair; no fixpoint epilogue).
    n0 = res_on.history[0].inner_iters
    assert psums[0]["inc"] == per["psum"] * n0 + out["psum"]
    assert gathers[0]["inc"] == per["all_gather"] * n0 + out["all_gather"]
    assert psums[0]["inc"] == analytic["psum"] * (n0 + 1)

    timings = _events(path, kind="event", name="batch_timing")
    assert len(timings) == 2
    assert str(jax.process_index()) in timings[0]["timings"]

    # every host span record carries the lowerings inside it; a batch's
    # steps cover it, so they account for every lowering of the batch
    spans = _events(path, kind="span")
    assert spans and all(isinstance(r["lowerings"], int)
                         and r["lower_s"] >= 0 for r in spans)
    for b in _events(path, kind="span", name="outer/batch"):
        kids = [r for r in spans if r["parent"] == b["id"]]
        assert b["lowerings"] == sum(r["lowerings"] for r in kids)


OUTER_STEPS = ["outer/stage", "outer/landmarks", "outer/diag", "outer/seed",
               "outer/init", "outer/inner", "outer/merge", "outer/sync",
               "outer/checkpoint", "outer/record"]


def _host_spans(logdir, prefix):
    """(name, start_ns, end_ns, stats) of the host-plane events of a
    profile whose name starts with ``prefix``."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def test_outer_loop_spans_on_the_profiler_clock(tmp_path):
    """A two-batch mesh fit under the profiler: one ``outer/fit``, an
    ``outer/batch`` per batch with its tag, the steps nested inside in
    program order and covering it (``outer/seed`` in the first batch
    only), and JSONL span records with the same names, parents and
    count."""
    from repro.distributed.mesh import make_test_mesh
    from repro.distributed.outer import DistributedMiniBatchKMeans

    x, _ = make_blobs(128, 6, 3, sep=6.0, seed=5)
    mesh = make_test_mesh({"data": 1})
    path = str(tmp_path / "spans.jsonl")
    logdir = str(tmp_path / "profile")
    with JsonlRecorder(path) as rec:
        km = DistributedMiniBatchKMeans(mesh, _exact_cfg(c=3, b=2),
                                        recorder=rec)
        with jax.profiler.trace(logdir):
            km.fit([x[:64], x[64:]], checkpoint_cb=lambda st, i: None)

    prof = sorted(_host_spans(logdir, "outer/"), key=lambda e: e[1])
    fits = [e for e in prof if e[0] == "outer/fit"]
    batches = [e for e in prof if e[0] == "outer/batch"]
    assert len(fits) == 1 and fits[0][3]["fit"] == 0
    assert [b[3]["batch"] for b in batches] == [0, 1]
    for b in batches:
        assert fits[0][1] <= b[1] and b[2] <= fits[0][2]
        kids = [e for e in prof if e[0] in OUTER_STEPS
                and e[3]["batch"] == b[3]["batch"]]
        want = OUTER_STEPS if b[3]["batch"] == 0 else \
            [n for n in OUTER_STEPS if n != "outer/seed"]
        assert [k[0] for k in kids] == want
        assert all(b[1] <= k[1] and k[2] <= b[2] for k in kids)
        assert all(a[2] <= c[1] for a, c in zip(kids, kids[1:]))
        covered = sum(k[2] - k[1] for k in kids)
        assert covered >= 0.95 * (b[2] - b[1])

    def parent_of(e):
        outer = [p for p in prof if p is not e and p[1] <= e[1]
                 and e[2] <= p[2]]
        return min(outer, key=lambda p: p[2] - p[1])[0] if outer else None

    recs = _events(path, kind="span")
    by_id = {r["id"]: r for r in recs}
    from collections import Counter
    got = Counter((r["name"], by_id[r["parent"]]["name"]
                   if r["parent"] is not None else None) for r in recs)
    assert got == Counter((e[0], parent_of(e)) for e in prof)
    assert len(recs) == len(prof) == 1 + 2 + 2 * len(OUTER_STEPS) - 1


def test_distributed_embed_recorder_with_prefetch(tmp_path):
    """Streaming embed path with the recorder through the BatchSource:
    queue-depth gauges + stage timings from the producer thread, psum
    counters + watermarks from the consumer, identical centroids."""
    from repro.distributed.embed import DistributedEmbedKMeans
    from repro.distributed.mesh import make_test_mesh

    x, _ = make_blobs(192, 8, 4, sep=6.0, seed=3)
    cfg = MiniBatchConfig(n_clusters=4, n_batches=3, kernel=KernelSpec("rbf",
                          gamma=0.5), seed=0, method="rff", embed_dim=32)
    mesh = make_test_mesh({"data": 1})
    batches = [x[:64], x[64:128], x[128:]]

    res_off = DistributedEmbedKMeans(mesh, cfg).fit(list(batches))
    path = str(tmp_path / "embed_dist.jsonl")
    with JsonlRecorder(path) as rec:
        km = DistributedEmbedKMeans(mesh, cfg, recorder=rec)
        res_on = km.fit(km.source(list(batches), depth=2))

    np.testing.assert_array_equal(np.asarray(res_off.state.centroids),
                                  np.asarray(res_on.state.centroids))

    assert len(_events(path, kind="gauge", name="prefetch/queue_depth")) == 3
    stage = _events(path, kind="series", name="prefetch/stage_seconds")
    assert len(stage) == 3 and all(s["value"] > 0 for s in stage)
    assert len(_events(path, kind="series",
                       name="prefetch/starve_seconds")) == 3
    assert len(_events(path, kind="counter", name="collectives/psum")) == 3
    marks = _events(path, kind="event", name="hbm_watermark")
    assert len(marks) == 3 and all(m["predicted_bytes"] > 0 for m in marks)


def test_straggler_monitor(tmp_path):
    """Satellite: detect_stragglers finally has call sites and tests."""
    from repro.ft.straggler import StragglerMonitor, detect_stragglers

    assert detect_stragglers({}) == []
    assert detect_stragglers({0: 1.0, 1: 1.1, 2: 1.0}) == []
    assert detect_stragglers({0: 1.0, 1: 1.1, 2: 5.0}) == [2]

    path = str(tmp_path / "strag.jsonl")
    rec = JsonlRecorder(path)
    mon = StragglerMonitor(rec, threshold=1.5)
    # healthy round: no event beyond the timing record
    assert mon.observe(0, {0: 1.0, 1: 1.05, 2: 0.95}, n_rows=1200) == []
    # worker 2 tanks: flagged, replan emitted over the rolling throughputs
    assert mon.observe(1, {0: 1.0, 1: 1.0, 2: 4.0}, n_rows=1200) == [2]
    rec.close()

    assert len(_events(path, kind="event", name="batch_timing")) == 2
    det = _events(path, kind="event", name="straggler_detected")
    assert len(det) == 1
    assert det[0]["stragglers"] == ["2"]
    replan = det[0]["replan"]
    assert replan is not None
    # the slow worker is assigned the fewest rows
    sizes = {k: v[1] for k, v in replan.items()}
    assert sizes["2"] == min(sizes.values())
    assert sum(sizes.values()) > 0


def test_elastic_runner_events(tmp_path):
    """elastic/resume + elastic/checkpoint appear next to batch metrics."""
    from repro.distributed.mesh import make_test_mesh
    from repro.ft.checkpoint import CheckpointManager
    from repro.ft.elastic import ElasticClusteringRunner

    x, _ = make_blobs(128, 6, 3, sep=6.0, seed=4)
    cfg = MiniBatchConfig(n_clusters=3, n_batches=2, kernel=KernelSpec("rbf",
                          gamma=0.5), seed=0, method="rff", embed_dim=16)
    path = str(tmp_path / "elastic.jsonl")
    with JsonlRecorder(path) as rec:
        runner = ElasticClusteringRunner(
            cfg, CheckpointManager(str(tmp_path / "ckpt")), recorder=rec)
        runner.run(make_test_mesh({"data": 1}), [x[:64], x[64:]])

    resume = _events(path, kind="event", name="elastic/resume")
    assert len(resume) == 1 and resume[0]["resumed"] is False
    assert len(_events(path, kind="event", name="elastic/checkpoint")) == 2


def test_collectives_per_iteration_counts():
    from repro.distributed.embed import \
        collectives_per_iteration as embed_bill
    from repro.distributed.inner import (DistributedInnerConfig,
                                         collectives_per_iteration)

    cfg_1d = DistributedInnerConfig(n_clusters=8, col_axis=None)
    cfg_2d = DistributedInnerConfig(n_clusters=8, col_axis="model")
    # ONE fused psum per sync on both layouts (the s-step contract);
    # payload: [C+2] floats in 1-D (g row-partials + cost + changed),
    # [rows_p+2, C] in 2-D (f + counts + g, scalars riding the gather).
    assert collectives_per_iteration(cfg_1d)["psum"] == 1
    assert collectives_per_iteration(cfg_2d)["psum"] == 1
    assert collectives_per_iteration(cfg_1d)["allgather"] == 1
    assert collectives_per_iteration(cfg_2d)["allgather"] == 1
    assert collectives_per_iteration(cfg_1d)["psum_bytes"] == 4 * (8 + 2)
    assert collectives_per_iteration(
        cfg_2d, n_local_rows=64)["psum_bytes"] == 4 * 8 * (64 + 2)

    b = embed_bill(8, 32)
    # embed: ONE fused psum/iteration (sums+counts+flag+cost), and the
    # prologue sync outside the loop pays the same payload.
    assert b["psum"] == 1 and b["final_psum"] == 1
    assert b["psum_bytes"] == 4 * (8 * 33 + 2)
    assert b["final_psum_bytes"] == b["psum_bytes"]


def test_jsonl_recorder_thread_safety(tmp_path):
    """Producer-thread writes interleave with the consumer without losing
    or corrupting records (the PrefetchLoader contract)."""
    import threading

    path = str(tmp_path / "mt.jsonl")
    rec = JsonlRecorder(path)

    def hammer(tid):
        for k in range(200):
            rec.counter("n", 1, thread=tid)
            rec.series(f"s{tid}", float(k))

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rec.close()
    assert rec.totals["n"] == 800
    counters = _events(path, kind="counter", name="n")
    assert len(counters) == 800
    with open(path) as f:
        for line in f:
            json.loads(line)   # no torn lines


def test_memory_watermark_cpu_fallback():
    """On backends without allocator stats the watermark still produces a
    measured value, tagged host_rss — the measured-vs-predicted pair must
    exist on every backend."""
    from repro.obs import memory as obs_memory

    class Sink(MetricsRecorder):
        enabled = True

        def __init__(self):
            self.events = []

        def event(self, name, **fields):
            self.events.append((name, fields))

    sink = Sink()
    obs_memory.watermark(sink, batch=0, predicted_bytes=123.0)
    (name, fields), = sink.events
    assert name == "hbm_watermark"
    assert fields["predicted_bytes"] == 123.0
    assert fields["measured_bytes"] is None or fields["measured_bytes"] > 0
    if jax.default_backend() == "cpu" and not fields["devices"]:
        assert fields["source"] == "host_rss"


def test_fit_list_batches_with_recorder(tmp_path):
    """fit() over plain list batches (the sparse/sketch benchmark shape)
    records without disturbing results."""
    from repro.data.sparse import split_csr
    from repro.data.synthetic import make_rcv1_sparse

    xs, _ = make_rcv1_sparse(200, vocab=64, n_classes=4, seed=0)
    cfg = MiniBatchConfig(n_clusters=4, n_batches=2,
                          kernel=KernelSpec("linear"), seed=0,
                          method="sketch", embed_dim=32)
    res_null = fit(split_csr(xs, 2, strategy="stride"), cfg)
    path = str(tmp_path / "sparse.jsonl")
    with JsonlRecorder(path) as rec:
        res_obs = fit(split_csr(xs, 2, strategy="stride"), cfg, recorder=rec)
    np.testing.assert_array_equal(np.asarray(res_null.state.centroids),
                                  np.asarray(res_obs.state.centroids))
    marks = _events(path, kind="event", name="hbm_watermark")
    assert len(marks) == 2 and all(m["predicted_bytes"] > 0 for m in marks)
