"""Distributed runtime tests. Multi-device cases run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the rest of the suite
keeps seeing exactly one device (jax locks the count on first init)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(body: str) -> dict:
    """Run ``body`` under 8 forced host devices; it must print one JSON."""
    script = textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax
        import jax.numpy as jnp
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["materialize", "fused", "tiled"])
def test_distributed_matches_single_device(mode):
    """The 2-D sharded inner loop (rows x landmarks) must produce the same
    labels and medoids as the single-device reference, all three GramEngine
    modes."""
    res = _run_subprocess(f"""
        from repro.core import MiniBatchConfig, KernelSpec
        from repro.core.minibatch import fit_dataset, predict
        from repro.distributed.outer import DistributedMiniBatchKMeans
        from repro.data.sampling import split_batches

        rng = np.random.default_rng(0)
        centers = np.array([[0.25,0.25],[0.75,0.75],[0.25,0.75],[0.75,0.25]])
        X = np.concatenate([rng.normal(c, 0.05, size=(512,2))
                            for c in centers]).astype(np.float32)
        y = np.repeat(np.arange(4), 512)
        perm = rng.permutation(len(X)); X, y = X[perm], y[perm]

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = MiniBatchConfig(n_clusters=4, n_batches=4, s=1.0,
                              kernel=KernelSpec("rbf", gamma=8.0), seed=0)
        km = DistributedMiniBatchKMeans(mesh, cfg, mode="{mode}")
        res = km.fit(split_batches(X, 4, strategy="stride"))
        labels = predict(jnp.asarray(X), res.state.medoids,
                         res.state.medoid_diag, spec=cfg.kernel)

        from repro.core.metrics import clustering_accuracy
        acc = clustering_accuracy(y, np.asarray(labels))
        total = int(np.asarray(res.state.cardinalities).sum())
        print(json.dumps({{"acc": acc, "total": total, "n": len(X)}}))
    """)
    assert res["acc"] > 0.95
    assert res["total"] == res["n"]


@pytest.mark.slow
def test_distributed_inner_identical_to_host_inner():
    """Bitwise-level agreement (labels) between repro.core.kkmeans and the
    shard_map inner loop from the SAME init on the SAME batch — the shared
    GramEngine means this must hold under every engine mode."""
    res = _run_subprocess("""
        from repro.core import KernelSpec
        from repro.core.kkmeans import kkmeans_fit
        from repro.distributed.inner import (DistributedInnerConfig,
                                             distributed_kkmeans_fit)

        rng = np.random.default_rng(1)
        X = rng.normal(size=(256, 8)).astype(np.float32)
        spec = KernelSpec("rbf", gamma=0.2)
        x = jnp.asarray(X)
        diag = spec.diag(x)
        l_idx = jnp.arange(256, dtype=jnp.int32)      # s = 1
        u0 = jnp.asarray(rng.integers(0, 5, 256), jnp.int32)

        host = kkmeans_fit(x, l_idx, diag, u0, spec=spec, n_clusters=5)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        out = {}
        for mode in ("materialize", "fused", "tiled"):
            cfg = DistributedInnerConfig(n_clusters=5, kernel=spec,
                                         engine=mode,
                                         row_axes=("data",),
                                         col_axis="model")
            dist = distributed_kkmeans_fit(mesh, x, x, l_idx, diag, u0,
                                           cfg=cfg)
            out[mode] = {
                "same": bool(jnp.all(host.labels == dist.labels)),
                "g_err": float(jnp.max(jnp.abs(host.g - dist.g))),
                "cost_err": abs(float(host.cost) - float(dist.cost))}
        print(json.dumps(out))
    """)
    for mode, r in res.items():
        assert r["same"], f"{mode}: distributed labels diverged from host"
        assert r["g_err"] < 1e-4, mode
        assert r["cost_err"] < 1e-2, mode


@pytest.mark.slow
def test_faithful_1d_distribution_mode():
    """col_axis=None recovers the paper's exact 1-D row-wise algorithm."""
    res = _run_subprocess("""
        from repro.core import KernelSpec
        from repro.core.kkmeans import kkmeans_fit
        from repro.distributed.inner import (DistributedInnerConfig,
                                             distributed_kkmeans_fit)

        rng = np.random.default_rng(2)
        X = rng.normal(size=(128, 4)).astype(np.float32)
        spec = KernelSpec("rbf", gamma=0.3)
        x = jnp.asarray(X)
        diag = spec.diag(x)
        l_idx = jnp.arange(128, dtype=jnp.int32)
        u0 = jnp.asarray(rng.integers(0, 3, 128), jnp.int32)
        host = kkmeans_fit(x, l_idx, diag, u0, spec=spec, n_clusters=3)

        mesh = jax.make_mesh((8,), ("data",))
        cfg = DistributedInnerConfig(n_clusters=3, kernel=spec,
                                     row_axes=("data",), col_axis=None)
        dist = distributed_kkmeans_fit(mesh, x, x, l_idx, diag, u0, cfg=cfg)
        print(json.dumps({"same": bool(jnp.all(host.labels == dist.labels))}))
    """)
    assert res["same"]


@pytest.mark.slow
def test_collective_structure_matches_paper():
    """The compiled inner iteration must contain the paper's two collectives
    (all-gather U, all-reduce g) and must NOT move the kernel matrix: total
    collective bytes per iteration << |K| bytes."""
    res = _run_subprocess("""
        from repro.core import KernelSpec
        from repro.distributed.inner import (DistributedInnerConfig,
                                             distributed_kkmeans_fit)
        from repro.launch.dryrun import collective_bytes
        from functools import partial

        rng = np.random.default_rng(3)
        n, d, C = 1024, 16, 4
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        spec = KernelSpec("rbf", gamma=0.1)
        diag = spec.diag(x)
        l_idx = jnp.arange(n, dtype=jnp.int32)
        u0 = jnp.zeros((n,), jnp.int32)

        mesh = jax.make_mesh((8,), ("data",))
        cfg = DistributedInnerConfig(n_clusters=C, kernel=spec,
                                     row_axes=("data",), col_axis=None)
        fn = partial(distributed_kkmeans_fit, mesh, cfg=cfg)
        lowered = jax.jit(lambda *a: fn(*a)).lower(x, x, l_idx, diag, u0)
        txt = lowered.compile().as_text()
        coll = collective_bytes(txt)
        k_bytes = n * n * 4
        print(json.dumps({
            "ag": coll["counts"]["all-gather"],
            "ar": coll["counts"]["all-reduce"],
            "total": coll["total_bytes"], "k_bytes": k_bytes}))
    """)
    assert res["ag"] >= 1, "missing the paper's all-gather(U)"
    assert res["ar"] >= 1, "missing the paper's all-reduce(g)"
    # kernel matrix never crosses the network (paper's key property):
    assert res["total"] < 0.05 * res["k_bytes"]


@pytest.mark.slow
def test_distributed_csr_fit_equals_dense_oracle():
    """Sharded-CSR ingestion correctness (interpret-mode, 8 host devices):
    the distributed fit on prefetch-staged CSR shards must label exactly
    like the single-host fit on the densified oracle — the pipeline
    (shard_csr surgery, slack capacity, masked padding, per-device O(nnz)
    sketch, psum merge) adds nothing."""
    res = _run_subprocess("""
        from repro.core import KernelSpec, MiniBatchConfig
        from repro.core.minibatch import fit
        from repro.data import split_batches, split_csr, to_dense
        from repro.data.synthetic import make_rcv1_sparse
        from repro.distributed.embed import DistributedEmbedKMeans

        xs, y = make_rcv1_sparse(2048, vocab=4096, n_classes=8, seed=0)
        cfg = MiniBatchConfig(n_clusters=8, n_batches=4,
                              kernel=KernelSpec("linear"), seed=0,
                              method="sketch", embed_dim=128)
        dense = to_dense(xs)
        res_host = fit(split_batches(dense, 4, strategy="stride"), cfg)

        mesh = jax.make_mesh((8,), ("data",))
        km = DistributedEmbedKMeans(mesh, cfg)
        with km.source(split_csr(xs, 4, strategy="stride"), depth=2) as src:
            res_dist = km.fit(src)

        lab_d = np.asarray(res_dist.predict(xs))
        lab_h = np.asarray(res_host.predict(dense))
        cerr = float(np.abs(np.asarray(res_dist.state.centroids)
                            - np.asarray(res_host.state.centroids)).max())
        print(json.dumps({
            "same": bool((lab_d == lab_h).all()), "cerr": cerr,
            "total": float(np.asarray(res_dist.state.cardinalities).sum()),
            "n": len(xs)}))
    """)
    assert res["same"], "distributed CSR labels diverged from dense oracle"
    assert res["cerr"] < 1e-5
    assert res["total"] == res["n"]     # masked padding never hits counts


@pytest.mark.slow
def test_staging_tail_batch_smaller_than_mesh():
    """Regression: a stream's last batch can be SMALLER than the mesh row
    count — staging used to index past the batch (CSR) or ship a short
    array into the row sharding (dense). Modulo-replicated ghost rows must
    keep the fit running with exact masked cardinalities; and a pre-staged
    first batch must give a data-dependent map (Nystrom) the same sample as
    the inline path — identical centroids either way."""
    res = _run_subprocess("""
        from repro.core import KernelSpec, MiniBatchConfig
        from repro.data.sparse import csr_from_dense
        from repro.distributed.embed import DistributedEmbedKMeans

        rng = np.random.default_rng(0)
        n = 2048 + 3                              # 3-row tail on 8 devices
        x = rng.normal(size=(n, 64)).astype(np.float32)
        x *= (rng.random((n, 64)) < 0.2)
        mesh = jax.make_mesh((8,), ("data",))

        cfg = MiniBatchConfig(n_clusters=4, n_batches=2,
                              kernel=KernelSpec("linear"), seed=0,
                              method="sketch", embed_dim=64)
        batches = [csr_from_dense(x[:2048]), csr_from_dense(x[2048:])]
        km = DistributedEmbedKMeans(mesh, cfg)
        with km.source(batches, depth=2) as src:
            res_csr = km.fit(src)
        dense_total = float(np.asarray(
            DistributedEmbedKMeans(mesh, cfg).fit([x[:2048], x[2048:]])
            .state.cardinalities).sum())

        # nystrom: staged-first-batch sampling == inline sampling (pad > 0)
        cfg_ny = MiniBatchConfig(n_clusters=3, n_batches=1,
                                 kernel=KernelSpec("rbf", gamma=0.5),
                                 seed=1, method="nystrom", embed_dim=12)
        xb = rng.normal(size=(1021, 16)).astype(np.float32)   # pad = 3
        inline = DistributedEmbedKMeans(mesh, cfg_ny).fit([xb])
        km2 = DistributedEmbedKMeans(mesh, cfg_ny)
        with km2.source([xb], depth=1) as src:
            staged = km2.fit(src)
        ny_same = bool((np.asarray(inline.state.centroids)
                        == np.asarray(staged.state.centroids)).all())
        # built from the staged (sharded) batch, the map and the served
        # artifact still live on one device, where a Mosaic kernel runs
        from repro.serving import freeze
        ny_one_device = all(len(a.devices()) == 1 for a in
                            [staged.fmap.landmarks, staged.fmap.proj]
                            + list(freeze(staged).arrays.values()))

        # non-divisible FIRST batch: k-means++ must seed over the unpadded
        # rows, or ghost rows shift every D^2 draw and the distributed fit
        # silently diverges from the single-host oracle.
        from repro.core.minibatch import fit
        from repro.data.sparse import split_csr, to_dense
        first_nd = [csr_from_dense(x[:1027]), csr_from_dense(x[1027:2048])]
        km_nd = DistributedEmbedKMeans(mesh, cfg)
        res_nd = km_nd.fit(first_nd)
        host_nd = fit([x[:1027], x[1027:2048]], cfg)
        seed_same = bool((np.asarray(res_nd.predict(csr_from_dense(x[:2048])))
                          == np.asarray(host_nd.predict(x[:2048]))).all())

        # exact path on a stream with the same 3-row tail (elastic
        # advertises live streams for every method): modulo padding must
        # keep the row sharding divisible.
        cfg_ex = MiniBatchConfig(n_clusters=4, n_batches=2, s=1.0,
                                 kernel=KernelSpec("rbf", gamma=0.5), seed=0)
        from repro.distributed.outer import DistributedMiniBatchKMeans
        res_ex = DistributedMiniBatchKMeans(mesh, cfg_ex).fit(
            [x[:2048], x[2048:]])
        exact_batches = int(res_ex.state.batches_done)

        print(json.dumps({
            "csr_total": float(np.asarray(res_csr.state.cardinalities).sum()),
            "dense_total": dense_total, "n": n, "ny_same": ny_same,
            "ny_one_device": ny_one_device,
            "seed_same": seed_same, "exact_batches": exact_batches}))
    """)
    assert res["csr_total"] == res["n"]      # ghost rows masked out
    assert res["dense_total"] == res["n"]
    assert res["ny_same"], "staged Nystrom sampling diverged from inline"
    assert res["ny_one_device"], "map or artifact spread over the mesh"
    assert res["seed_same"], "non-divisible first batch: seeding diverged"
    assert res["exact_batches"] == 2         # tail batch staged, not crashed


@pytest.mark.slow
def test_exact_ghost_rows_unbiased_when_p_does_not_divide_batch():
    """Regression for the exact-path ghost-row bias (old ROADMAP item):
    with P∤(N/B), the modulo-replicated padding rows used to be landmark
    candidates and to score in the medoid/merge argmins, perturbing
    cardinalities and the Eq.12 alpha by O(P/(N/B)). Selection now runs
    over the unpadded rows and the argmins/cost are weight-masked, so —
    starting both paths from the same state — the distributed fit must
    reproduce the single-host cardinalities exactly and the medoids
    bit-for-bit."""
    res = _run_subprocess("""
        from repro.core import KernelSpec, MiniBatchConfig
        from repro.core.minibatch import fit as host_fit
        from repro.distributed.outer import DistributedMiniBatchKMeans

        rng = np.random.default_rng(0)
        x = rng.normal(size=(2048 + 1027, 8)).astype(np.float32)
        mesh = jax.make_mesh((8,), ("data",))
        # landmark_multiple_of matches the mesh so |L| agrees across paths
        cfg = MiniBatchConfig(n_clusters=5, n_batches=2, s=0.5,
                              kernel=KernelSpec("rbf", gamma=0.5),
                              max_inner_iters=4, seed=3,
                              landmark_multiple_of=8)
        batches = [x[:2048], x[2048:]]          # second batch: 1027 % 8 != 0
        st0 = host_fit([batches[0]], cfg).state  # shared starting state

        dist = DistributedMiniBatchKMeans(mesh, cfg).fit([batches[1]],
                                                         state=st0)
        host = host_fit([batches[1]], cfg, state=st0)
        cards_equal = bool((np.asarray(dist.state.cardinalities)
                            == np.asarray(host.state.cardinalities)).all())
        medoid_diff = float(np.abs(np.asarray(dist.state.medoids)
                                   - np.asarray(host.state.medoids)).max())
        # with s=0.5 cardinalities count LANDMARK rows (Eq.14 expansion):
        # 1024 for the first batch + 520 for the 1027-row tail batch —
        # any ghost landmark would show up as excess mass here.
        total = float(np.asarray(dist.state.cardinalities).sum())
        print(json.dumps({"cards_equal": cards_equal,
                          "medoid_diff": medoid_diff,
                          "total": total}))
    """)
    assert res["cards_equal"], "ghost rows still biased the cardinalities"
    assert res["medoid_diff"] == 0.0
    assert res["total"] == 1024 + 520


@pytest.mark.slow
def test_distributed_exact_resume_bit_identical():
    """Regression (same class as PR 2's minibatch fix): the distributed
    exact path must draw per-batch keys purely from (seed, i), so a
    checkpoint-resumed fit is bit-identical to the uninterrupted run —
    non-separable data, s < 1, truncated inner loop, so any key divergence
    shows in the medoids."""
    res = _run_subprocess("""
        from repro.core import KernelSpec, MiniBatchConfig
        from repro.data.sampling import split_batches
        from repro.distributed.outer import DistributedMiniBatchKMeans

        rng = np.random.default_rng(11)
        x = rng.normal(size=(1024, 8)).astype(np.float32)
        cfg = MiniBatchConfig(n_clusters=6, n_batches=4, s=0.4,
                              kernel=KernelSpec("rbf", gamma=0.5),
                              max_inner_iters=3, seed=5,
                              landmark_multiple_of=8)
        batches = split_batches(x, 4, strategy="stride")
        mesh = jax.make_mesh((8,), ("data",))

        km = DistributedMiniBatchKMeans(mesh, cfg)
        straight = km.fit(batches)
        half = DistributedMiniBatchKMeans(mesh, cfg).fit(batches[:2])
        resumed = DistributedMiniBatchKMeans(mesh, cfg).fit(
            batches[2:], state=half.state)
        same = bool((np.asarray(straight.state.medoids)
                     == np.asarray(resumed.state.medoids)).all())
        print(json.dumps({"same": same}))
    """)
    assert res["same"], "exact distributed resume diverged (key schedule)"


@pytest.mark.slow
def test_outer_batch_programs_compile_once_across_fits():
    """The outer loop's diag, Eq.8 init and Eq.7/Eq.12 merge programs are
    keyed on shapes, mesh, kernel and ``first``, never on the fit: a second
    two-batch fit on the same shapes, by a new instance with another seed,
    adds no entry to their caches, and its diag/init/merge spans lower
    nothing. Guards against a static argument that changes per fit."""
    res = _run_subprocess("""
        import tempfile
        from repro.core import KernelSpec, MiniBatchConfig
        from repro.data.sampling import split_batches
        from repro.distributed import outer
        from repro.obs import JsonlRecorder

        rng = np.random.default_rng(4)
        x = rng.normal(size=(1027, 6)).astype(np.float32)  # ghost rows
        batches = split_batches(x, 2, strategy="stride")
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        progs = (outer._diag_program, outer._init_program,
                 outer._merge_program)

        def fit(seed, rec=None):
            cfg = MiniBatchConfig(n_clusters=4, n_batches=2, s=0.5,
                                  kernel=KernelSpec("rbf", gamma=0.5),
                                  max_inner_iters=3, seed=seed,
                                  landmark_multiple_of=8)
            return outer.DistributedMiniBatchKMeans(
                mesh, cfg, recorder=rec).fit(batches)

        first = fit(1)
        sizes = [p._cache_size() for p in progs]
        path = os.path.join(tempfile.mkdtemp(), "fit.jsonl")
        with JsonlRecorder(path) as rec:
            second = fit(2, rec)
        lowerings = {}
        for line in open(path):
            r = json.loads(line)
            if r.get("kind") == "span" and r["name"] in (
                    "outer/diag", "outer/init", "outer/merge"):
                lowerings.setdefault(r["name"], []).append(r["lowerings"])
        differ = bool((np.asarray(first.state.medoids)
                       != np.asarray(second.state.medoids)).any())
        print(json.dumps({"before": sizes, "differ": differ,
                          "after": [p._cache_size() for p in progs],
                          "lowerings": lowerings}))
    """)
    assert res["differ"], "the two seeds fitted the same medoids"
    assert min(res["before"]) >= 1 and res["before"][2] == 2
    assert res["after"] == res["before"]
    assert res["lowerings"] == {"outer/diag": [0, 0], "outer/init": [0, 0],
                                "outer/merge": [0, 0]}


@pytest.mark.slow
def test_streaming_sharded_csr_end_to_end():
    """Acceptance: RCV1-scale synthetic stream (d >= 40k) through the full
    pipeline — ragged CSR chunks -> stream_blocks -> prefetch staging ->
    per-device O(nnz) sketch -> psum Lloyd — with the dense paths BOOBY-
    TRAPPED so any [n, d] densification anywhere in the pipeline fails the
    test. Labels must equal the single-host dense oracle bit-for-bit, and a
    mid-stream checkpoint resume (elastic: smaller mesh) must reproduce the
    straight run exactly."""
    res = _run_subprocess("""
        import tempfile
        from repro.core import KernelSpec, MiniBatchConfig
        from repro.core.minibatch import fit
        from repro.data import BatchSource, split_batches
        from repro.data.sparse import is_sparse, slice_rows, to_dense
        from repro.data.synthetic import make_rcv1_sparse
        from repro.distributed.embed import DistributedEmbedKMeans
        from repro.ft.checkpoint import CheckpointManager
        from repro.ft.elastic import ElasticClusteringRunner, SimulatedFailure
        import repro.approx.sketch as sketch_mod
        import repro.data.sparse as sparse_mod

        n, vocab, c, b = 2048, 40960, 10, 4
        xs, y = make_rcv1_sparse(n, vocab=vocab, n_classes=c, seed=0)
        dense = to_dense(xs)                       # oracle, built up front
        cfg = MiniBatchConfig(n_clusters=c, n_batches=b, sampling="block",
                              kernel=KernelSpec("linear"), seed=0,
                              method="sketch", embed_dim=128)

        rng = np.random.default_rng(1)
        def stream():
            bounds = np.unique(np.concatenate(
                [[0], rng.integers(1, n, size=17), [n]]))
            for a, z in zip(bounds[:-1], bounds[1:]):
                chunk = slice_rows(xs, int(a), int(z))
                assert is_sparse(chunk)
                yield chunk

        # booby-trap every densification route while the pipeline runs
        def boom(*a, **k):
            raise AssertionError("dense [n, d] path hit in CSR pipeline")
        saved = (sparse_mod.to_dense, sketch_mod.count_sketch_features,
                 sketch_mod.tensor_sketch_features)
        sparse_mod.to_dense = boom
        sketch_mod.count_sketch_features = boom
        sketch_mod.tensor_sketch_features = boom

        mesh = jax.make_mesh((8,), ("data",))
        km = DistributedEmbedKMeans(mesh, cfg)
        src = BatchSource.from_stream(stream(), n // b, stage=km.stage,
                                      prefetch=2)
        with src:
            straight = km.fit(src)

        # mid-stream failure after 2 committed batches, elastic resume on a
        # SMALLER mesh from the checkpoint (fmap restored from disk).
        with tempfile.TemporaryDirectory() as ckdir:
            runner = ElasticClusteringRunner(cfg, CheckpointManager(ckdir))
            try:
                runner.run(mesh, BatchSource.from_stream(stream(), n // b),
                           fail_after=2)
                raise SystemExit("expected SimulatedFailure")
            except SimulatedFailure:
                pass
            resumed = runner.run(
                jax.make_mesh((4,), ("data",)),
                BatchSource.from_stream(stream(), n // b))

        (sparse_mod.to_dense, sketch_mod.count_sketch_features,
         sketch_mod.tensor_sketch_features) = saved

        # oracle: single-host fit on the dense matrix, same block batches
        oracle = fit(split_batches(dense, b, strategy="block"), cfg)
        lab_s = np.asarray(straight.predict(xs))
        lab_o = np.asarray(oracle.predict(dense))
        lab_r = np.asarray(resumed.predict(xs))
        print(json.dumps({
            "d": vocab,
            "oracle_same": bool((lab_s == lab_o).all()),
            "resume_same": bool((lab_r == lab_s).all()),
            "batches": int(resumed.state.batches_done),
            "cards": float(np.asarray(straight.state.cardinalities).sum())}))
    """)
    assert res["d"] >= 40000
    assert res["oracle_same"], "streaming labels != single-host dense oracle"
    assert res["resume_same"], "mid-stream resume diverged from straight run"
    assert res["batches"] == 4
    assert res["cards"] == 2048.0


@pytest.mark.slow
def test_sstep_matches_synchronous_on_both_layouts():
    """s_step=2 runs two local Lloyd refinements per global sync against
    frozen remote stats — a different trajectory than the fully-synchronous
    loop, but on separable data it must land on the SAME final partition,
    on both the paper's 1-D layout and the 2-D rows x landmarks mesh,
    without inflating the global sync count (n_iter counts loop bodies
    = syncs)."""
    res = _run_subprocess("""
        from repro.core import KernelSpec
        from repro.distributed.inner import (DistributedInnerConfig,
                                             distributed_kkmeans_fit)

        rng = np.random.default_rng(7)
        centers = np.array([[0.2, 0.2], [0.8, 0.8], [0.2, 0.8], [0.8, 0.2]])
        X = np.concatenate([rng.normal(c, 0.05, size=(128, 2))
                            for c in centers]).astype(np.float32)
        perm = rng.permutation(len(X))
        x = jnp.asarray(X[perm])
        spec = KernelSpec("rbf", gamma=8.0)
        diag = spec.diag(x)
        l_idx = jnp.arange(512, dtype=jnp.int32)
        u0 = jnp.asarray(rng.integers(0, 4, 512), jnp.int32)

        layouts = {
            "1d": (jax.make_mesh((8,), ("data",)),
                   dict(row_axes=("data",), col_axis=None)),
            "2d": (jax.make_mesh((4, 2), ("data", "model")),
                   dict(row_axes=("data",), col_axis="model")),
        }
        out = {}
        for name, (mesh, ax) in layouts.items():
            runs = {}
            for s in (1, 2):
                cfg = DistributedInnerConfig(n_clusters=4, kernel=spec,
                                             s_step=s, **ax)
                runs[s] = distributed_kkmeans_fit(mesh, x, x, l_idx, diag,
                                                  u0, cfg=cfg)
            # same PARTITION, modulo cluster index permutation: the s-step
            # trajectory differs (refinements argmin stale stats), so the
            # index an escaping cluster lands on may permute even when the
            # induced partition is identical.
            l1 = np.asarray(runs[1].labels).tolist()
            l2 = np.asarray(runs[2].labels).tolist()
            pairs = set(zip(l1, l2))
            out[name] = {
                "same": len(pairs) == len(set(l1)) == len(set(l2)),
                "cost_err": abs(float(runs[1].cost) - float(runs[2].cost)),
                "syncs_1": int(runs[1].n_iter),
                "syncs_2": int(runs[2].n_iter)}
        print(json.dumps(out))
    """)
    for name, r in res.items():
        assert r["same"], f"{name}: s_step=2 partition != synchronous loop"
        assert r["cost_err"] < 1e-3, name
        # the communication-avoiding point: global syncs must not blow up
        # relative to the synchronous loop (+s allowed: on tiny problems
        # that converge in a couple of sweeps, certifying the fixpoint
        # under frozen remote stats can cost extra syncs; the ~1/s
        # reduction is measured on longer runs by
        # benchmarks/fig6_scaling.py).
        assert r["syncs_2"] <= r["syncs_1"] + 2, name
        assert r["syncs_2"] >= 1, name


@pytest.mark.slow
def test_sstep_2d_replicas_stay_consistent():
    """s-step refinements are column-local, so model-axis replicas of the
    same row block would silently diverge on a 2-D mesh if the sync did
    not canonicalize labels over the model axis — the stats psum would
    then mix partials of DIFFERENT label vectors and the returned f/g/
    counts would not describe the returned labels at all. NON-separable
    data (uniform noise, no converged fixpoint in a few sweeps) forces
    real divergence; the contract under test: the mesh result's f/g/counts
    are the stats of its labels, to a host-side recompute."""
    res = _run_subprocess("""
        from repro.core import KernelSpec
        from repro.core.engine import (GramEngine, engine_stats_raw,
                                       finalize_stats)
        from repro.distributed.inner import (DistributedInnerConfig,
                                             distributed_kkmeans_fit)

        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.uniform(size=(512, 6)), jnp.float32)
        spec = KernelSpec("rbf", gamma=2.0)
        diag = spec.diag(x)
        l_idx = jnp.arange(512, dtype=jnp.int32)
        u0 = jnp.asarray(rng.integers(0, 5, 512), jnp.int32)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        out = {}
        for s in (2, 4):
            cfg = DistributedInnerConfig(
                n_clusters=5, kernel=spec, max_iters=8, s_step=s,
                row_axes=("data",), col_axis="model")
            res = distributed_kkmeans_fit(mesh, x, x, l_idx, diag, u0,
                                          cfg=cfg)
            # host-side stats of the labels the mesh returned
            eng = GramEngine(mode="materialize")
            op_xl = eng.prepare(spec, x, x)
            u = res.labels
            f, g, counts = finalize_stats(*engine_stats_raw(
                eng, spec, op_xl, op_xl, u, u, 5))
            out[s] = {
                "counts_ok": bool(jnp.all(counts == res.counts)),
                "f_err": float(jnp.max(jnp.abs(f - res.f))),
                "g_err": float(jnp.max(jnp.abs(g - res.g)))}
        print(json.dumps(out))
    """)
    for s, r in res.items():
        assert r["counts_ok"], \
            f"s={s}: returned counts != counts of returned labels"
        # fp-reduction-order tolerance only — a single flipped label moves
        # f/g entries by O(kernel value) >> this.
        assert r["f_err"] < 1e-4, f"s={s}: f inconsistent with labels"
        assert r["g_err"] < 1e-4, f"s={s}: g inconsistent with labels"
