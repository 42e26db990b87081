"""Tests for the assignment-serving subsystem (repro.serving.artifact /
repro.serving.assign — ISSUE 10).

Covers: frozen artifact <-> FitResult predict parity (bit-identical labels
at f32, bounded NMI drift at bf16), npz save/load round-trip (bf16 tiles
exactly preserved), CSR == dense labels across every feature-map method and
both precisions, the booby-trapped padding proof (garbage padding rows
never perturb real rows' labels), the compile-count regression of the
bucket-routed ``FitResult.predict``, the continuous-batching service
(FIFO packing, partial consumption, admission control, AOT program count
== ladder size), and ``serve_footprint_bytes`` == measured
``artifact_nbytes`` at bucket=0.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.approx import make_nystrom, make_rff
from repro.approx.sketch import make_count_sketch, make_tensor_sketch
from repro.core import KernelSpec, MiniBatchConfig, nmi
from repro.core.memory import serve_footprint_bytes
from repro.core.minibatch import fit_dataset
from repro.data.sparse import csr_from_dense
from repro.data.synthetic import make_blobs
from repro.kernels import ops
from repro.serving import (DEFAULT_BUCKETS, AssignServeConfig, AssignService,
                           QueueFull, artifact_nbytes, bucket_for, freeze,
                           freeze_map, load_artifact, predict_frozen,
                           save_artifact)

_PRECISIONS = ("f32", "bf16")

#: (method id, feature-map builder) — every map the serving layer freezes.
#: orf is the orthogonal RFF variant (same artifact kind, different tables).
_MAPS = {
    "rff": lambda key, d, m: make_rff(key, d, m,
                                      KernelSpec("rbf", gamma=0.5)),
    "orf": lambda key, d, m: make_rff(key, d, m,
                                      KernelSpec("rbf", gamma=0.5),
                                      orthogonal=True),
    "nystrom": lambda key, d, m: make_nystrom(
        key, jax.random.normal(key, (4 * m, d)), m,
        KernelSpec("rbf", gamma=0.5)),
    "sketch": lambda key, d, m: make_count_sketch(key, d, m,
                                                  KernelSpec("linear")),
    "tensorsketch": lambda key, d, m: make_tensor_sketch(
        key, d, m, KernelSpec("polynomial", gamma=0.5, coef0=1.0, degree=2)),
}


def _blob_artifact(method, precision="f32", *, d=6, m=32, c=4, seed=0):
    """Synthetic frozen artifact + query rows: centroids from blob means
    pushed through the map, so labels are well-separated (no float ties)."""
    x, y = make_blobs(200, d, c, sep=8.0, seed=seed)
    key = jax.random.PRNGKey(seed)
    fmap = _MAPS[method](key, d, m)
    z = np.asarray(fmap(jnp.asarray(x)), np.float64)
    centroids = np.stack([z[y == j].mean(0) for j in range(c)])
    counts = np.bincount(y, minlength=c).astype(np.float32)
    art = freeze_map(fmap, jnp.asarray(centroids, jnp.float32),
                     jnp.asarray(counts), precision=precision)
    return art, x, y


# ---------------------------------------------------------------------------
# artifact: freeze / save / load / pricing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["rff", "nystrom", "sketch",
                                    "tensorsketch"])
@pytest.mark.parametrize("precision", _PRECISIONS)
def test_roundtrip_preserves_arrays(tmp_path, method, precision):
    art, _, _ = _blob_artifact(method, precision)
    path = str(tmp_path / "art.npz")
    save_artifact(art, path)
    art2 = load_artifact(path)
    assert art2.kind == art.kind and art2.precision == art.precision
    assert art2.statics == art.statics
    for k in art.arrays:
        a, b = np.asarray(art.arrays[k]), np.asarray(art2.arrays[k])
        assert a.dtype == b.dtype, k
        # bf16 -> f32 -> bf16 is lossless: bitwise equality, not allclose
        np.testing.assert_array_equal(
            a.view(np.uint16) if a.dtype.name == "bfloat16" else a,
            b.view(np.uint16) if b.dtype.name == "bfloat16" else b,
            err_msg=k)


@pytest.mark.parametrize("method", ["rff", "nystrom", "sketch",
                                    "tensorsketch"])
@pytest.mark.parametrize("precision", _PRECISIONS)
def test_serve_footprint_prices_the_artifact(method, precision):
    """The analytic price at bucket=0 IS the measured resident bytes."""
    art, _, _ = _blob_artifact(method, precision)
    predicted = serve_footprint_bytes(
        art.n_clusters, art.dim, art.in_dim, method=art.kind,
        q_tile=2 if precision == "bf16" else None,
        degree=int(art.statics.get("degree", 2)))
    assert predicted == artifact_nbytes(art)


def test_exact_footprint_and_freeze():
    x, _ = make_blobs(120, 5, 3, seed=1)
    cfg = MiniBatchConfig(n_clusters=3, n_batches=2,
                          kernel=KernelSpec("rbf", gamma=0.5))
    res = fit_dataset(x, cfg)
    art = freeze(res)
    assert art.kind == "exact"
    assert serve_footprint_bytes(3, 0, 5, method="exact") \
        == artifact_nbytes(art)
    np.testing.assert_array_equal(np.asarray(res.predict(x)),
                                  np.asarray(predict_frozen(art, x)))


def test_freeze_requires_spec_on_exact_path():
    x, _ = make_blobs(60, 4, 2, seed=0)
    res = fit_dataset(x, MiniBatchConfig(n_clusters=2, n_batches=1))
    with pytest.raises(ValueError, match="KernelSpec"):
        freeze(res._replace(spec=None))


# ---------------------------------------------------------------------------
# predict parity: frozen vs FitResult, CSR vs dense, f32 vs bf16
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method,cfg_kw", [
    ("rff", dict(method="rff")),
    ("orf", dict(method="rff", rff_orthogonal=True)),
    ("nystrom", dict(method="nystrom")),
    ("sketch", dict(method="sketch", kernel=KernelSpec("linear"))),
    ("tensorsketch", dict(method="tensorsketch",
                          kernel=KernelSpec("polynomial", gamma=0.5,
                                            coef0=1.0, degree=2))),
])
def test_frozen_matches_live_predict_f32(method, cfg_kw):
    """freeze(result) predicts bit-identically to the live embedded path."""
    from repro.approx import predict_embedded
    x, _ = make_blobs(180, 6, 4, sep=8.0, seed=2)
    cfg = MiniBatchConfig(n_clusters=4, n_batches=2, embed_dim=32, seed=3,
                          **cfg_kw)
    res = fit_dataset(x, cfg)
    live = np.asarray(predict_embedded(jnp.asarray(x), res.state, res.fmap))
    frozen = np.asarray(predict_frozen(freeze(res), x))
    np.testing.assert_array_equal(live, frozen)
    # FitResult.predict is itself routed through the frozen bucket ladder
    np.testing.assert_array_equal(frozen, np.asarray(res.predict(x)))


@pytest.mark.parametrize("method", sorted(_MAPS))
@pytest.mark.parametrize("precision", _PRECISIONS)
def test_csr_matches_dense(method, precision):
    """CSR ingestion must label exactly like the dense path — sketch kinds
    through their O(nnz) program, the rest via row-local densification."""
    art, x, _ = _blob_artifact(method, precision)
    dense = np.asarray(predict_frozen(art, x))
    sparse = np.asarray(predict_frozen(art, csr_from_dense(x)))
    np.testing.assert_array_equal(dense, sparse)


@pytest.mark.parametrize("method", ["rff", "nystrom", "sketch"])
def test_bf16_drift_is_bounded(method):
    """bf16 tiles may flip near-tie labels, but cluster structure holds:
    NMI(f32 labels, bf16 labels) >= 0.95 on separated blobs."""
    art32, x, _ = _blob_artifact(method, "f32")
    art16, _, _ = _blob_artifact(method, "bf16")
    l32 = np.asarray(predict_frozen(art32, x))
    l16 = np.asarray(predict_frozen(art16, x))
    assert nmi(l32, l16) >= 0.95


# ---------------------------------------------------------------------------
# padding: the booby-trapped proof
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["rff", "sketch"])
def test_garbage_padding_never_perturbs_real_rows(method):
    """Pad a 5-row query to its 8-bucket with GARBAGE (1e6-scale rows,
    NaNs would poison a cross-row reduction) instead of zeros: the real
    rows' labels must be unchanged — the argmin is row-independent, which
    is exactly why zero-padding in the engine is safe."""
    from repro.serving.assign import _predict_padded
    art, x, _ = _blob_artifact(method)
    rows = np.asarray(x[:5], np.float32)
    clean = np.zeros((8, rows.shape[1]), np.float32)
    clean[:5] = rows
    trapped = np.full((8, rows.shape[1]), 1e6, np.float32)
    trapped[:5] = rows
    kw = dict(fused=False, interpret=True, backend="tpu")
    l_clean = np.asarray(_predict_padded(art, jnp.asarray(clean), **kw))
    l_trap = np.asarray(_predict_padded(art, jnp.asarray(trapped), **kw))
    np.testing.assert_array_equal(l_clean[:5], l_trap[:5])
    # and the engine's sliced output equals the clean bucket's real rows
    svc = AssignService(art, AssignServeConfig(warm=False))
    np.testing.assert_array_equal(np.asarray(svc.predict(rows)), l_clean[:5])


# ---------------------------------------------------------------------------
# compile-count regression: the bucket ladder bounds retracing
# ---------------------------------------------------------------------------


def test_predict_compile_count_bounded_by_buckets():
    """FitResult.predict at many distinct query counts may compile at most
    one program per DISTINCT BUCKET touched — the ISSUE 10 bugfix (it used
    to retrace per distinct query-batch shape)."""
    x, _ = make_blobs(200, 6, 4, sep=8.0, seed=4)
    cfg = MiniBatchConfig(n_clusters=4, n_batches=2, method="rff",
                          embed_dim=32, seed=4)
    res = fit_dataset(x, cfg)
    res.predict(x[:8])          # warm the 8-bucket
    base = ops.predict_assign._cache_size()
    for n in (3, 5, 7, 8):      # all land in the warmed 8-bucket
        res.predict(x[:n])
    assert ops.predict_assign._cache_size() == base
    res.predict(x[:60])         # first touch of the 64-bucket
    res.predict(x[:33])
    assert ops.predict_assign._cache_size() == base + 1
    res.predict(x[:200])        # chunks: 2 x 64-bucket + (72 ->) one more
    assert ops.predict_assign._cache_size() <= base + 2


def test_bucket_for():
    assert [bucket_for(n, DEFAULT_BUCKETS) for n in (1, 2, 8, 9, 64, 65,
                                                     512)] \
        == [1, 8, 8, 64, 64, 512, 512]
    with pytest.raises(ValueError):
        bucket_for(513, DEFAULT_BUCKETS)


# ---------------------------------------------------------------------------
# the continuous-batching service
# ---------------------------------------------------------------------------


def test_service_warm_compiles_ladder_and_only_ladder():
    art, x, _ = _blob_artifact("rff")
    svc = AssignService(art)
    assert svc.compiled_programs == len(DEFAULT_BUCKETS)
    svc.predict(x[:3])
    svc.predict(x[:100])
    assert svc.compiled_programs == len(DEFAULT_BUCKETS)


def test_service_program_text_is_the_bucket_program():
    """``program_text`` reads the HLO of the program a bucket runs (the
    chip smoke searches it for the Mosaic kernel), compiling nothing new."""
    art, x, _ = _blob_artifact("nystrom")
    svc = AssignService(art)
    texts = {b: svc.program_text(b) for b in DEFAULT_BUCKETS}
    assert svc.compiled_programs == len(DEFAULT_BUCKETS)
    for b, text in texts.items():
        assert "HloModule" in text
        assert f"f32[{b},{art.in_dim}]" in text
    svc.predict(x[:3])
    assert svc.compiled_programs == len(DEFAULT_BUCKETS)


def test_service_packs_fifo_and_completes_all():
    """Many small requests ride one bucket; a large one drains chunked —
    every request gets exactly its own rows' labels back."""
    art, x, _ = _blob_artifact("rff", c=4)
    want = np.asarray(predict_frozen(art, x))
    svc = AssignService(art, AssignServeConfig(buckets=(1, 8, 64),
                                               max_queue_rows=4096))
    slices = [(0, 2), (2, 5), (5, 6), (6, 40), (40, 200)]
    uids = {svc.submit(x[a:b]): (a, b) for a, b in slices}
    done = svc.drain()
    assert sorted(done) == sorted(uids)
    for uid, (a, b) in uids.items():
        np.testing.assert_array_equal(done[uid], want[a:b])


def test_service_admission_control():
    art, x, _ = _blob_artifact("rff")
    svc = AssignService(art, AssignServeConfig(max_queue_rows=10,
                                               warm=False))
    svc.submit(x[:6])
    with pytest.raises(QueueFull):
        svc.submit(x[:5])
    svc.drain()
    svc.submit(x[:5])           # capacity freed once the queue drains


def test_service_csr_and_dense_interleave():
    art, x, _ = _blob_artifact("sketch")
    want = np.asarray(predict_frozen(art, x))
    svc = AssignService(art)
    u1 = svc.submit(x[:7])
    u2 = svc.submit(csr_from_dense(np.asarray(x[7:30])))
    u3 = svc.submit(x[30:31])
    done = svc.drain()
    np.testing.assert_array_equal(done[u1], want[:7])
    np.testing.assert_array_equal(done[u2], want[7:30])
    np.testing.assert_array_equal(done[u3], want[30:31])


def test_service_records_request_obs(tmp_path):
    from repro.obs import JsonlRecorder, export
    art, x, _ = _blob_artifact("rff")
    path = str(tmp_path / "serve.jsonl")
    with JsonlRecorder(path) as rec:
        svc = AssignService(art, recorder=rec)
        svc.predict(x[:5])
        svc.predict(x[:70])
    summary = export.summarize(path)
    assert summary["counters"]["serve/submitted"] == 2
    assert summary["stats"]["serve/queue_seconds"]["count"] == 2
    assert summary["stats"]["serve/compute_seconds"]["count"] == 2
    # warm + one event per request are in the log
    with open(path) as fh:
        lines = fh.read()
    assert lines.count('"serve/request"') == 2
    assert lines.count('"serve/warm"') == 1


def test_service_rejects_bad_width():
    art, x, _ = _blob_artifact("rff")
    svc = AssignService(art, AssignServeConfig(warm=False))
    with pytest.raises(ValueError, match="queries must be"):
        svc.submit(np.zeros((3, art.in_dim + 1), np.float32))
    with pytest.raises(ValueError, match="empty"):
        svc.submit(np.zeros((0, art.in_dim), np.float32))
