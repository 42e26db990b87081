"""Tests for the landmark-selection subsystem (repro.approx.selectors).

Covers: restart determinism (same key => same landmarks), streaming folds
over a ``BatchSource`` bit-identical to the offline sample under any
re-chunking, ``SelectorState`` checkpoint round-trip + mid-stream resume
through ``repro.ft.checkpoint``, RLS actually covering starved clusters,
the consolidated ``num_landmarks`` feasibility errors, selector dispatch
through the exact path and the config validation, and the mesh-native
psum RLS selection matching the single-host selector (subprocess, 8
forced host devices — same pattern as test_distributed.py).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.approx import make_feature_map, selectors
from repro.core import (KernelSpec, MiniBatchConfig, nmi, num_landmarks,
                        select_landmark_indices)
from repro.core.minibatch import fit_dataset
from repro.data.loader import BatchSource
from repro.ft.checkpoint import CheckpointManager

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SPEC = KernelSpec("rbf", gamma=0.4)


def _data(n=400, d=6, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


# ---------------------------------------------------------------------------
# determinism + streaming/offline equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", selectors.NAMES)
def test_same_key_same_landmarks_across_restarts(name):
    """Selection is a pure function of (key, data): re-running — as a
    restarted process would — draws identical landmarks; a different key
    draws different ones."""
    x = _data()
    sel = selectors.resolve(name)
    a = np.asarray(sel.select_indices(jax.random.PRNGKey(3), x, 24, _SPEC))
    b = np.asarray(sel.select_indices(jax.random.PRNGKey(3), x, 24, _SPEC))
    np.testing.assert_array_equal(a, b)
    assert len(np.unique(a)) == 24
    assert (np.sort(a) == a).all()          # sorted: DMA-friendly gathers
    c = np.asarray(sel.select_indices(jax.random.PRNGKey(4), x, 24, _SPEC))
    assert not (a == c).all()


@pytest.mark.parametrize("name", selectors.NAMES)
@pytest.mark.parametrize("n_chunks", [1, 3, 7])
def test_streaming_matches_offline_bitwise(name, n_chunks):
    """Folding a BatchSource — under ANY re-chunking — selects landmarks
    bit-identical to the offline sample (per-gid fold_in keys)."""
    x = _data()
    key = jax.random.PRNGKey(11)
    sel = selectors.resolve(name)
    offline = np.asarray(sel.select(key, x, 20, _SPEC))
    src = BatchSource(np.array_split(x, n_chunks))
    streamed, state = selectors.select_streaming(name, key, src, 20, _SPEC)
    np.testing.assert_array_equal(np.asarray(streamed), offline)
    assert int(state.rows_seen) == len(x)
    assert int(state.folds) == n_chunks


def test_streaming_pool_caps_memory_and_stays_boundary_invariant():
    x = _data(n=300)
    key = jax.random.PRNGKey(5)
    sel = selectors.RLSSelector(pool=128)
    lm3, st3 = selectors.select_streaming(sel, key, np.array_split(x, 3),
                                          16, _SPEC)
    lm5, st5 = selectors.select_streaming(sel, key, np.array_split(x, 5),
                                          16, _SPEC)
    assert st3.rows.shape[0] == 128          # capped
    np.testing.assert_array_equal(np.asarray(lm3), np.asarray(lm5))


def test_selector_state_checkpoint_roundtrip_and_resume(tmp_path):
    """SelectorState is a checkpointable pytree: fold half the stream,
    checkpoint via ft.checkpoint, 'crash', restore, fold the rest — the
    final landmarks are bit-identical to the uninterrupted fold AND to the
    offline sample (the elastic mid-stream resume guarantee)."""
    x = _data(n=360, d=5)
    key = jax.random.PRNGKey(9)
    batches = np.array_split(x, 6)
    sel = selectors.resolve("rls")
    ckpt = CheckpointManager(str(tmp_path), keep=10)

    def cb(state, i):
        ckpt.save(i, state, extra={"d": x.shape[1]})

    # straight run (also exercises checkpoint_cb on every fold)
    straight, _ = selectors.select_streaming("rls", key, batches, 18, _SPEC,
                                             checkpoint_cb=cb)
    # crash after fold 2 (steps 0..2 committed), restore, resume
    step = 2
    like = selectors.state_like(x.shape[1])
    restored = selectors.SelectorState(*ckpt.restore(step, like))
    assert int(restored.folds) == step + 1
    src = BatchSource(batches).skip(int(restored.folds))
    resumed, _ = selectors.select_streaming("rls", key, src, 18, _SPEC,
                                            state=restored)
    np.testing.assert_array_equal(np.asarray(resumed), np.asarray(straight))
    np.testing.assert_array_equal(np.asarray(resumed),
                                  np.asarray(sel.select(key, x, 18, _SPEC)))


def test_streaming_rejects_sparse_and_empty():
    from repro.data.sparse import csr_from_dense
    with pytest.raises(ValueError, match="dense"):
        selectors.resolve("rls").fold(
            selectors.resolve("rls").init(jax.random.PRNGKey(0), 4),
            csr_from_dense(_data(8, 4)))
    with pytest.raises(ValueError, match="empty"):
        selectors.select_streaming("uniform", jax.random.PRNGKey(0), [],
                                   4, _SPEC)


# ---------------------------------------------------------------------------
# selection quality: RLS covers what uniform starves
# ---------------------------------------------------------------------------


def test_rls_covers_starved_clusters_better_than_uniform():
    """One dominant cluster (97%) + three tiny ones: a uniform m-sample
    usually leaves tiny clusters without any landmark; high ridge leverage
    lives exactly there, so RLS must cover more of them."""
    rng = np.random.default_rng(2)
    centers = np.array([[0, 0], [8, 8], [-8, 8], [8, -8]], np.float32)
    sizes = [970, 10, 10, 10]
    x = np.concatenate([rng.normal(c, 0.3, size=(s, 2))
                        for c, s in zip(centers, sizes)]).astype(np.float32)
    y = np.repeat(np.arange(4), sizes)
    perm = rng.permutation(len(x))
    x, y = x[perm], y[perm]
    spec = KernelSpec("rbf", gamma=0.5)

    def tiny_covered(name, key):
        idx = np.asarray(selectors.resolve(name).select_indices(
            key, jnp.asarray(x), 8, spec))
        return len(set(y[idx]) - {0})      # distinct tiny clusters hit

    keys = [jax.random.PRNGKey(k) for k in range(8)]
    unif = sum(tiny_covered("uniform", k) for k in keys)
    rls = sum(tiny_covered("rls", k) for k in keys)
    assert rls > unif, (rls, unif)
    assert rls >= 8 * 3 - 4                # RLS nearly always covers all 3


# ---------------------------------------------------------------------------
# dispatch: exact path, config validation, make_feature_map gating
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["rls", "kpp"])
def test_exact_path_selector_dispatch(name, blobs):
    x, y = blobs
    cfg = MiniBatchConfig(n_clusters=4, n_batches=4, s=0.4,
                          kernel=KernelSpec("rbf", gamma=8.0), seed=0,
                          selector=name)
    res = fit_dataset(x, cfg)
    assert nmi(y, np.asarray(res.predict(x))) >= 0.9
    # resumed == uninterrupted (pure per-batch fold_in schedule)
    from repro.data.sampling import split_batches
    from repro.core.minibatch import fit
    batches = split_batches(x, 4, strategy="stride")
    half = fit(batches[:2], cfg)
    resumed = fit(batches[2:], cfg, state=half.state)
    np.testing.assert_array_equal(np.asarray(resumed.state.medoids),
                                  np.asarray(res.state.medoids))


def test_select_landmark_indices_uniform_is_choose_landmarks():
    from repro.core import choose_landmarks
    x = _data(100, 3)
    key = jax.random.PRNGKey(1)
    np.testing.assert_array_equal(
        np.asarray(select_landmark_indices(key, jnp.asarray(x), 16, _SPEC)),
        np.asarray(choose_landmarks(key, 100, 16)))


def test_config_rejects_selector_on_data_oblivious_methods():
    with pytest.raises(ValueError, match="selector"):
        MiniBatchConfig(n_clusters=4, method="rff", selector="rls")
    with pytest.raises(ValueError, match="selector"):
        MiniBatchConfig(n_clusters=4, method="sketch", selector="kpp")
    with pytest.raises(ValueError, match="unknown landmark selector"):
        MiniBatchConfig(n_clusters=4, selector="bogus")
    # landmark-based methods accept any selector (incl. instances)
    MiniBatchConfig(n_clusters=4, selector="rls")
    MiniBatchConfig(n_clusters=4, method="nystrom",
                    selector=selectors.RLSSelector(delta=1e-3))
    with pytest.raises(ValueError, match="selector"):
        make_feature_map("rff", jax.random.PRNGKey(0), _data(16, 4), 8,
                         KernelSpec("rbf"), selector="rls")


def test_num_landmarks_consolidated_feasibility_errors():
    # C > batch: no silent min() clamp below C any more
    with pytest.raises(ValueError, match="infeasible"):
        num_landmarks(8, 1.0, n_clusters=16)
    # no multiple of `multiple_of` in [C, batch]
    with pytest.raises(ValueError, match="infeasible"):
        num_landmarks(10, 0.5, n_clusters=5, multiple_of=16)
    # feasible combinations keep the documented bounds
    assert num_landmarks(100, 0.3, n_clusters=4) == 30
    assert num_landmarks(100, 0.3, n_clusters=4, multiple_of=8) == 32
    assert num_landmarks(100, 1.0, n_clusters=4, multiple_of=8) == 96
    assert num_landmarks(4, 0.1, n_clusters=4) == 4


# ---------------------------------------------------------------------------
# planner: selector cost + frontier
# ---------------------------------------------------------------------------


def test_plan_selector_term_and_frontier():
    from repro.core import MachineSpec, plan, selector_footprint_bytes
    machine = MachineSpec(memory_bytes=16e9, n_processors=64)
    p = plan(2_000_000, 50, machine, d=256, selector="rls", sketchable=True,
             density=0.01)
    assert p.selector == "rls"
    assert p.selector_footprint > 0
    assert p.selector_footprint > selector_footprint_bytes(
        2_000_000, p.b, 64, m=p.embed_dim, selector="uniform")
    front = p.frontier()
    names = [f"{r['method']}:{r['selector']}" for r in front]
    assert "nystrom:rls" in names and "nystrom:uniform" in names
    assert "sketch:-" in names
    # the frontier's whole claim: leverage selection buys more accuracy
    # from the same byte budget than uniform sampling
    assert names.index("nystrom:rls") < names.index("nystrom:uniform")
    for r in front:
        assert r["bytes"] <= p.embed_footprint + p.selector_footprint + 1
        assert 0.0 <= r["predicted_accuracy"] <= 1.0
    # explicit budget: more bytes -> at least as much predicted accuracy
    small = p.frontier(budget_bytes=front[0]["bytes"] / 4)
    if small:
        assert small[0]["predicted_accuracy"] <= front[0]["predicted_accuracy"]
    with pytest.raises(ValueError, match="unknown selector"):
        plan(2_000_000, 50, machine, d=256, selector="bogus")


# ---------------------------------------------------------------------------
# distributed: mesh-native psum RLS == single-host selector
# ---------------------------------------------------------------------------


def _run_subprocess(body: str) -> dict:
    script = textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax
        import jax.numpy as jnp
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_distributed_rls_selection_matches_single_host():
    """The mesh-native RLS path (per-device partial leverage sketches, one
    psum, ghost rows masked) must select the same landmarks as the
    single-host selector and produce the same labels — including a
    non-divisible batch (pad > 0)."""
    res = _run_subprocess("""
        from repro.core import KernelSpec, MiniBatchConfig
        from repro.core.minibatch import fit as host_fit
        from repro.core.metrics import nmi
        from repro.distributed.embed import DistributedEmbedKMeans

        rng = np.random.default_rng(0)
        centers = np.array([[0.25,0.25],[0.75,0.75],[0.25,0.75],[0.75,0.25]])
        X = np.concatenate([rng.normal(c, 0.05, size=(515, 2))
                            for c in centers]).astype(np.float32)
        y = np.repeat(np.arange(4), 515)
        perm = rng.permutation(len(X)); X, y = X[perm], y[perm]
        batches = [X[i::4] for i in range(4)]      # 515 rows: pad = 5 on 8

        mesh = jax.make_mesh((8,), ("data",))
        cfg = MiniBatchConfig(n_clusters=4, n_batches=4, seed=0,
                              kernel=KernelSpec("rbf", gamma=8.0),
                              method="nystrom", embed_dim=24,
                              selector="rls")
        km = DistributedEmbedKMeans(mesh, cfg)
        with km.source(batches, depth=2) as src:
            dist = km.fit(src)
        host = host_fit(batches, cfg)
        lm_same = bool((np.asarray(dist.fmap.landmarks)
                        == np.asarray(host.fmap.landmarks)).all())
        labels = np.asarray(dist.predict(jnp.asarray(X)))
        label_same = bool(
            (labels == np.asarray(host.predict(jnp.asarray(X)))).all())
        # the map and the served artifact live on one device: a Mosaic
        # kernel outside shard_map cannot be partitioned over the mesh
        from repro.serving import freeze
        one_device = [len(a.devices()) == 1 for a in
                      [dist.fmap.landmarks, dist.fmap.proj]
                      + list(freeze(dist).arrays.values())]
        print(json.dumps({
            "lm_same": lm_same, "label_same": label_same,
            "one_device": all(one_device),
            "nmi": nmi(y, labels),
            "total": float(np.asarray(dist.state.cardinalities).sum()),
            "n": len(X)}))
    """)
    assert res["lm_same"], "mesh RLS selected different landmarks"
    assert res["label_same"]
    assert res["one_device"], "map or artifact spread over the mesh"
    assert res["nmi"] >= 0.9
    assert res["total"] == res["n"]      # ghost rows masked out
