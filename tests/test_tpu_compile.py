"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's Mosaic
compiler refuses: tiles that do not align, VMEM overrun, layouts it cannot
relayout. These tests compile each kernel of the main path at the shapes
``chip_smoke.py`` runs (the paper's MNIST shape: 70,000 x 784, C=10), for a
chip that is described and not attached, at both tile precisions, and check
that the compiled program calls the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.approx.nystrom import NystromMap
from repro.approx.rff import RFFMap
from repro.approx.sketch import CountSketchMap
from repro.core.kernels import KernelSpec
from repro.kernels import ops

N, D, C = 70_000, 784, 10        # MNIST (paper Tab.1)
ROWS = N // 2                    # one mini-batch of the B=2 exact plan
M = 1024                         # embedding dim of the embedded fit
GAMMA = 1e-4
PRECISIONS = ("f32", "bf16")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _tpu(precision):
    return dict(interpret=False, backend="tpu", precision=precision)


@pytest.mark.parametrize("precision", PRECISIONS)
def test_assign_fused_compiles(one_chip, precision):
    s = lambda *a: _sds(one_chip, *a)   # noqa: E731
    _assert_mosaic(
        lambda x, lm, lab, cnt, g: ops.assign_fused(
            x, lm, lab, cnt, g, n_clusters=C, gamma=GAMMA, **_tpu(precision)),
        s((ROWS, D)), s((ROWS, D)), s((ROWS,), jnp.int32), s((C,)), s((C,)))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_gram_matvec_compiles(one_chip, precision):
    s = lambda *a: _sds(one_chip, *a)   # noqa: E731
    _assert_mosaic(
        lambda x, lm, h: ops.gram_matvec(x, lm, h, gamma=GAMMA,
                                         **_tpu(precision)),
        s((ROWS, D)), s((ROWS, D)), s((ROWS, C)))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_kernel_matrix_compiles(one_chip, precision):
    s = lambda *a: _sds(one_chip, *a)   # noqa: E731
    _assert_mosaic(
        lambda x, y: ops.kernel_matrix(x, y, gamma=GAMMA, **_tpu(precision)),
        s((N, D)), s((M, D)))


def _fmap(kind, s):
    if kind == "rff":
        return RFFMap(w=s((M, D)), b=s((M,)), scale=(2.0 / M) ** 0.5)
    if kind == "nystrom":
        return NystromMap(landmarks=s((M, D)), proj=s((M, M)),
                          spec=KernelSpec("rbf", gamma=GAMMA))
    return CountSketchMap(h=s((D,), jnp.int32), sign=s((D,)), m=M)


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("kind", ["rff", "nystrom"])
def test_embed_assign_compiles(one_chip, kind, precision):
    s = lambda *a: _sds(one_chip, *a)   # noqa: E731
    _assert_mosaic(
        lambda x, fmap, c, cnt: ops.embed_assign(x, fmap, c, cnt,
                                                 **_tpu(precision)),
        s((N, D)), _fmap(kind, s), s((C, M)), s((C,)))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_sketch_assign_compiles(one_chip, precision):
    s = lambda *a: _sds(one_chip, *a)   # noqa: E731
    _assert_mosaic(
        lambda x, fmap, c, cnt: ops.sketch_assign(x, fmap, c, cnt,
                                                  **_tpu(precision)),
        s((N, D)), _fmap("sketch", s), s((C, M)), s((C,)))


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("bucket", [1, 512])
@pytest.mark.parametrize("kind", ["rff", "nystrom", "sketch"])
def test_predict_assign_compiles(one_chip, kind, bucket, precision):
    """The serving bucket programs, with the panels a frozen artifact holds
    (``serving.artifact.freeze_map``)."""
    s = lambda *a: _sds(one_chip, *a)   # noqa: E731
    if kind == "sketch":
        sign_dtype = jnp.int8 if precision == "bf16" else jnp.float32
        w, aux = s((D,), jnp.int32), s((D,), sign_dtype)
        statics = dict(map_kind="sketch")
    else:
        tile = jnp.bfloat16 if precision == "bf16" else jnp.float32
        w, aux = s((M, D), tile), s((M, 1))
        statics = (dict(map_kind="rff", scale=(2.0 / M) ** 0.5)
                   if kind == "rff" else dict(map_kind="rbf", gamma=GAMMA))
    _assert_mosaic(
        lambda x, w, aux, v, csq: ops.predict_assign(
            x, w, aux, v, csq, fused=True, **statics, **_tpu(precision)),
        s((bucket, D)), w, aux, s((M, C)), s((C,)))
