"""Pallas TPU kernel: FUSED count-sketch application + centroid argmin.

Scatter-add variant of ``kernels/embed_assign.py`` for the hashing map
(``repro.approx.sketch.CountSketchMap``): the embedding is not a dense
projection ``X @ W^T`` but a signed scatter of input columns into m buckets,

    z(x)_j = sum_{i : h(i) = j} sign_i * x_i.

TPUs have no efficient cross-lane scatter, so the kernel realizes the
scatter-add as a masked one-hot contraction on the MXU: each (bd x bme)
sketch tile

    S[c, j] = sign_c * [h_c == j_global]

is *built in VMEM* from the O(d) integer tables (never materialized in HBM —
a dense S would be an [m, d] array, exactly the footprint sketching exists
to avoid) and contracted against the row tile, A += X_tile @ S. The rest is
the embed_assign pipeline: on the last feature step the finished embedding
tile E = A is contracted against the value panel V = centroids^T, and the
last embed step computes ``argmin_j |c_j|^2 - 2 z.c_j``. Z never touches
HBM; per-row traffic is O(d + C) regardless of m.

Padding contract: padded feature columns carry ``h = -1`` (matches no
bucket), padded embed dims are buckets >= m (matched by no column, value
rows zeroed), padded clusters carry ``csq = +BIG``.

Off-TPU the wrapper (ops.sketch_assign) runs this body in interpret mode
for tests; production CPU/GPU prediction should use the jnp fallback path
(``predict_embedded(..., use_fused=False)``, i.e. ``fmap(x)`` +
``assign_embedded``) which materializes Z but costs the same
O(n(d + mC)) flops.

Grid: (rows/bm, M/bme, D/bd); embed and feature dims are reductions.
Scratch: fp32 sketch-accumulator tile [bm, bme] + fp32 F accumulator
[bm, Cp].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import gpu_compiler_params


def _kernel(x_ref, h_ref, sign_ref, v_ref, csq_ref,
            labels_ref, score_ref, acc_a_ref, acc_f_ref, *,
            n_embed_steps: int, n_feat_steps: int, bme: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_f():
        acc_f_ref[...] = jnp.zeros_like(acc_f_ref)

    @pl.when(k == 0)
    def _init_a():
        acc_a_ref[...] = jnp.zeros_like(acc_a_ref)

    xt = x_ref[...]                                  # [bm, bd] tile dtype
    h = h_ref[...]                                   # [bd, 1] int32
    # sign table storage is a precision-policy choice (int8 under bf16 —
    # ±1 is exact in every float format). The sketch tile is selected in
    # f32 and cast once to the x tile dtype, so the MXU contraction sees
    # matched operands: Mosaic cannot relayout the [bd, bme] mask for a
    # packed (bf16) select.
    sign = sign_ref[...].astype(jnp.float32)         # [bd, 1]
    bd = h.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bd, bme), 1) + j * bme
    s = jnp.where(h == lane, sign, 0.0).astype(xt.dtype)
    acc_a_ref[...] += jax.lax.dot_general(
        xt, s, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_feat_steps - 1)
    def _contract():
        acc_f_ref[...] += jax.lax.dot_general(
            acc_a_ref[...], v_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(j == n_embed_steps - 1)
        def _argmin():
            score = csq_ref[...].astype(jnp.float32) - 2.0 * acc_f_ref[...]
            labels_ref[...] = jnp.argmin(score, axis=1, keepdims=True
                                         ).astype(jnp.int32)
            score_ref[...] = jnp.min(score, axis=1, keepdims=True)


def _kernel_gpu(x_ref, h_ref, sign_ref, v_ref, csq_ref,
                labels_ref, score_ref, *, m: int):
    xt = x_ref[...]                                  # [bm, D]
    h = h_ref[...]                                   # [D, 1] int32
    sign = sign_ref[...].astype(xt.dtype)            # [D, 1]
    d = h.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (d, m), 1)
    s = jnp.where(h == lane, sign, jnp.zeros((), xt.dtype))
    z = jax.lax.dot_general(xt, s, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    f = jax.lax.dot_general(z, v_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    score = csq_ref[...].astype(jnp.float32) - 2.0 * f
    labels_ref[...] = jnp.argmin(score, axis=1, keepdims=True
                                 ).astype(jnp.int32)
    score_ref[...] = jnp.min(score, axis=1, keepdims=True)


def sketch_assign_pallas(x, h, sign, v, csq, *,
                         bm: int = 256, bme: int = 256, bd: int = 512,
                         interpret: bool = False, backend: str = "tpu"):
    """Fused count-sketch + assign on pre-padded inputs.

    x: [n, D] rows (tile dtype); h: [D, 1] int32 bucket ids (-1 on padded
    columns); sign: [D, 1] Rademacher signs — f32 at full precision, int8
    under the bf16 policy (0 on padding either way); v: [M, Cp] value panel
    (centroids^T, zero rows for padded embed dims); csq: [1, Cp] centroid
    squared norms (+BIG on padded clusters).
    Returns (labels [n, 1] int32, score [n, 1] f32 = min_j |c_j|^2 - 2 z.c_j).
    """
    n, d = x.shape
    m = v.shape[0]
    cp = v.shape[1]
    if backend == "gpu":
        return pl.pallas_call(
            functools.partial(_kernel_gpu, m=m),
            grid=(n // bm,),
            in_specs=[
                pl.BlockSpec((bm, d), lambda i: (i, 0)),    # x row panel
                pl.BlockSpec((d, 1), lambda i: (0, 0)),     # h
                pl.BlockSpec((d, 1), lambda i: (0, 0)),     # sign
                pl.BlockSpec((m, cp), lambda i: (0, 0)),    # v
                pl.BlockSpec((1, cp), lambda i: (0, 0)),    # csq
            ],
            out_specs=[
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, 1), jnp.int32),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
            ],
            interpret=interpret,
            **gpu_compiler_params(interpret=interpret),
        )(x, h, sign, v, csq)
    grid = (n // bm, m // bme, d // bd)
    kernel = functools.partial(
        _kernel, n_embed_steps=grid[1], n_feat_steps=grid[2], bme=bme)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),    # x
            pl.BlockSpec((bd, 1), lambda i, j, k: (k, 0)),     # h
            pl.BlockSpec((bd, 1), lambda i, j, k: (k, 0)),     # sign
            pl.BlockSpec((bme, cp), lambda i, j, k: (j, 0)),   # v
            pl.BlockSpec((1, cp), lambda i, j, k: (0, 0)),     # csq
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bme), jnp.float32),
            pltpu.VMEM((bm, cp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x, h, sign, v, csq)
