"""Pallas TPU kernel: FUSED feature-map application + centroid argmin.

Embedded-space counterpart of ``kernels/assign.py`` (same VMEM-resident tile
pattern): instead of materializing the embedded batch Z = phi_m(X) [n, m] in
HBM and then running a linear-k-means assignment over it, a single kernel

  1. builds each (bm x bme) projection tile A = X W^T in VMEM (MXU),
     streaming the feature dim,
  2. applies the map epilogue in-register —
       * ``rff``:    E = scale * cos(A + b)           (random Fourier map)
       * Mercer kinds: E = epilogue(A, |x|^2, |l|^2)  (Nystrom: W = landmarks,
         the whitening projection is folded into V outside the kernel),
  3. immediately contracts E against the "value" panel V [m, Cp]
     (centroids^T for RFF, proj @ centroids^T for Nystrom) to accumulate
     the cross term F = Z C^T,
  4. on the last embed tile computes argmin_j (|c_j|^2 - 2 F_ij).

Z never touches HBM: per-row traffic is O(d + C) regardless of m. The
returned score is ||z - c_j||^2 - ||z||^2 (the row-constant ||z||^2 is
dropped — it cannot change the argmin and, for Nystrom, is not computable
without materializing Z).

TPU grid: (rows/bm, M/bme, D/bd); embed and feature dims are reductions.
Scratch: fp32 projection tile [bm, bme] + fp32 F accumulator [bm, Cp] —
accumulators stay f32 whatever the tile dtype (x/w may arrive bf16 under
the kernels/precision.py policy: half the HBM/VMEM per tile, f32 math).
The per-tile HBM loads are pipelined against the MXU by the Mosaic grid
machinery (BlockSpec index maps); the fused exact-assignment kernel
(kernels/assign.py) additionally hand-double-buffers its tiles.

GPU body (``backend="gpu"``): register-accumulator row-block variant, see
kernels/backend.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import gpu_compiler_params
from .kernel_matrix import _epilogue


def _kernel(x_ref, w_ref, xsq_ref, aux_ref, v_ref, csq_ref,
            labels_ref, score_ref, acc_a_ref, acc_f_ref, *,
            map_kind: str, gamma: float, coef0: float, degree: int,
            scale: float, n_embed_steps: int, n_feat_steps: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_f():
        acc_f_ref[...] = jnp.zeros_like(acc_f_ref)

    @pl.when(k == 0)
    def _init_a():
        acc_a_ref[...] = jnp.zeros_like(acc_a_ref)

    acc_a_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_feat_steps - 1)
    def _contract():
        aux = aux_ref[...].astype(jnp.float32)          # [bme, 1]
        if map_kind == "rff":
            e = scale * jnp.cos(acc_a_ref[...] + aux.T)
        else:
            xsq = xsq_ref[...].astype(jnp.float32)      # [bm, 1]
            e = _epilogue(map_kind, acc_a_ref[...], xsq, aux.T,
                          gamma=gamma, coef0=coef0, degree=degree)
        acc_f_ref[...] += jax.lax.dot_general(
            e, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(j == n_embed_steps - 1)
        def _argmin():
            score = csq_ref[...].astype(jnp.float32) - 2.0 * acc_f_ref[...]
            labels_ref[...] = jnp.argmin(score, axis=1, keepdims=True
                                         ).astype(jnp.int32)
            score_ref[...] = jnp.min(score, axis=1, keepdims=True)


def _kernel_gpu(x_ref, w_ref, xsq_ref, aux_ref, v_ref, csq_ref,
                labels_ref, score_ref, *,
                map_kind: str, gamma: float, coef0: float, degree: int,
                scale: float):
    a = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    aux = aux_ref[...].astype(jnp.float32)
    if map_kind == "rff":
        e = scale * jnp.cos(a + aux.T)
    else:
        xsq = xsq_ref[...].astype(jnp.float32)
        e = _epilogue(map_kind, a, xsq, aux.T,
                      gamma=gamma, coef0=coef0, degree=degree)
    f = jax.lax.dot_general(
        e, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    score = csq_ref[...].astype(jnp.float32) - 2.0 * f
    labels_ref[...] = jnp.argmin(score, axis=1, keepdims=True
                                 ).astype(jnp.int32)
    score_ref[...] = jnp.min(score, axis=1, keepdims=True)


def embed_assign_pallas(x, w, xsq, aux, v, csq, *,
                        map_kind: str = "rff", gamma: float = 1.0,
                        coef0: float = 1.0, degree: int = 3,
                        scale: float = 1.0,
                        bm: int = 256, bme: int = 256, bd: int = 512,
                        interpret: bool = False, backend: str = "tpu"):
    """Fused embed+assign on pre-padded inputs.

    x: [n, D] rows; w: [M, D] frequencies/landmarks; xsq: [n, 1] squared
    norms (Mercer epilogues); aux: [M, 1] phases (rff) or landmark squared
    norms (Mercer); v: [M, Cp] value panel (zero rows for padded embed dims);
    csq: [1, Cp] centroid squared norms (+BIG on padded clusters).
    Returns (labels [n, 1] int32, score [n, 1] f32 = min_j |c_j|^2 - 2 z.c_j).
    """
    n, d = x.shape
    m = w.shape[0]
    cp = v.shape[1]
    if backend == "gpu":
        kernel = functools.partial(
            _kernel_gpu, map_kind=map_kind, gamma=gamma, coef0=coef0,
            degree=degree, scale=scale)
        return pl.pallas_call(
            kernel,
            grid=(n // bm,),
            in_specs=[
                pl.BlockSpec((bm, d), lambda i: (i, 0)),    # x row panel
                pl.BlockSpec((m, d), lambda i: (0, 0)),     # w
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),    # xsq
                pl.BlockSpec((m, 1), lambda i: (0, 0)),     # aux
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
        )(x, w, xsq, aux, v, csq)
    grid = (n // bm, m // bme, d // bd)
    kernel = functools.partial(
        _kernel, map_kind=map_kind, gamma=gamma, coef0=coef0, degree=degree,
        scale=scale, n_embed_steps=grid[1], n_feat_steps=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),    # x
            pl.BlockSpec((bme, bd), lambda i, j, k: (j, k)),   # w
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),     # xsq
            pl.BlockSpec((bme, 1), lambda i, j, k: (j, 0)),    # aux
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
    )(x, w, xsq, aux, v, csq)
