"""Pallas TPU kernel: tiled Mercer kernel (Gram) block evaluation.

The paper offloads kernel-matrix evaluation to the GPU (§3.3, Fig.3). The
TPU-native adaptation computes each (bm x bn) Gram tile on the MXU from
(bm x bd)/(bn x bd) VMEM-resident feature tiles, streaming the feature
dimension, and fuses the kernel epilogue (norm combine + exp / poly / cosine)
into the same kernel so HBM only ever sees X, Y, and K.

TPU grid: (M/bm, N/bn, D/bd), feature dim innermost (reduction). The fp32
accumulator lives in a VMEM scratch tile; the epilogue fires on the last
feature step. MXU alignment: the wrapper (ops.py) pads every tile dim to
multiples of 128 (rows may use 8; 16 under bf16 — the Mosaic min-tile
second-minor) and slices the result back. Feature tiles arrive in the
caller's tile dtype (kernels/precision.py: bf16 halves HBM traffic);
accumulation is always f32. GPU body: register-accumulator row panels
(kernels/backend.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import gpu_compiler_params


def _epilogue(kind: str, acc, xsq, ysq, *, gamma, coef0, degree):
    if kind == "linear":
        return acc
    if kind == "polynomial":
        return (gamma * acc + coef0) ** degree
    if kind == "cosine":
        denom = jnp.sqrt(jnp.maximum(xsq, 0.0)) * jnp.sqrt(jnp.maximum(ysq, 0.0))
        return acc / jnp.maximum(denom, 1e-12)
    if kind == "rbf":
        d2 = jnp.maximum(xsq + ysq - 2.0 * acc, 0.0)
        return jnp.exp(-gamma * d2)
    raise ValueError(kind)


def _kernel(x_ref, y_ref, xsq_ref, ysq_ref, out_ref, acc_ref, *,
            kind: str, gamma: float, coef0: float, degree: int,
            n_feat_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # [bm, bd]
    y = y_ref[...]  # [bn, bd]
    acc_ref[...] += jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == n_feat_steps - 1)
    def _fin():
        xsq = xsq_ref[...].astype(jnp.float32)        # [bm, 1]
        ysq = ysq_ref[...].astype(jnp.float32)        # [bn, 1]
        out_ref[...] = _epilogue(kind, acc_ref[...], xsq, ysq.T,
                                 gamma=gamma, coef0=coef0, degree=degree)


def _kernel_gpu(x_ref, y_ref, xsq_ref, ysq_ref, out_ref, *,
                kind: str, gamma: float, coef0: float, degree: int):
    acc = jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    xsq = xsq_ref[...].astype(jnp.float32)
    ysq = ysq_ref[...].astype(jnp.float32)
    out_ref[...] = _epilogue(kind, acc, xsq, ysq.T,
                             gamma=gamma, coef0=coef0, degree=degree)


def kernel_matrix_pallas(x, y, xsq, ysq, *, kind: str = "rbf",
                         gamma: float = 1.0, coef0: float = 1.0,
                         degree: int = 3, bm: int = 256, bn: int = 256,
                         bd: int = 512, interpret: bool = False,
                         backend: str = "tpu"):
    """K(X, Y) on pre-padded inputs.

    x: [M, D], y: [N, D] (M % bm == N % bn == D % bd == 0, zero padded, in
    the caller's tile dtype), xsq/ysq: [M, 1]/[N, 1] f32 row squared norms
    of the *unpadded* features (zero padding keeps the dot exact; norms are
    computed by ops.py).
    """
    m, d = x.shape
    n = y.shape[0]
    if backend == "gpu":
        kernel = functools.partial(
            _kernel_gpu, kind=kind, gamma=gamma, coef0=coef0, degree=degree)
        return pl.pallas_call(
            kernel,
            grid=(m // bm,),
            in_specs=[
                pl.BlockSpec((bm, d), lambda i: (i, 0)),
                pl.BlockSpec((n, d), lambda i: (0, 0)),
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                pl.BlockSpec((n, 1), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            interpret=interpret,
            **gpu_compiler_params(interpret=interpret),
        )(x, y, xsq, ysq)
    grid = (m // bm, n // bn, d // bd)
    kernel = functools.partial(
        _kernel, kind=kind, gamma=gamma, coef0=coef0, degree=degree,
        n_feat_steps=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, y, xsq, ysq)
