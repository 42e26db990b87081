"""Pallas kernel: FUSED kernel-evaluation + masked-reduction + argmin.

This is the beyond-paper optimization of the inner-loop assignment step
(DESIGN.md §2): instead of materializing the mini-batch kernel block
K^i [rows x |L|] in HBM (the paper's producer/consumer hand-off) and then
reducing it against the label one-hot, a single kernel

  1. builds each (bm x bl) Gram tile in VMEM from feature tiles (MXU),
  2. immediately contracts it against the normalized one-hot H [bl x C]
     to accumulate f = K @ H (Eq.17),
  3. on the last landmark tile emits f and computes
     argmin_j (g_j - 2 f_ij) (Eq.15).

K never touches HBM: per-row traffic drops from O(|L|) Gram elements to
O(d + C), raising arithmetic intensity from ~1 FLOP/byte to ~|L| FLOPs/byte
(see EXPERIMENTS.md §Perf for the measured roofline shift).

The f panel [rows, Cp] IS written back (O(C) per row — negligible next to
the O(|L|) Gram block it replaces): the outer loop needs the cluster-average
similarities at the fixpoint for the Eq.7 medoid argmin, and the GramEngine
``fused`` mode (repro.core.engine) uses the same kernel as a Gram-free
matvec K @ H when only the stats — not the assignment — are wanted.

TPU body (``backend="tpu"``): grid (rows/bm, L/bl); the feature reduction
runs INSIDE the kernel over explicitly DMA'd (bm x bd)/(bl x bd) tiles with
TWO VMEM slots per operand — while chunk k feeds the MXU, the DMAs for
chunk k+1 are already in flight (``double_buffer``; PR 5's stated
leftover), so HBM tile loads overlap MXU compute instead of serializing
ahead of it. Tiles are moved in the caller's dtype — bf16 tiles halve the
DMA bytes and double the effective MXU rate — while the Gram accumulator
is a loop-carried f32 value and the f accumulator f32 VMEM scratch
(``preferred_element_type=float32`` on every dot; the kernels/precision.py
contract, statically enforced by ``repro.analysis.check_precision``).

GPU body (``backend="gpu"``): Triton has no TPU-style scratch allocator in
the pinned jax, so the row-block body holds the whole landmark panel per
program and accumulates in registers — the communication-avoiding GPU
kernel-k-means layout (see kernels/backend.py). Runs under interpret mode
on CPU for CI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import gpu_compiler_params
from .kernel_matrix import _epilogue


def _kernel(x_hbm, l_hbm, xsq_ref, lsq_ref, h_ref, g_ref,
            labels_ref, mind_ref, f_ref,
            xbuf, lbuf, sem_x, sem_l, acc_f_ref, *,
            kind: str, gamma: float, coef0: float, degree: int,
            n_lm_steps: int, n_feat_steps: int,
            bm: int, bl: int, bd: int, prefetch: bool):
    i = pl.program_id(0)
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init_f():
        acc_f_ref[...] = jnp.zeros_like(acc_f_ref)

    def x_dma(slot, k):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * bm, bm), pl.ds(k * bd, bd)],
            xbuf.at[slot], sem_x.at[slot])

    def l_dma(slot, k):
        return pltpu.make_async_copy(
            l_hbm.at[pl.ds(li * bl, bl), pl.ds(k * bd, bd)],
            lbuf.at[slot], sem_l.at[slot])

    if prefetch:
        # warm-up: chunk 0 in flight before the loop; each iteration then
        # starts chunk k+1 into the other slot BEFORE waiting on chunk k,
        # so the MXU contraction of chunk k overlaps the HBM loads of k+1.
        x_dma(0, 0).start()
        l_dma(0, 0).start()

    def body(k, acc):
        slot = jax.lax.rem(k, 2)
        if prefetch:
            nxt = jax.lax.rem(k + 1, 2)

            @pl.when(k + 1 < n_feat_steps)
            def _ahead():
                x_dma(nxt, k + 1).start()
                l_dma(nxt, k + 1).start()
        else:
            x_dma(slot, k).start()
            l_dma(slot, k).start()
        x_dma(slot, k).wait()
        l_dma(slot, k).wait()
        return acc + jax.lax.dot_general(
            xbuf[slot], lbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc_k = jax.lax.fori_loop(
        0, n_feat_steps, body, jnp.zeros((bm, bl), jnp.float32))

    xsq = xsq_ref[...].astype(jnp.float32)          # [bm, 1]
    lsq = lsq_ref[...].astype(jnp.float32)          # [bl, 1]
    kblk = _epilogue(kind, acc_k, xsq, lsq.T,
                     gamma=gamma, coef0=coef0, degree=degree)
    acc_f_ref[...] += jax.lax.dot_general(
        kblk, h_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(li == n_lm_steps - 1)
    def _argmin():
        f_ref[...] = acc_f_ref[...]
        dist = g_ref[...].astype(jnp.float32) - 2.0 * acc_f_ref[...]
        # tie-break contract: jnp.argmin returns the FIRST (lowest)
        # index of the minimum — identical to the jnp oracle path, so
        # engine choice never changes labels (repro.core.engine).
        labels_ref[...] = jnp.argmin(dist, axis=1, keepdims=True
                                     ).astype(jnp.int32)
        mind_ref[...] = jnp.min(dist, axis=1, keepdims=True)


def _kernel_gpu(x_ref, l_ref, xsq_ref, lsq_ref, h_ref, g_ref,
                labels_ref, mind_ref, f_ref, *,
                kind: str, gamma: float, coef0: float, degree: int):
    acc = jax.lax.dot_general(
        x_ref[...], l_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    xsq = xsq_ref[...].astype(jnp.float32)
    lsq = lsq_ref[...].astype(jnp.float32)
    kblk = _epilogue(kind, acc, xsq, lsq.T,
                     gamma=gamma, coef0=coef0, degree=degree)
    f = jax.lax.dot_general(
        kblk, h_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    f_ref[...] = f
    dist = g_ref[...].astype(jnp.float32) - 2.0 * f
    labels_ref[...] = jnp.argmin(dist, axis=1, keepdims=True
                                 ).astype(jnp.int32)
    mind_ref[...] = jnp.min(dist, axis=1, keepdims=True)


def assign_fused_pallas(x, landmarks, xsq, lsq, h_norm, g, *,
                        kind: str = "rbf", gamma: float = 1.0,
                        coef0: float = 1.0, degree: int = 3,
                        bm: int = 256, bl: int = 256, bd: int = 512,
                        interpret: bool = False, backend: str = "tpu",
                        double_buffer: bool = True):
    """Fused Eq.15/17 assignment on pre-padded inputs.

    x: [n, D] rows, landmarks: [L, D] (both in the TILE dtype the caller's
    precision policy picked — f32 or bf16), xsq/lsq: [n, 1]/[L, 1] f32
    squared norms, h_norm: [L, Cp] f32 one-hot/counts (zero rows for padded
    landmarks), g: [1, Cp] f32 compactness (+BIG on padded clusters).
    Returns (labels [n, 1] int32, mind [n, 1] f32, f [n, Cp] f32).
    """
    n, d = x.shape
    lm = landmarks.shape[0]
    cp = h_norm.shape[1]
    out_specs_shapes = (
        [
            pl.BlockSpec((bm, 1), lambda *a: (a[0], 0)),
            pl.BlockSpec((bm, 1), lambda *a: (a[0], 0)),
            pl.BlockSpec((bm, cp), lambda *a: (a[0], 0)),
        ],
        [
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, cp), jnp.float32),
        ],
    )
    if backend == "gpu":
        kernel = functools.partial(
            _kernel_gpu, kind=kind, gamma=gamma, coef0=coef0, degree=degree)
        return pl.pallas_call(
            kernel,
            grid=(n // bm,),
            in_specs=[
                pl.BlockSpec((bm, d), lambda i: (i, 0)),     # x row panel
                pl.BlockSpec((lm, d), lambda i: (0, 0)),     # landmarks
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),     # xsq
                pl.BlockSpec((lm, 1), lambda i: (0, 0)),     # lsq
                pl.BlockSpec((lm, cp), lambda i: (0, 0)),    # h_norm
                pl.BlockSpec((1, cp), lambda i: (0, 0)),     # g
            ],
            out_specs=out_specs_shapes[0],
            out_shape=out_specs_shapes[1],
            interpret=interpret,
            **gpu_compiler_params(interpret=interpret),
        )(x, landmarks, xsq, lsq, h_norm, g)

    grid = (n // bm, lm // bl)
    kernel = functools.partial(
        _kernel, kind=kind, gamma=gamma, coef0=coef0, degree=degree,
        n_lm_steps=grid[1], n_feat_steps=d // bd,
        bm=bm, bl=bl, bd=bd, prefetch=double_buffer)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # x/landmarks stay HBM-resident (ANY): the kernel streams their
            # feature chunks through the double-buffered VMEM slots itself.
            pl.BlockSpec(memory_space=pl.ANY),                # x
            pl.BlockSpec(memory_space=pl.ANY),                # landmarks
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),       # xsq
            pl.BlockSpec((bl, 1), lambda i, j: (j, 0)),       # lsq
            pl.BlockSpec((bl, cp), lambda i, j: (j, 0)),      # h_norm
            pl.BlockSpec((1, cp), lambda i, j: (0, 0)),       # g
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, cp), lambda i, j: (i, 0)),
        ],
        out_shape=out_specs_shapes[1],
        scratch_shapes=[
            pltpu.VMEM((2, bm, bd), x.dtype),     # x tile slots
            pltpu.VMEM((2, bl, bd), landmarks.dtype),  # landmark tile slots
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((bm, cp), jnp.float32),    # f accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, landmarks, xsq, lsq, h_norm, g)
