"""Pallas TPU kernel: causal flash attention (online softmax, scores
VMEM-resident).

The §Perf cell-C analysis (EXPERIMENTS.md) attributes most of the dense
train/prefill memory term to the pure-JAX chunked attention writing
[cq, Sk] f32 score tensors to HBM. This kernel keeps the running max/sum
and the output accumulator in VMEM scratch, so HBM sees only Q, K, V and
the output — the same HBM-elision discipline as kernels/assign.py.

Grid: (B, H, Sq/bq, Sk/bk), key dim innermost (reduction). GQA is handled
in the BlockSpec index maps (kv head = h // (H/KH)) — K/V are never
repeated in memory. Causal masking skips fully-masked key blocks via
``pl.when`` (the compute for those blocks is elided, not just masked).

Reachability triage (mixed-precision PR): this kernel was flagged as
possibly dead — it is NOT. The live call chain is
``repro.models.attention`` (``attn_impl="flash"``) -> ``kernels.ops
.flash_attention`` -> ``flash_attention_pallas`` here, exercised by the
model smoke tests and the training launcher, and the RK003 dead-kernel
lint passes without a waiver. It therefore carries the full precision
policy: ``ops.flash_attention(precision=)`` casts Q/K/V to the tile
dtype, the softmax state (m, l) and the output accumulator stay f32
whatever the tiles are, and ``launch/audit.py`` includes this kernel in
the both-dtype ``check_precision`` sweep next to the clustering kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, softcap: float | None,
            bq: int, bk: int, n_k_steps: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: key block strictly after the query block -> nothing to do
    live = (qi + 1) * bq > ki * bk if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]                                # [bq, dh]
        k = k_ref[0, 0]                                # [bk, dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_ref[...]                            # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                         # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                 # [bq, 1]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k_steps - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           softcap: float | None = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False):
    """q: [B, H, Sq, dh]; k/v: [B, KH, Sk, dh] with H % KH == 0 (GQA).

    Pre-padded inputs: Sq % bq == Sk % bk == 0; dh MXU-aligned. fp32
    softmax state; output in q.dtype.
    """
    b, h, sq, dh = q.shape
    kh, sk = k.shape[1], k.shape[2]
    groups = h // kh
    grid = (b, h, sq // bq, sk // bk)
    kernel = functools.partial(
        _kernel, scale=dh ** -0.5, causal=causal, softcap=softcap,
        bq=bq, bk=bk, n_k_steps=grid[3])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, i, j: (b_, h_ // groups, j, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, i, j: (b_, h_ // groups, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
            pltpu.VMEM((bq, dh), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
