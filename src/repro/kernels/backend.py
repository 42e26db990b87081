"""Multi-backend seam: the same kernels lower to Mosaic, Triton, or the
interpreter.

Every kernel module in this package ships two bodies behind one wrapper:

  * ``backend="tpu"`` — the Mosaic lowering: 2-D/3-D grid with reduction
    dims, VMEM scratch accumulators, DMA double buffering where the module
    implements it, ``dimension_semantics`` compiler params. This is the
    production path and the one interpret-mode CI executes by default.
  * ``backend="gpu"`` — the Triton lowering: row-block grid only, no
    scratch refs (Triton Pallas has no TPU-style scratch allocator in the
    pinned jax), accumulators live as loop values in registers, the whole
    landmark/value panel is a per-program block — the communication-
    avoiding GPU kernel-k-means layout (Bellavita et al., PAPERS.md). The
    body is plain ``pl``/``jnp`` so it ALSO runs under ``interpret=True``
    on CPU: CI exercises the GPU body without a GPU, and
    ``launch/audit.py --gpu-trace`` dry-traces the non-interpret Triton
    staging (the ``pallas_call`` binds without lowering) so backend
    regressions surface without GPU runners.

``kernel_backend("auto")`` resolves the seam at trace time from
``jax.default_backend()`` — which ``launch/env.py``'s ``--platform`` flag
pins through ``JAX_PLATFORMS`` before the first jax import.
CPU resolves to the TPU body in interpret mode: it is the reference
lowering and the one the oracles pin tightest.
"""
from __future__ import annotations

BACKENDS = ("tpu", "gpu")


def kernel_backend(backend: str = "auto") -> str:
    """Resolve a backend request to a kernel body: "tpu" | "gpu".

    "auto" follows ``jax.default_backend()``; CPU gets the TPU body (run
    in interpret mode by the wrappers' dispatch). Explicit names pass
    through so tests and the audit CLI can trace either lowering anywhere.
    """
    if backend in BACKENDS:
        return backend
    if backend != "auto":
        raise ValueError(
            f"backend must be 'auto' or one of {BACKENDS}, got {backend!r}")
    import jax
    native = jax.default_backend()
    return native if native in BACKENDS else "tpu"


def gpu_compiler_params(*, interpret: bool, num_warps: int = 4,
                        num_stages: int = 2):
    """TritonCompilerParams for the gpu body — omitted under interpret
    mode (the interpreter rejects backend-specific params)."""
    if interpret:
        return {}
    from jax.experimental.pallas import triton as plgpu
    return {"compiler_params": plgpu.TritonCompilerParams(
        num_warps=num_warps, num_stages=num_stages)}
