"""Process-level JAX/XLA environment setup for the launchers.

Everything here must happen before the first ``import jax``: XLA parses
``XLA_FLAGS`` once at backend init, and JAX reads ``JAX_PLATFORMS`` and
``JAX_COMPILATION_CACHE_DIR`` when it is imported. This module owns that:

  * ``configure(...)`` — call it FIRST (before importing anything that
    imports jax). It
      - pins the backend through ``JAX_PLATFORMS`` (``platform=``). JAX then
        fails at start-up when that platform has no device, instead of
        falling back to the CPU; the pin also selects the kernel lowering
        (kernels/backend.py follows ``jax.default_backend()``: Mosaic on
        tpu, Triton on gpu, interpret elsewhere);
      - points JAX's persistent compilation cache at ``<checkout>/.jax_cache``
        unless ``JAX_COMPILATION_CACHE_DIR`` already names a directory (the
        path is part of the cache key, so it is derived from this module's
        own location and nothing else);
      - merges the GPU latency-hiding flag set into ``XLA_FLAGS`` (not on a
        TPU pin, where those flags do nothing) without clobbering flags the
        caller or a test harness (``--xla_force_host_platform_device_count``)
        already set.
  * ``platform_from_argv(...)`` — pre-parses ``--platform`` from the raw
    argv so launchers can pin the backend BEFORE their argparse runs
    (argparse lives after the jax import, which is too late for the env
    var).

Everything here is a plain env-var edit; this module never imports jax.
"""
from __future__ import annotations

import os
import sys
import warnings
from pathlib import Path

# the jax gpu-performance-tips flag set (latency-hiding scheduler + fusion
# knobs). Safe to parse on every jaxlib build: DebugOptions registers
# xla_gpu_* flags regardless of backend.
GPU_PERF_FLAGS = (
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
)

PLATFORMS = ("cpu", "gpu", "tpu")

#: persistent compilation cache used when JAX_COMPILATION_CACHE_DIR is unset
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def platform_from_argv(argv=None) -> str | None:
    """Extract ``--platform <p>`` / ``--platform=<p>`` from raw argv
    (default: ``sys.argv``) without argparse — launchers call this at
    module import, before jax exists in the process, so the pin can land
    in ``JAX_PLATFORMS`` while it still matters. Returns None when the
    flag is absent; validation happens in ``configure``."""
    argv = sys.argv[1:] if argv is None else list(argv)
    for i, tok in enumerate(argv):
        if tok == "--platform" and i + 1 < len(argv):
            return argv[i + 1]
        if tok.startswith("--platform="):
            return tok.split("=", 1)[1]
    return None


def _merge_xla_flags(new_flags) -> bool:
    """Append flags to ``XLA_FLAGS``; existing settings of the same flag
    win (never clobber what the caller/test harness already pinned).
    Returns whether anything was actually added."""
    current = os.environ.get("XLA_FLAGS", "").split()
    have = {f.split("=", 1)[0] for f in current}
    added = [f for f in new_flags if f.split("=", 1)[0] not in have]
    if added:
        os.environ["XLA_FLAGS"] = " ".join(current + added)
    return bool(added)


def configure(*, gpu_flags: bool = True,
              host_device_count: int | None = None,
              platform: str | None = None) -> dict:
    """Prepare the process environment for a launcher run.

    Must run before the first jax import in the process. Idempotent: a
    second call that would change nothing is a silent no-op, so every
    launcher module can stage the env at import without worrying about
    which one ran first. Returns the settings actually applied (for logging
    / the obs run header).

    ``platform`` pins the jax backend ('cpu' | 'gpu' | 'tpu', typically
    from ``platform_from_argv()``). An explicit pin wins over an inherited
    ``JAX_PLATFORMS``; None leaves whatever the environment says.
    """
    applied: dict = {}
    changed = False
    if platform is not None:
        if platform not in PLATFORMS:
            raise ValueError(f"unknown platform {platform!r}; "
                             f"have {PLATFORMS}")
        if os.environ.get("JAX_PLATFORMS") != platform:
            os.environ["JAX_PLATFORMS"] = platform
            changed = True
        applied["platform"] = platform
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(DEFAULT_CACHE_DIR)
        changed = True
    applied["compilation_cache_dir"] = os.environ["JAX_COMPILATION_CACHE_DIR"]
    # cache every program, not only those that took over a second: on one
    # TPU v5e a cold exact fit compiled 393 programs, of which JAX's default
    # threshold cached 10, so a second run still spent 28 of 35 s compiling
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        changed = True
    if host_device_count:
        changed |= _merge_xla_flags(
            [f"--xla_force_host_platform_device_count={host_device_count}"])
        applied["host_device_count"] = host_device_count
    if gpu_flags and platform != "tpu":
        changed |= _merge_xla_flags(GPU_PERF_FLAGS)
        applied["gpu_flags"] = list(GPU_PERF_FLAGS)
    if changed and "jax" in sys.modules:   # too late for the env vars
        warnings.warn(
            "repro.launch.env.configure() changed the JAX environment after "
            "jax was imported; the changes will not take effect in this "
            "process", RuntimeWarning, stacklevel=2)
    applied["xla_flags"] = os.environ.get("XLA_FLAGS", "")
    return applied
