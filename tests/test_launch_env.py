"""``repro.launch.env``: the platform pin, the persistent compile cache and
the launch mesh. Each case that imports jax after ``configure`` runs in a
subprocess, since the environment is read once, at the first jax import."""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import pytest

from repro.launch import env as launch_env

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, **env_vars) -> dict:
    """Run ``body`` in a fresh interpreter; it must print one JSON line."""
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    for k in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS",
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        env.pop(k, None)
    env.update(env_vars)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _configure(monkeypatch, **kw) -> dict:
    """``configure`` on a scratch copy of the environment (jax is already
    imported in this process, so it warns; the warning is not under test)."""
    for k in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR",
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(k, "")      # so that teardown restores it
        monkeypatch.delenv(k)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return launch_env.configure(**kw)


def test_default_cache_dir_is_the_checkout():
    assert launch_env.DEFAULT_CACHE_DIR == \
        launch_env.Path(_ROOT).resolve() / ".jax_cache"


@pytest.mark.parametrize("platform", ["cpu", "gpu", "tpu"])
def test_configure_pins_jax_platforms(monkeypatch, platform):
    applied = _configure(monkeypatch, platform=platform)
    assert os.environ["JAX_PLATFORMS"] == platform
    assert applied["platform"] == platform
    gpu = [f for f in os.environ.get("XLA_FLAGS", "").split()
           if f.startswith("--xla_gpu_")]
    # the GPU flag set is staged everywhere but on a TPU pin
    assert bool(gpu) == (platform != "tpu")


def test_configure_rejects_unknown_platform(monkeypatch):
    with pytest.raises(ValueError, match="unknown platform"):
        _configure(monkeypatch, platform="tpu-v5e")


def test_configure_keeps_caller_xla_flags(monkeypatch):
    _configure(monkeypatch)
    monkeypatch.setenv("XLA_FLAGS", "--xla_gpu_triton_gemm_any=False")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        launch_env.configure(platform="cpu")
    flags = os.environ["XLA_FLAGS"].split()
    assert "--xla_gpu_triton_gemm_any=False" in flags
    assert "--xla_gpu_triton_gemm_any=True" not in flags


def test_platform_from_argv():
    assert launch_env.platform_from_argv(["--platform", "tpu"]) == "tpu"
    assert launch_env.platform_from_argv(["-x", "--platform=cpu"]) == "cpu"
    assert launch_env.platform_from_argv(["--mesh", "2x2"]) is None


_CACHE_PROBE = """\
    import json, os
    from repro.launch.env import configure
    applied = configure(platform="cpu")
    import jax, jax.numpy as jnp
    jax.jit(lambda a: a * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    d = jax.config.jax_compilation_cache_dir
    print(json.dumps({
        "dir": d, "applied": applied["compilation_cache_dir"],
        "files": len(os.listdir(d)) if os.path.isdir(d) else 0,
        "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
        "backend": jax.default_backend()}))
"""


def test_cache_lands_in_the_given_directory(tmp_path):
    got = _run(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert got["dir"] == got["applied"] == str(tmp_path)
    assert got["files"] > 0
    assert got["min_secs"] == 0
    assert got["backend"] == "cpu"


def test_cache_defaults_to_the_checkout():
    # no compile is needed to show the directory; keep the checkout clean
    probe = _CACHE_PROBE.replace(
        "jax.jit(lambda a: a * 2 + 1)(jnp.arange(8.0)).block_until_ready()",
        "")
    got = _run(probe)
    assert got["dir"] == got["applied"] == \
        os.path.join(os.path.realpath(_ROOT), ".jax_cache")


def test_build_mesh_takes_the_first_devices(tmp_path):
    got = _run("""\
        import json, os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        from repro.launch.env import configure
        configure(platform="cpu")
        import jax
        from repro.launch.train import build_mesh
        mesh = build_mesh("2x2")
        try:
            build_mesh("4x4")
            too_big = None
        except SystemExit as e:
            too_big = str(e)
        print(json.dumps({
            "ids": [d.id for d in mesh.devices.flat],
            "first": [d.id for d in jax.devices()[:4]],
            "names": list(mesh.axis_names), "too_big": too_big}))
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert sorted(got["ids"]) == sorted(got["first"])
    assert got["names"] == ["data", "model"]
    assert got["too_big"] and "needs 16 devices" in got["too_big"]
