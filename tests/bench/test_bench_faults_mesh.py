"""The four-chip cell on four CPU devices, in a subprocess (the device
count is fixed when JAX starts): the sound mesh fit is correct, and one
whose inner loop leaves out the exchange between devices is not."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from benchutil import BENCH, make_root

SCRIPT = textwrap.dedent("""\
    import json, sys
    sys.path.insert(0, {bench!r})
    from harness import device
    device.bytes_limit = lambda d: int(2e8)
    if {fault!r}:
        # every all-reduce and all-gather of the inner loop stays on its
        # own device: each device sees its own rows' labels and partials
        from harness import faults
        faults.plant("exchange_left_out", setattr)
    import run
    from pathlib import Path
    sys.exit(run.main(["--workload", "mnist-tab1.restarts-4chip", "--seed",
                       "5", "--seconds", "0.01", "--trace", "0"],
                      root=Path({root!r}), platform="cpu"))
""")


def _run(root, fault: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(bench=str(BENCH), fault=fault,
                                             root=str(root))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [False, True])
def test_exchange_left_out_is_caught(tmp_path, fault):
    res = _run(make_root(tmp_path), fault)
    assert res["device"]["count"] == 4
    assert res["correct"] is (not fault), res["checks"]
