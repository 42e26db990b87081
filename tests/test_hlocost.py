"""Unit tests for the loop-aware HLO cost model (repro.launch.hlocost) —
every §Roofline number depends on it, so its FLOPs/trip-count/collective
accounting is locked here against analytically-known programs."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlocost

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hlo_of(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_of_matmuls_flops_exact():
    """12-layer scan of [128,256]x[256,256] matmuls: trip-multiplied FLOPs
    must match 12 * 2MNK within 1% (cost_analysis reports ~1/12 of this)."""
    def f(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h.sum()

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((12, 256, 256), jnp.float32)
    cost = hlocost.analyze(_hlo_of(f, x, w))
    expect = 12 * 2 * 128 * 256 * 256
    assert abs(cost.flops - expect) / expect < 0.01


def test_single_matmul_flops_and_bytes():
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    cost = hlocost.analyze(_hlo_of(f, a, b))
    expect_flops = 2 * 64 * 512 * 128
    assert abs(cost.flops - expect_flops) / expect_flops < 0.01
    # traffic >= operands + result (may include copies)
    min_bytes = (64 * 512 + 512 * 128 + 64 * 128) * 4
    assert cost.bytes >= min_bytes
    assert cost.bytes < 4 * min_bytes


def test_nested_scan_trip_multiplication():
    """outer scan 4 x inner scan 8 -> 32x the body cost."""
    def f(x, w):
        def inner(h, wi):
            return h @ wi, None

        def outer(h, _):
            h, _ = jax.lax.scan(inner, h, w)
            return h, None

        h, _ = jax.lax.scan(outer, x, None, length=4)
        return h.sum()

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    cost = hlocost.analyze(_hlo_of(f, x, w))
    expect = 4 * 8 * 2 * 32 * 64 * 64
    assert abs(cost.flops - expect) / expect < 0.05


def test_comment_stripping_in_tuples():
    """Long HLO tuples embed /*index=N*/ comments whose '=' used to break
    the instruction regex (regression guard)."""
    comps = hlocost._split_computations(
        "ENTRY %main (a: f32[4]) -> f32[4] {\n"
        "  %t = (f32[4]{0}, /*index=1*/f32[4]{0}) tuple(%a, %a)\n"
        "  ROOT %r = f32[4]{0} get-tuple-element(%t), index=0\n"
        "}\n")
    assert "main" in comps
    assert len(comps["main"]) == 2
    m = hlocost._INSTR_RE.match(comps["main"][0])
    assert m and m.group(3) == "tuple"


def test_known_trip_count_preferred():
    line = ('%w = (s32[]) while(%t), condition=%c, body=%b, '
            'backend_config={"known_trip_count":{"n":"42"}}')
    assert hlocost._trip_count(line, ["%k = s32[] constant(99999)"]) == 42


def test_trip_count_root_compare_fallback():
    cond = [
        "%big = s32[] constant(151936)",          # decoy (vocab-sized)
        "%lim = s32[] constant(16)",
        "%i = s32[] get-tuple-element(%arg), index=0",
        "ROOT %cmp = pred[] compare(%i, %lim), direction=LT",
    ]
    assert hlocost._trip_count("%w = (s32[]) while(%t), condition=%c, "
                               "body=%b", cond) == 16


@pytest.mark.slow
def test_sharded_collective_accounting():
    """8-way sharded matmul sum: per-device FLOPs = total/8 and exactly one
    all-reduce is recorded with ring cost 2*(g-1)/g * bytes."""
    script = textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlocost

        def f(a, b):
            return jnp.sum(a @ b)

        from repro.distributed.compat import make_mesh
        mesh = make_mesh((8,), ("d",))
        a = jax.ShapeDtypeStruct((256, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 64), jnp.float32)
        hlo = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P("d", None)),
            NamedSharding(mesh, P(None, None)))).lower(a, b) \\
            .compile().as_text()
        c = hlocost.analyze(hlo)
        print(json.dumps({"flops": c.flops,
                          "ar": c.coll_counts["all-reduce"],
                          "coll": c.coll_bytes}))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    res = json.loads(out.stdout.strip().splitlines()[-1])
    expect = 2 * 256 * 128 * 64 / 8
    assert abs(res["flops"] - expect) / expect < 0.05
    assert res["ar"] >= 1
    # scalar all-reduce: 2*(8-1)/8 * 4 bytes = 7
    assert 1 <= res["coll"] <= 64


# ---------------------------------------------------------------------------
# compiled-program compat helpers (PR 7 satellite: first direct coverage)


class _FakeMem:
    temp_size_in_bytes = 100
    argument_size_in_bytes = 40
    output_size_in_bytes = 8
    peak_memory_in_bytes = 120


class _FakeCompiled:
    def __init__(self, cost, mem="raise"):
        self._cost = cost
        self._mem = mem

    def cost_analysis(self):
        return self._cost

    def memory_analysis(self):
        if self._mem == "raise":
            raise NotImplementedError("no memory analysis on this backend")
        return self._mem


def test_xla_cost_list_vs_dict_vs_none():
    # the installed JAX returns the dict directly
    assert hlocost.xla_cost(_FakeCompiled({"flops": 7.0})) == {"flops": 7.0}
    # backends with no analysis: None and {} both collapse to {}
    assert hlocost.xla_cost(_FakeCompiled(None)) == {}
    assert hlocost.xla_cost(_FakeCompiled({})) == {}
    # a real compiled program yields a plain dict with XLA's flop count
    compiled = jax.jit(lambda a: a @ a).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    cost = hlocost.xla_cost(compiled)
    assert type(cost) is dict and cost.get("flops", 0) > 0


def test_xla_memory_guarded_on_cpu_shapes():
    empty = {"bytes_per_device": None, "argument_bytes": None,
             "output_bytes": None, "peak_bytes": None}
    # memory_analysis() raising or returning None: all-None dict
    assert hlocost.xla_memory(_FakeCompiled({}, mem="raise")) == empty
    assert hlocost.xla_memory(_FakeCompiled({}, mem=None)) == empty
    # the installed shape reports the allocator's own peak
    got = hlocost.xla_memory(_FakeCompiled({}, mem=_FakeMem()))
    assert got["peak_bytes"] == 120
    assert got["bytes_per_device"] == 100
    assert got["argument_bytes"] == 40
    assert got["output_bytes"] == 8


def test_compiled_cost_terms_matmul():
    """End-to-end on a real compiled program: the loop-aware FLOPs match
    the analytic matmul count and every compat key is present."""
    n = 64

    def f(a, b):
        return a @ b

    a = jnp.ones((n, n), jnp.float32)
    b = jnp.ones((n, n), jnp.float32)
    terms = hlocost.compiled_cost_terms(f, a, b)
    expect = 2 * n ** 3
    assert abs(terms["flops"] - expect) / expect < 0.05
    assert terms["hbm_bytes"] >= 3 * n * n * 4
    assert terms["coll_counts"] == {}
    assert set(terms["memory"]) == {"bytes_per_device", "argument_bytes",
                                    "output_bytes", "peak_bytes"}
    # xla_flops may be None on backends without cost_analysis, but when
    # present it must agree with the loop-aware count (no loops here).
    if terms["xla_flops"] is not None:
        assert abs(terms["xla_flops"] - expect) / expect < 0.05


def test_compiled_cost_terms_static_kwargs_and_loops():
    """kwargs close over static config, and scan FLOPs are trip-multiplied
    (the whole reason this module exists)."""
    steps = 5
    n = 32

    def f(a, *, n_steps):
        def step(c, _):
            return c @ a, None
        out, _ = jax.lax.scan(step, a, None, length=n_steps)
        return out

    a = jnp.ones((n, n), jnp.float32)
    terms = hlocost.compiled_cost_terms(f, a, n_steps=steps)
    expect = steps * 2 * n ** 3
    assert abs(terms["flops"] - expect) / expect < 0.10
