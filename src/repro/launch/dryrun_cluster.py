import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_SMOKE_DEVICES", "512"))

# ^ must precede every other import (jax locks the device count on first
# init) — same contract as repro.launch.dryrun. The multi-process smoke
# workers (--smoke-mp) override the per-process device count via
# REPRO_SMOKE_DEVICES so P processes x 2 devices stay CI-sized.

"""Dry-run for the PAPER'S ALGORITHM on the production mesh.

Lowers one inner-loop sweep (Alg.1 lines 10-14: the unit the paper's
communication bound is stated for) plus the full while-loop fit, for three
distribution variants:

  paper-1d   faithful Alg.1: rows sharded over ALL 256/512 workers,
             landmark columns replicated, K^i(p) materialized per device.
  2d         beyond-paper: rows over (pod, data), landmark columns over
             model — per-device K block shrinks by the model-axis size,
             letting s -> 1 survive bigger mini-batches (DESIGN.md §2).
  fused      beyond-paper: the Gram block is recomputed inside the
             assignment each sweep and never materialized in HBM (the
             Pallas kernel's structure; the dry-run uses the jnp body the
             TPU kernel replaces 1:1).

Default problem size (production regime, fits 16 GB/chip):
  N/B = 1,048,576 rows x d=768 fp32, C=64, |L|=65,536 (s = 1/16).

Writes the same JSON schema as repro.launch.dryrun so benchmarks/roofline.py
ingests these cells alongside the LM zoo.
"""

import argparse          # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from functools import partial  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp

from repro.distributed.compat import shard_map  # noqa: E402

from repro.core.kernels import KernelSpec                       # noqa: E402
from repro.distributed.inner import DistributedInnerConfig  # noqa: E402,F401
from repro.launch.dryrun import collective_bytes                # noqa: E402
from repro.launch.mesh import data_axes, make_production_mesh   # noqa: E402

MODES = {
    # mode -> (row_axes(sp), col_axis, inner mode, K dtype)
    "paper-1d": (("data", "model"), None, "materialize", jnp.float32),
    "2d": (("data",), "model", "materialize", jnp.float32),
    "fused": (("data",), "model", "fused", jnp.float32),
    # §Perf hillclimb A: K block stored bf16 (f32 accumulation in the
    # f-matmul is unchanged — MXU-native); halves the dominant memory term.
    "2d-bf16k": (("data",), "model", "materialize", jnp.bfloat16),
}


def _analyze(compiled):
    from repro.distributed.compat import cost_analysis as _ca
    cost = _ca(compiled)
    try:
        from repro.distributed.compat import memory_stats
        mem_info = memory_stats(compiled)
    except Exception as e:
        mem_info = {"error": str(e)}
    hlo_text = compiled.as_text()
    from repro.launch import hlocost
    return cost, mem_info, collective_bytes(hlo_text), \
        hlocost.analyze(hlo_text)


def lower_cluster(mode: str, *, multi_pod: bool = False, n_rows: int = 2**20,
                  d: int = 768, c: int = 64, n_landmarks: int = 65536):
    """Lower ONE assignment sweep (Alg.1 lines 10-14 — the unit of the
    paper's communication bound) + the per-batch Gram evaluation.

    materialize modes: the sweep consumes a precomputed K block (input);
    the Gram evaluation is lowered separately and amortized over sweeps.
    fused mode: the sweep recomputes the Gram block inside the assignment
    (never materialized in HBM) — the Pallas-kernel structure."""
    row_axes, col_axis, inner_mode, k_dtype = MODES[mode]
    if multi_pod:
        row_axes = ("pod",) + row_axes
    mesh = make_production_mesh(multi_pod=multi_pod)
    spec = KernelSpec("rbf", gamma=0.05)
    t0 = time.time()

    from jax.sharding import PartitionSpec as P
    from repro.core.engine import (GramEngine, ReducePlan, assign_from_stats,
                                   engine_stats)

    d_size = math.prod(mesh.shape[a] for a in row_axes)
    m_size = mesh.shape[col_axis] if col_axis else 1
    rows_p = n_rows // d_size
    cols_p = n_landmarks // m_size

    x = jax.ShapeDtypeStruct((n_rows, d), jnp.float32)
    lm = jax.ShapeDtypeStruct((n_landmarks, d), jnp.float32)
    lidx = jax.ShapeDtypeStruct((n_landmarks,), jnp.int32)
    k_xl = jax.ShapeDtypeStruct((n_rows, n_landmarks), k_dtype)
    k_ll = jax.ShapeDtypeStruct((n_landmarks, n_landmarks), k_dtype)
    u = jax.ShapeDtypeStruct((n_rows,), jnp.int32)

    rowspec = P(row_axes)
    colspec = P(col_axis) if col_axis else P()
    kspec = P(row_axes, col_axis)
    # 1-D: K_ll row-sharded (the paper's layout); 2-D: replicated over the
    # row axes so g joins the fused stats psum (distributed.inner).
    llspec = P(row_axes, col_axis) if col_axis is None else P(None, col_axis)
    lrowspec = rowspec if col_axis is None else P()

    # the mesh's ONE batched reduction, handed to the SHARED engine stats
    # as a ReducePlan (identical structure to distributed.inner
    # ._body_factory): 2-D reduces the whole counts/f/g payload in one
    # flat psum over the model axis; 1-D reduces only g over the rows
    # (counts/f are local there — the real loop appends its cost/changed
    # scalars to the same buffer).
    if col_axis is not None:
        def _fused(counts_p, f_p, g_p):
            flat = jnp.concatenate(
                [f_p, counts_p[None, :], g_p[None, :]], axis=0)
            flat = jax.lax.psum(flat, col_axis)
            return flat[-2], flat[:-2], flat[-1]
    else:
        def _fused(counts_p, f_p, g_p):
            return counts_p, f_p, jax.lax.psum(g_p, row_axes)
    reduce_plan = ReducePlan(_fused)

    def _sweep(op_xl, op_ll, lidx_cols, lidx_rows, u_full, eng):
        f, g, counts = engine_stats(
            eng, spec, op_xl, op_ll, jnp.take(u_full, lidx_cols),
            jnp.take(u_full, lidx_rows), c, reduce=reduce_plan)
        labels, _ = assign_from_stats(f, g, counts)
        return labels

    def sweep_mat(k_local, kll_local, lidx_cols, lidx_rows, u_local):
        u_full = jax.lax.all_gather(u_local, row_axes, tiled=True)
        return _sweep(GramEngine.from_matrix(k_local),
                      GramEngine.from_matrix(kll_local),
                      lidx_cols, lidx_rows, u_full,
                      GramEngine("materialize"))

    def sweep_fused(x_local, lm_cols, lm_rows, lidx_cols, lidx_rows,
                    u_local):
        u_full = jax.lax.all_gather(u_local, row_axes, tiled=True)
        # the portable recompute structure (Gram rebuilt inside the sweep,
        # never stored) — the Pallas kernel replaces it on real TPUs.
        eng = GramEngine("fused", pallas="never")
        return _sweep(eng.prepare(spec, x_local, lm_cols),
                      eng.prepare(spec, lm_rows, lm_cols),
                      lidx_cols, lidx_rows, u_full, eng)

    def gram(x_local, lm_cols):
        return spec(x_local, lm_cols).astype(k_dtype)

    with mesh:
        if inner_mode == "fused":
            fn = shard_map(
                sweep_fused, mesh=mesh,
                in_specs=(P(row_axes, None),
                          P(col_axis, None) if col_axis else P(None, None),
                          P(row_axes, None) if col_axis is None
                          else P(None, None),
                          colspec, lrowspec, rowspec),
                out_specs=rowspec, check_vma=False)
            lowered = jax.jit(lambda *a: fn(*a)).lower(
                x, lm, lm, lidx, lidx, u)
            sweep_compiled = lowered.compile()
            gram_compiled = None
        else:
            fn = shard_map(
                sweep_mat, mesh=mesh,
                in_specs=(kspec, llspec, colspec, lrowspec, rowspec),
                out_specs=rowspec, check_vma=False)
            lowered = jax.jit(lambda *a: fn(*a)).lower(
                k_xl, k_ll, lidx, lidx, u)
            sweep_compiled = lowered.compile()
            gfn = shard_map(
                gram, mesh=mesh,
                in_specs=(P(row_axes, None),
                          P(col_axis, None) if col_axis else P(None, None)),
                out_specs=kspec, check_vma=False)
            gram_compiled = jax.jit(lambda *a: gfn(*a)).lower(
                x, lm).compile()

    cost, mem_info, coll, la = _analyze(sweep_compiled)
    amortize_sweeps = 20.0      # typical inner iterations per batch
    if gram_compiled is not None:
        _, gmem, gcoll, gla = _analyze(gram_compiled)
        la += gla.scaled(1.0 / amortize_sweeps)    # Cost defines __iadd__
        mem_info["gram_peak_bytes"] = gmem.get("peak_bytes")
        mem_info["k_block_bytes_per_device"] = rows_p * cols_p * 4

    # useful work per sweep: f-matmul 2 rows L C (+ Gram 2 rows L d, fully
    # for fused, amortized for materialize)
    gram_f = 2.0 * n_rows * n_landmarks * d
    fmat = 2.0 * n_rows * n_landmarks * c
    model_flops = fmat + (gram_f if inner_mode == "fused"
                          else gram_f / amortize_sweeps)

    return {
        "arch": f"kkmeans-{mode}", "shape": "minibatch_1m",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_params": n_rows * d,
        "n_active_params": n_rows * d,
        "tokens_per_step": n_rows,
        "model_flops_total": model_flops,
        "flops_per_device": cost.get("flops"),
        "bytes_per_device": cost.get("bytes accessed"),
        "loop_aware": {
            "flops_per_device": la.flops,
            "bytes_per_device": la.bytes,
            "collective_bytes_by_kind": la.coll,
            "collective_counts": la.coll_counts,
            "collective_bytes": la.coll_bytes,
        },
        "problem": {"n_rows": n_rows, "d": d, "c": c,
                    "n_landmarks": n_landmarks, "mode": mode,
                    "per_sweep": True},
        "memory_analysis": mem_info,
        "collectives": coll,
        "compile_seconds": round(time.time() - t0, 2),
        "ok": True,
    }


def smoke_driver(args) -> int:
    """Spawn ``--smoke-mp`` ranks of ``repro.launch.smoke_mp`` (REAL
    cross-process gloo collectives through the s-step fit path) and wait.
    Exits 0 (with a message) when the jax build cannot do multi-process
    CPU collectives — CI must not go red over a missing gloo backend."""
    import socket
    import subprocess
    import sys

    from repro.launch.smoke_mp import SKIP_EXIT

    with socket.socket() as s:   # grab a free coordinator port
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    # the ranks run on forced host devices: pin them to the CPU so none of
    # them contends for an accelerator this host may have
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               REPRO_SMOKE_DEVICES="2",
               REPRO_SMOKE_NPROCS=str(args.smoke_mp),
               REPRO_SMOKE_COORD=f"localhost:{port}")
    cmd = [sys.executable, "-m", "repro.launch.smoke_mp",
           "--s-step", str(args.s_step)]
    if args.obs:
        cmd += ["--obs", args.obs]
    procs = [subprocess.Popen(cmd, env=dict(env, REPRO_SMOKE_RANK=str(r)))
             for r in range(args.smoke_mp)]
    codes = [p.wait() for p in procs]
    if any(c == SKIP_EXIT for c in codes):
        print(f"[skip] multi-process CPU smoke unsupported here "
              f"(exit codes {codes})")
        return 0
    if any(codes):
        print(f"[FAIL] smoke worker exit codes {codes}")
        return 1
    print(f"[ok] multi-process smoke: {args.smoke_mp} processes clean")
    return 0


def main():
    ap = argparse.ArgumentParser(description="clustering dry-run")
    ap.add_argument("--mode", default=None, choices=sorted(MODES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rows", type=int, default=2**20)
    ap.add_argument("--d", type=int, default=768)
    ap.add_argument("--clusters", type=int, default=64)
    ap.add_argument("--landmarks", type=int, default=65536)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--smoke-mp", type=int, default=0, metavar="P",
                    help="run the multi-process CPU smoke with P "
                         "processes (real gloo collectives through the "
                         "s-step fit path) instead of the lowering sweep")
    ap.add_argument("--s-step", type=int, default=2,
                    help="s-step depth for the smoke fit")
    ap.add_argument("--obs", default=None, metavar="PATH",
                    help="smoke: rank-0 flight-recorder JSONL (CI "
                         "artifact)")
    args = ap.parse_args()

    if args.smoke_mp:
        raise SystemExit(smoke_driver(args))

    modes = sorted(MODES) if args.all else [args.mode]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    n_fail = 0
    for mode in modes:
        for mp in meshes:
            tag = f"kkmeans-{mode}__minibatch_1m__{'mp' if mp else 'sp'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (cached)")
                continue
            try:
                res = lower_cluster(mode, multi_pod=mp, n_rows=args.rows,
                                    d=args.d, c=args.clusters,
                                    n_landmarks=args.landmarks)
                print(f"[ok]   {tag}  compile={res['compile_seconds']}s "
                      f"coll/sweep="
                      f"{res['loop_aware']['collective_bytes']:.3e}B")
            except Exception as e:
                n_fail += 1
                res = {"arch": f"kkmeans-{mode}", "shape": "minibatch_1m",
                       "mesh": "2x16x16" if mp else "16x16", "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()}
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
