"""Clustering launcher — the paper's algorithm as the production entry point.

``python -m repro.launch.cluster --n 100000 --d 64 --clusters 16 [...]``

End-to-end flow (paper §3 + §4.2 model selection, automated):

  1. plan (B, s) from the per-chip memory budget (Eq.19, repro.core.memory),
  2. build the mesh, shard rows over the data axes, landmarks over model,
  3. run distributed mini-batch kernel k-means with per-batch checkpointing
     (restart loses at most one mini-batch),
  4. report accuracy/NMI (when labels exist) + the Fig.4b displacement
     diagnostic for sampling quality.
"""
from __future__ import annotations

import argparse
import time

# XLA_FLAGS / JAX_PLATFORMS must be staged BEFORE the first jax
# import: the latency-hiding scheduler that overlaps the s-step loop's one
# fused collective per sync with the next Gram panel is a compile-time,
# process-level switch, and the --platform pin (which also selects the
# Mosaic/Triton/interpret kernel lowering, kernels/backend.py) is read
# once at backend init (repro.launch.env) — importing jax first would
# freeze both as-is. --platform is therefore pre-parsed from raw argv
# here; the argparse entry below only documents and validates it.
from .env import configure as _configure_env, platform_from_argv
_ENV = _configure_env(platform=platform_from_argv())

import jax   # noqa: E402  (env staging above is load-bearing)
import numpy as np   # noqa: E402

from repro.core import (KernelSpec, MachineSpec, MiniBatchConfig,
                        clustering_accuracy, gamma_from_dmax,
                        mean_displacement, nmi, plan)
from repro.core.minibatch import predict
from repro.data.sampling import split_batches
from repro.data.synthetic import make_blobs
from repro.distributed.outer import DistributedMiniBatchKMeans
from repro.ft.checkpoint import CheckpointManager

from .train import build_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--clusters", type=int, default=8)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--platform", default=None,
                    choices=["cpu", "gpu", "tpu"],
                    help="pin the jax backend (and with it the kernel "
                         "lowering: Mosaic on tpu, Triton on gpu, "
                         "interpret on cpu). Consumed from raw argv "
                         "before the first jax import; listed here for "
                         "--help and validation")
    ap.add_argument("--memory-gb", type=float, default=0.5,
                    help="per-processor budget R for the Eq.19 planner")
    ap.add_argument("--s", type=float, default=None,
                    help="override the planned landmark fraction")
    ap.add_argument("--b", type=int, default=None,
                    help="override the planned number of mini-batches")
    ap.add_argument("--sampling", default="stride",
                    choices=["stride", "block"])
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "materialize", "fused", "tiled"],
                    help="Gram residency of the exact inner loop "
                         "(repro.core.engine); auto = the planner's pick")
    ap.add_argument("--s-step", type=int, default=1,
                    help="communication-avoiding s-step depth: s local "
                         "Lloyd refinements per global sync — the "
                         "collective bill drops to (1 allgather + 1 "
                         "psum)/s at the price of replicating the batch "
                         "labels on every device")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs", default=None, metavar="PATH",
                    help="write a repro.obs flight-recorder JSONL here "
                         "(per-batch wall time, collective counts, HBM "
                         "watermarks vs the plan)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="dump a TensorBoard profiler trace of the fit")
    args = ap.parse_args(argv)

    mesh = build_mesh(args.mesh)
    n_proc = mesh.size

    # -- data (synthetic stand-in for the MD/UCI streams; DESIGN.md §8.5)
    x, y = make_blobs(args.n, args.d, args.clusters, sep=8.0,
                      seed=args.seed)

    # -- memory-aware (B, s) plan — the paper's §4.2 rationale
    machine = MachineSpec(memory_bytes=args.memory_gb * 1e9,
                          n_processors=n_proc)
    p = plan(args.n, args.clusters, machine, d=args.d,
             s_step=args.s_step)
    b = args.b or p.b
    s = args.s if args.s is not None else p.s
    gamma = gamma_from_dmax(jax.numpy.asarray(x[:4096]))
    print(f"[cluster] N={args.n} d={args.d} C={args.clusters} "
          f"mesh={dict(mesh.shape)}")
    mode = p.engine if args.mode == "auto" else args.mode
    print(f"[cluster] plan: B={b} s={s:.2f} ({p.note}); "
          f"footprint/node {p.footprint/1e6:.1f} MB "
          f"(fused {p.fused_footprint/1e6:.1f} MB); "
          f"engine={mode}; gamma={gamma:.2e}")

    cfg = MiniBatchConfig(n_clusters=args.clusters, n_batches=b, s=s,
                          kernel=KernelSpec("rbf", gamma=gamma),
                          sampling=args.sampling, seed=args.seed,
                          s_step=args.s_step)

    rec = None
    if args.obs:
        from repro.obs import JsonlRecorder, export
        rec = JsonlRecorder(args.obs, header=export.run_header(
            entry="launch.cluster", plan=p, b=b, s=s, engine=str(mode),
            s_step=args.s_step, xla_flags=_ENV.get("xla_flags", ""),
            mesh={k: int(v) for k, v in mesh.shape.items()}))
    km = DistributedMiniBatchKMeans(mesh, cfg, mode=mode, recorder=rec)

    cb = None
    if args.ckpt_dir:
        cm = CheckpointManager(args.ckpt_dir)
        cb = lambda state, i: cm.save(i, state,  # noqa: E731
                                      extra={"B": b, "s": s})

    if args.profile:
        from repro.obs import start_profile
        start_profile(args.profile)
    t0 = time.time()
    try:
        res = km.fit(split_batches(x, b, strategy=args.sampling),
                     checkpoint_cb=cb)
    finally:
        if args.profile:
            from repro.obs import stop_profile
            stop_profile()
            print(f"[cluster] profiler trace -> {args.profile}")
        if rec is not None:
            rec.close()
    dt = time.time() - t0

    labels = np.asarray(predict(jax.numpy.asarray(x), res.state.medoids,
                                res.state.medoid_diag, spec=cfg.kernel))
    acc = clustering_accuracy(y, labels)
    disp = mean_displacement(res.history)
    print(f"[cluster] {dt:.2f}s  acc={acc:.4f} nmi={nmi(y, labels):.4f}")
    print(f"[cluster] displacement/batch (Fig.4b): "
          f"{np.array2string(disp, precision=4)}")
    print(f"[cluster] inner iters/batch: "
          f"{[h.inner_iters for h in res.history]}")
    if args.obs:
        from repro.obs import export
        s_ = export.summarize(args.obs)
        print(f"[cluster] obs: {s_['events']} events -> {args.obs}")
    return acc


if __name__ == "__main__":
    main()
