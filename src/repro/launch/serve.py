"""Serving launcher: ``python -m repro.launch.serve [...]``.

Two services share this entry point:

* ``--arch <id>`` — the LM-zoo continuous-batching engine
  (``repro.serving.engine``) on a synthetic request stream, reporting
  throughput + per-request latency.
* ``--assign <artifact.npz | synth>`` — the ASSIGNMENT service
  (``repro.serving.assign``): load a frozen predict artifact (or fit +
  freeze a small synthetic model for smoke runs), AOT-warm one compiled
  program per shape bucket, and drive a ragged request stream through the
  continuous-batching queue, reporting p50/p99 latency and rows/sec.

Both report into the same ``--obs`` flight-recorder JSONL.
"""
from __future__ import annotations

import argparse
import time

# XLA_FLAGS / JAX_PLATFORMS must be staged BEFORE the first jax
# import: the latency-hiding scheduler flags are a compile-time,
# process-level switch, and the --platform pin (which also selects the
# Mosaic/Triton/interpret kernel lowering, kernels/backend.py — i.e.
# which body the AOT-warmed bucket programs compile) is read once at
# backend init (repro.launch.env) — importing jax first would freeze
# both as-is. --platform is therefore pre-parsed from raw argv here; the
# argparse entry below only documents and validates it.
from .env import configure as _configure_env, platform_from_argv
_ENV = _configure_env(platform=platform_from_argv())

import jax   # noqa: E402  (env staging above is load-bearing)
import jax.numpy as jnp   # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import get_arch   # noqa: E402
from repro.models import Axes, get_model   # noqa: E402
from repro.serving import (AssignServeConfig, AssignService,   # noqa: E402
                           ServeConfig, ServingEngine, artifact_nbytes,
                           freeze, greedy, load_artifact, sample_top_p)

from .train import build_mesh   # noqa: E402


def _make_recorder(args, **extra):
    if not args.obs:
        return None
    from repro.obs import JsonlRecorder, export
    return JsonlRecorder(args.obs, header=export.run_header(
        entry="launch.serve", **extra))


def _synth_artifact(precision: str):
    """Fit a small rbf/RFF model on blobs and freeze it (smoke path)."""
    from repro.core.minibatch import MiniBatchConfig, fit_dataset
    from repro.data.synthetic import make_blobs
    x, _ = make_blobs(2048, 16, 8, seed=0)
    cfg = MiniBatchConfig(n_clusters=8, n_batches=4, method="rff",
                          embed_dim=64, seed=0)
    return freeze(fit_dataset(np.asarray(x), cfg), precision=precision)


def _assign_main(args):
    art = (_synth_artifact(args.precision) if args.assign == "synth"
           else load_artifact(args.assign))
    buckets = tuple(int(b) for b in args.buckets.split(","))
    rec = _make_recorder(args, mode="assign", kind=art.kind,
                         precision=art.precision, buckets=list(buckets))
    t0 = time.time()
    svc = AssignService(art, AssignServeConfig(buckets=buckets),
                        recorder=rec)
    warm_s = time.time() - t0

    rng = np.random.default_rng(0)
    sizes = rng.integers(1, args.rows_max + 1, size=args.requests)
    lat, rows = [], 0
    t0 = time.time()
    for n in sizes:
        ts = time.time()
        svc.predict(rng.normal(size=(int(n), art.in_dim)).astype(np.float32))
        lat.append(time.time() - ts)
        rows += int(n)
    dt = time.time() - t0
    p50, p99 = np.percentile(lat, [50, 99])
    if rec is not None:
        rec.event("serve/summary", requests=len(sizes), rows=rows,
                  seconds=dt, p50_seconds=float(p50),
                  p99_seconds=float(p99), warm_seconds=warm_s,
                  programs=svc.compiled_programs,
                  artifact_bytes=artifact_nbytes(art))
        rec.close()
    print(f"[serve.assign] kind={art.kind} precision={art.precision} "
          f"programs={svc.compiled_programs} (warm {warm_s:.2f}s) | "
          f"{len(sizes)} requests / {rows} rows in {dt:.2f}s "
          f"({rows/dt:.0f} rows/s, p50 {p50*1e3:.2f}ms, p99 {p99*1e3:.2f}ms)")
    return svc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM-zoo arch id (LM serving mode)")
    ap.add_argument("--assign", default=None, metavar="ARTIFACT",
                    help="assignment-serving mode: path to a frozen "
                    "artifact .npz (repro.serving.save_artifact) or "
                    "'synth' for a self-contained smoke model")
    ap.add_argument("--platform", choices=("cpu", "gpu", "tpu"),
                    default=None,
                    help="pin the jax backend (pre-parsed from raw argv "
                    "before the first jax import; see repro.launch.env)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="0 -> greedy; else nucleus sampling")
    # -- assignment-serving knobs --
    ap.add_argument("--buckets", default="1,8,64,512",
                    help="shape-bucket ladder (comma-separated row counts)")
    ap.add_argument("--rows-max", type=int, default=64,
                    help="synthetic request sizes draw from [1, rows-max]")
    ap.add_argument("--precision", choices=("f32", "bf16"), default="f32",
                    help="tile dtype for --assign synth freezing")
    ap.add_argument("--obs", default=None, metavar="PATH",
                    help="write a repro.obs flight-recorder JSONL here")
    args = ap.parse_args(argv)

    if args.assign is not None:
        return _assign_main(args)
    if args.arch is None:
        ap.error("one of --arch (LM serving) or --assign is required")

    mesh = build_mesh(args.mesh)
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    axes = Axes(dp=dp_axes, tp="model")
    cfg = get_arch(args.arch, smoke=args.smoke)
    api = get_model(cfg, tp_size=mesh.shape["model"])
    params, _ = api.init(jax.random.PRNGKey(0))

    sampler = greedy if args.top_p <= 0 else \
        (lambda logits, key: sample_top_p(logits, key, top_p=args.top_p))
    eng = ServingEngine(api, params, ServeConfig(
        max_batch=args.max_batch, max_len=args.max_len,
        max_new_tokens=args.max_new_tokens, eos_token=-1), sampler=sampler)

    rng = np.random.default_rng(0)
    lens = rng.integers(2, args.prompt_len + 1, size=args.requests)
    for l in lens:
        eng.submit(rng.integers(1, cfg.vocab_size, size=int(l)))

    rec = _make_recorder(args, arch=args.arch,
                         mesh={k: int(v) for k, v in mesh.shape.items()})
    results = {}
    t0 = time.time()
    try:
        with mesh:
            results = eng.run(axes)
    finally:
        dt = time.time() - t0
        if rec is not None:
            rec.event("serve/summary", requests=len(lens),
                      tokens=sum(len(v) for v in results.values()),
                      seconds=dt, ticks=eng.ticks)
            rec.close()
    n_tokens = sum(len(v) for v in results.values())
    print(f"[serve] {args.arch}: {len(results)} requests, "
          f"{n_tokens} tokens in {dt:.2f}s "
          f"({n_tokens/dt:.1f} tok/s, {eng.ticks} batched ticks)")
    return results


if __name__ == "__main__":
    main()
