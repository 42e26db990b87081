"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the full production loop on whatever devices exist (1 CPU device for
local smoke, a forced-device mesh for integration tests, a real pod via the
same flags). Features exercised end-to-end:

  * sharded params/optimizer from the model's PartitionSpecs,
  * synthetic token pipeline with double-buffered prefetch,
  * microbatch gradient accumulation,
  * step-granular checkpoint/restart (atomic manifest, elastic restore),
  * straggler-aware step timing log.

On a multi-host pod this module is launched once per host (JAX distributed
init is orthogonal to the program) — the mesh axes and shardings used here
are exactly the dry-run-validated production ones.
"""
from __future__ import annotations

import argparse
import math
import time

# stage XLA_FLAGS (latency-hiding scheduler / async-collective overlap)
# and the --platform backend pin before the first jax import — see
# repro.launch.env; --platform is pre-parsed from raw argv because the
# argparse in main() runs long after the backend is frozen.
from .env import configure as _configure_env, platform_from_argv
_ENV = _configure_env(platform=platform_from_argv())

import jax   # noqa: E402  (env staging above is load-bearing)

from repro.distributed.compat import make_mesh   # noqa: E402
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, TrainConfig, get_arch
from repro.ft.checkpoint import CheckpointManager
from repro.models import Axes, get_model
from repro.training.optim import adamw_init, opt_state_specs
from repro.training.step import make_train_step


def build_mesh(spec: str):
    """'4x2' -> mesh (data=4, model=2) over the first 4*2 visible devices."""
    dims = tuple(int(x) for x in spec.split("x"))
    n = math.prod(dims)
    devices = jax.devices()
    if n > len(devices):
        raise SystemExit(
            f"mesh {spec} needs {n} devices, have {len(devices)}")
    names = ("data", "model")[:len(dims)] if len(dims) <= 2 else \
        ("pod", "data", "model")
    return make_mesh(dims, names, devices=devices[:n])


def synthetic_batches(vocab: int, batch: int, seq: int, steps: int,
                      seed: int = 0):
    """Self-labelled LM batches: labels are next-token shifted tokens."""
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        tok = rng.integers(1, vocab, size=(batch, seq), dtype=np.int64)
        yield {"tokens": tok.astype(np.int32),
               "labels": np.roll(tok, -1, axis=1).astype(np.int32)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--platform", default=None,
                    choices=["cpu", "gpu", "tpu"],
                    help="pin the jax backend (consumed from raw argv "
                         "before the first jax import; listed here for "
                         "--help and validation)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    mesh = build_mesh(args.mesh)
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    axes = Axes(dp=dp_axes, tp="model")
    dp_size = math.prod(mesh.shape[a] for a in dp_axes)

    cfg = get_arch(args.arch, smoke=args.smoke)
    api = get_model(cfg, tp_size=mesh.shape["model"], dp_size=dp_size)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 10, 1),
                       microbatches=args.microbatches,
                       remat=not args.smoke)

    params, specs = api.init(jax.random.PRNGKey(0))
    opt = adamw_init(params, tcfg)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"[train] {args.arch} ({'smoke' if args.smoke else 'full'}): "
          f"{n_params/1e6:.1f}M params, mesh={dict(mesh.shape)}")

    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))
    opt_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          opt_state_specs(specs),
                          is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, param_sh)
    opt = jax.device_put(opt, opt_sh)

    start_step = 0
    cm = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if cm and args.resume and cm.latest_step() is not None:
        s = cm.latest_step()
        restored = cm.restore(s, {"params": params, "opt": opt},
                              shardings={"params": param_sh, "opt": opt_sh})
        params, opt = restored["params"], restored["opt"]
        start_step = s
        print(f"[train] resumed from step {s}")

    step_fn = jax.jit(make_train_step(api, tcfg, axes),
                      donate_argnums=(0, 1))

    batch_spec = api.batch_partition(
        type("S", (), {"kind": "train", "global_batch": args.batch,
                       "seq_len": args.seq})(), axes)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), batch_spec,
                            is_leaf=lambda x: isinstance(x, P))

    times = []
    with mesh:
        gen = synthetic_batches(cfg.vocab_size, args.batch, args.seq,
                                args.steps - start_step, seed=start_step)
        for i, batch in enumerate(gen, start=start_step):
            batch = jax.tree.map(
                lambda a, sh: jax.device_put(jnp.asarray(a), sh),
                batch, batch_sh)
            t0 = time.time()
            params, opt, metrics = step_fn(params, opt, batch)
            jax.block_until_ready(metrics["loss"])
            times.append(time.time() - t0)
            if (i + 1) % args.log_every == 0 or i == start_step:
                print(f"  step {i+1:5d}  loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"dt={times[-1]*1e3:.0f}ms")
            if cm and (i + 1) % args.ckpt_every == 0:
                cm.save(i + 1, {"params": params, "opt": opt},
                        extra={"arch": args.arch})
    med = float(np.median(times[1:])) if len(times) > 1 else float("nan")
    print(f"[train] done. median step {med*1e3:.0f}ms "
          f"(first/compile {times[0]*1e3:.0f}ms)")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
