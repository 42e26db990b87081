#!/usr/bin/env python3
"""Run the kernel k-means main path once on one TPU chip, and check it.

    python chip_smoke.py [--seed N]                # one chip: phases A and B
    python chip_smoke.py [--seed N] --four-chips   # only the mesh path, 4 chips
    python chip_smoke.py [--seed N] --bf16-control # phase A must fail

Data: the paper's Tab.1 MNIST shape, 70,000 x 784 f32 with C=10, generated
from ``--seed`` by ``repro.data.synthetic.make_blobs``; rbf kernel with the
paper's gamma rule (``gamma_from_dmax``).

Phase A, exact fit (the ``launch/cluster.py`` path): ``plan()`` from the
chip's own memory limit, then ``DistributedMiniBatchKMeans.fit`` on a 1x1
mesh with the fused Pallas engine (``kernels/assign.py``), against the same
fit with the plain-jnp materialize engine: labels must agree, each side's
reported cost must match the float64 cost of its own labels, and a probe
must show that both sides compute kernel means at f32 precision
(``--bf16-control`` shows that these checks fail on a bf16 fused fit).

Phase B, embedded fit and serving: a Nystrom fit, ``serving.freeze``, an
``AssignService`` on the default bucket ladder, against the jnp oracle of
``ops.predict_assign``; then the same at bf16. ``--four-chips`` runs only the mesh path: a 4-way
row-sharded exact fit at s_step=1, a 2x2 data x model fit at s_step=2 and a
4-way embedded fit, each against the same fit on one chip.

Every step that promises a Pallas kernel is compiled and its HLO searched
for ``tpu_custom_call`` (no interpret mode, no jnp fallback). Times are the
set-up and run seconds of this one pass (compile seconds from JAX's own
compile events), not a benchmark. Any failed check exits non-zero before
the last line, which is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N, D, C = 70_000, 784, 10        # MNIST (paper Tab.1)
EMBED_DIM = 1024
# Max abs error of a kernel mean at "highest": f32 gives ~1e-6; one bf16
# pass gives ~3e-3.
PROBE_LIMIT = 1e-5
# Relative error of a reported exact-fit cost. At sigma = 4 d_max every
# kernel value is within 5e-3 of 1 and a row's cost term 1 - 2 f + g is
# ~1e-4, while f and g are f32 sums over thousands of kernel values with
# errors of several 1e-6 on the chip: f32 alone moves the cost by a few
# percent. A bf16 pass (~3e-3 per mean) puts it off by more than its size.
COST_LIMIT = 1e-1


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


class CompileClock:
    """Sums JAX's backend-compile events (persistent-cache loads included)
    so each phase can report set-up seconds apart from run seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        c0 = (self.seconds, self.count, self.cache_hits)
        yield
        wall = time.perf_counter() - t0
        comp = self.seconds - c0[0]
        log(f"time {name}: wall {wall:.2f}s = set-up (compile "
            f"{self.count - c0[1]} programs, {self.cache_hits - c0[2]} from "
            f"the persistent cache) {comp:.2f}s + run {wall - comp:.2f}s")


def matched_agreement(a, b) -> float:
    """Share of rows on which two labelings agree after the best one-to-one
    matching of their clusters."""
    import numpy as np
    from scipy.optimize import linear_sum_assignment

    from repro.core.metrics import contingency
    o = contingency(np.asarray(a), np.asarray(b))
    r, c = linear_sum_assignment(-o)
    return float(o[r, c].sum() / len(a))


def mosaic(jax, name: str, fn, *args) -> None:
    """Compile ``fn`` at ``args`` and require a Mosaic kernel in its HLO."""
    t0 = time.perf_counter()
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    check(calls > 0, f"{name}: no tpu_custom_call in the compiled program")
    log(f"kernel {name}: {calls} tpu_custom_call in the compiled HLO "
        f"(compiled in {time.perf_counter() - t0:.2f}s)")


def precision_probe(jax, x, gamma) -> None:
    """How precise each side of the exact fit is: the per-cluster means of
    K(x, l) on 2048 x 2048 rows (what the inner loop's f holds) against a
    float64 host reference, for the jnp path and the Pallas kernel behind
    ``assign_fused``, at the default matmul precision (shown) and at
    "highest" (checked against ``PROBE_LIMIT``)."""
    import numpy as np
    import jax.numpy as jnp

    from repro.core.kernels import KernelSpec
    from repro.kernels import ops

    k = min(2048, len(x) // 2)
    xs, ls = (np.asarray(a, np.float64) for a in (x[:k], x[k:2 * k]))
    h = np.eye(C)[np.random.default_rng(0).integers(0, C, k)]
    h /= np.maximum(h.sum(0), 1.0)
    d2 = ((xs ** 2).sum(1)[:, None] + (ls ** 2).sum(1)[None, :]
          - 2.0 * xs @ ls.T)
    ref = np.exp(-gamma * np.maximum(d2, 0.0)) @ h
    spec = KernelSpec("rbf", gamma=gamma)
    xj, lj, hj = (jnp.asarray(a, jnp.float32) for a in (xs, ls, h))
    errs = {}
    for prec in ("default", "highest"):
        with jax.default_matmul_precision(prec):
            jnp_f = np.asarray(jnp.dot(spec(xj, lj), hj))
            ker_f = np.asarray(ops.gram_matvec(
                xj, lj, hj, gamma=gamma, interpret=False, backend="tpu"))
        for side, f in (("jnp", jnp_f), ("pallas", ker_f)):
            errs[side, prec] = float(np.max(np.abs(f - ref)))
            log(f"precision {side} @ {prec}: max abs err of the cluster "
                f"means of K vs float64 = {errs[side, prec]:.3e} (values "
                f"{ref.min():.6f}..{ref.max():.6f})")
    for side in ("jnp", "pallas"):
        check(errs[side, "highest"] <= PROBE_LIMIT,
              f"{side} kernel means at 'highest' off by "
              f"{errs[side, 'highest']:.3e} > {PROBE_LIMIT:g}: not f32")


def batch_cost64(x, labels, n_batches: int, gamma: float):
    """Float64 host value of the cost the exact fit reports, for the
    partition ``labels``: at s=1 every row of a batch is a landmark, so a
    batch's inner cost sum_i K_ii - 2 f_i + g is sum_c n_c (1 - g_c), g_c
    the mean rbf kernel value over pairs of the batch's rows in cluster c.
    The mean of 1 - K is taken as -expm1(-gamma d^2): every term is
    positive and nothing cancels. Returns (cost, per-batch counts [B, C])."""
    import numpy as np

    total, counts = 0.0, np.zeros((n_batches, C))
    for b in range(n_batches):
        xb, lb = x[b::n_batches].astype(np.float64), labels[b::n_batches]
        for c in range(C):
            m = xb[lb == c]
            counts[b, c] = len(m)
            sq = (m * m).sum(1)
            s = 0.0
            for a in range(0, len(m), 2048):
                d2 = sq[a:a + 2048, None] + sq[None, :] \
                    - 2.0 * m[a:a + 2048] @ m.T
                s -= np.expm1(-gamma * np.maximum(d2, 0.0)).sum()
            total += s / max(len(m), 1)            # n_c * mean over pairs
    return total, counts


def check_cost(name, res, x, labels, n_batches, gamma) -> float:
    """Hold one fit's reported cost to its float64 value (``batch_cost64``
    of its own labels): the gap is that side's rounding alone."""
    import numpy as np

    cost64, counts = batch_cost64(x, labels, n_batches, gamma)
    cost = sum(h.cost for h in res.history)
    rel = abs(cost - cost64) / cost64
    same = all(np.array_equal(h.counts, counts[b])
               for b, h in enumerate(res.history))
    log(f"{name}: reported cost {cost:.6e}, float64 cost of its labels "
        f"{cost64:.6e}, rel diff {rel:.3e} (limit {COST_LIMIT:g}); batch "
        f"counts equal to its labels' {same}")
    check(rel <= COST_LIMIT, f"{name}: cost off its float64 value by "
                             f"{rel:.3e} > {COST_LIMIT:g}")
    check(same, f"{name}: the batch partitions are not the final labels'")
    return cost


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def exact_fit(mesh, cfg, batches, mode):
    from repro.distributed.outer import DistributedMiniBatchKMeans
    km = DistributedMiniBatchKMeans(mesh, cfg, mode=mode)
    res = km.fit(batches)
    res.state.medoids.block_until_ready()
    return km, res


def exact_labels(jax, x, res, spec):
    import numpy as np
    from repro.core.minibatch import predict
    with jax.default_matmul_precision("highest"):
        return np.asarray(predict(jax.numpy.asarray(x), res.state.medoids,
                                  res.state.medoid_diag, spec=spec))


def inner_program(jax, km, rows: int, n_l: int):
    """The mesh inner-loop program ``km.fit`` runs for one batch, followed
    by the shapes of its arguments (x, landmarks, l_idx, diag, u0, wgt)."""
    import jax.numpy as jnp

    from repro.distributed.inner import distributed_kkmeans_fit
    sds = jax.ShapeDtypeStruct
    args = (sds((rows, D), jnp.float32), sds((n_l, D), jnp.float32),
            sds((n_l,), jnp.int32), sds((rows,), jnp.float32),
            sds((rows,), jnp.int32), sds((rows,), jnp.float32))

    def fn(x, lm, l_idx, diag, u0, wgt):
        return distributed_kkmeans_fit(km.mesh, x, lm, l_idx, diag, u0,
                                       cfg=km.inner_cfg, wgt=wgt)
    return (fn,) + args


def phase_a(jax, clock, x, spec, seed, bytes_limit, fused_precision):
    import numpy as np

    from repro.core import MachineSpec, MiniBatchConfig, plan
    from repro.core.landmarks import num_landmarks
    from repro.data.sampling import split_batches

    p = plan(N, C, MachineSpec(memory_bytes=bytes_limit, n_processors=1),
             d=D)
    log(f"phase A plan: B={p.b} s={p.s} engine={p.engine} "
        f"materialize footprint {p.engine_footprints['materialize']/1e9:.3f}"
        f" GB of {bytes_limit/1e9:.3f} GB ({p.note})")
    cfg = MiniBatchConfig(n_clusters=C, n_batches=p.b, s=p.s, kernel=spec,
                          seed=seed)
    batches = split_batches(x, p.b, strategy="stride")
    rows = len(batches[0])
    n_l = num_landmarks(rows, p.s, n_clusters=C)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    precision_probe(jax, x, spec.gamma)
    # both sides at full f32: on a TPU an f32 jnp.dot defaults to one bf16
    # pass (the probe above shows how far), and the comparison is of the
    # engines, not of matmul precisions. ``fused_precision`` is "default"
    # only in the --bf16-control run.
    with jax.default_matmul_precision(fused_precision):
        with clock.phase(f"phase A fused fit (Pallas assign_fused, "
                         f"{fused_precision} precision)"):
            km, fused = exact_fit(mesh, cfg, batches, "fused")
        mosaic(jax, "assign_fused/gram_matvec in the fused inner loop",
               *inner_program(jax, km, rows, n_l))
    with jax.default_matmul_precision("highest"):
        with clock.phase("phase A reference fit (jnp materialize)"):
            _, ref = exact_fit(mesh, cfg, batches, "materialize")
    lab_f = exact_labels(jax, x, fused, spec)
    lab_r = exact_labels(jax, x, ref, spec)
    agree = matched_agreement(lab_r, lab_f)
    same_medoids = bool(np.array_equal(np.asarray(fused.state.medoids),
                                       np.asarray(ref.state.medoids)))
    log(f"phase A fused vs reference: label agreement {agree:.6f}, inner "
        f"iters {[h.inner_iters for h in fused.history]} vs "
        f"{[h.inner_iters for h in ref.history]}, medoids identical "
        f"{same_medoids}")
    # The two reported costs are not compared with each other to 1e-4: a
    # row's cost term is a difference of near-1 kernel means, and f32
    # summation order alone moves the sum by ~1 % (COST_LIMIT). Each side
    # is held to the float64 cost of its own labels instead.
    cost_r = check_cost("phase A reference", ref, x, lab_r, p.b, spec.gamma)
    cost_f = check_cost("phase A fused", fused, x, lab_f, p.b, spec.gamma)
    log(f"phase A reported costs, fused vs reference: rel diff "
        f"{abs(cost_f - cost_r) / cost_r:.3e}")
    check(agree >= 0.999, f"phase A label agreement {agree} < 0.999")
    return p.b


def serve_all(svc, x, rng):
    """Label every row through the service, then drive ragged requests."""
    import numpy as np

    out = np.empty((len(x),), np.int32)
    step = svc.cfg.max_queue_rows
    for a in range(0, len(x), step):
        uid = svc.submit(x[a:a + step])
        out[a:a + step] = svc.drain()[uid]
    sizes = np.minimum(rng.zipf(1.5, size=300), 512)
    starts = rng.integers(0, len(x) - 512, size=len(sizes))
    want, got = {}, {}
    for n, a in zip(sizes, starts):
        want[svc.submit(x[a:a + n])] = (a, n)
        if len(want) % 3 == 0:
            got.update(svc.step())
    got.update(svc.drain())
    check(set(got) == set(want), "ragged requests: not all completed")
    for uid, (a, n) in want.items():
        check(np.array_equal(got[uid], out[a:a + n]),
              f"ragged request {uid} ({n} rows) != bulk labels")
    return out, int(sizes.sum())


def phase_b(jax, clock, x, spec, seed, n_batches, rng):
    import numpy as np

    from repro.core import MiniBatchConfig, nmi
    from repro.core.minibatch import fit_dataset
    from repro.kernels import ops
    from repro.serving import AssignService, freeze

    cfg = MiniBatchConfig(n_clusters=C, n_batches=n_batches, kernel=spec,
                          seed=seed, method="nystrom", embed_dim=EMBED_DIM)
    with clock.phase(f"phase B Nystrom fit (m={EMBED_DIM}, B={n_batches})"):
        res = fit_dataset(x, cfg)
        res.state.centroids.block_until_ready()
    mosaic(jax, "kernel_matrix in the Nystrom embedding",
           lambda fmap, xb: fmap(xb), res.fmap,
           jax.ShapeDtypeStruct((N // n_batches, D), jax.numpy.float32))
    labels = {}
    for precision in ("f32", "bf16"):
        art = freeze(res, precision=precision)
        with clock.phase(f"phase B AssignService warm ({precision})"):
            svc = AssignService(art)
        for b in svc.cfg.buckets:
            text = svc.program_text(b)
            check("tpu_custom_call" in text,
                  f"serving bucket {b} ({precision}): no tpu_custom_call")
        log(f"kernel predict_assign ({precision}): tpu_custom_call in all "
            f"{svc.compiled_programs} bucket programs {svc.cfg.buckets}")
        with clock.phase(f"phase B serve {N} rows + ragged ({precision})"):
            labels[precision], ragged_rows = serve_all(svc, x, rng)
        log(f"phase B served {N} rows, then 300 ragged requests "
            f"({ragged_rows} rows)")
    a = freeze(res).arrays
    with jax.default_matmul_precision("highest"):
        ref, _ = ops.predict_assign(
            jax.numpy.asarray(x), a["w"], a["aux"], a["v"], a["csq"],
            fused=False, map_kind="rbf", gamma=spec.gamma)
    ref = np.asarray(ref)
    mismatch = int((labels["f32"] != ref).sum())
    drift = nmi(labels["f32"], labels["bf16"])
    log(f"phase B serving vs jnp oracle (highest): {mismatch} of {N} "
        f"labels differ at f32; NMI(f32, bf16) = {drift:.6f}")
    check(mismatch == 0, f"phase B: {mismatch} f32 labels != oracle")
    check(drift >= 0.99, f"phase B: NMI(f32, bf16) {drift} < 0.99")


def four_chips(jax, clock, x, spec, seed, bytes_limit):
    import numpy as np

    from repro.core import MachineSpec, MiniBatchConfig, plan
    from repro.distributed.embed import DistributedEmbedKMeans

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, have "
                             f"{len(devices)}")
    p = plan(N, C, MachineSpec(memory_bytes=bytes_limit, n_processors=4),
             d=D)
    log(f"four chips plan: B={p.b} s={p.s} engine={p.engine} (per chip "
        f"{N // (4 * p.b)} x {int(p.s * N / p.b)} block)")
    # the fused engine against one chip: the one-chip reference would need
    # a resident 70,000^2 block under materialize, which no chip holds. The
    # planned engine runs on the 4x1 mesh and is compared with fused there.
    cfg = MiniBatchConfig(n_clusters=C, n_batches=p.b, s=p.s, kernel=spec,
                          seed=seed, engine="fused")
    batches = [x[i::p.b] for i in range(p.b)]
    one = jax.make_mesh((1,), ("data",), devices=devices[:1])
    row4 = jax.make_mesh((4,), ("data",), devices=devices[:4])
    grid = jax.make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    with jax.default_matmul_precision("highest"):
        with clock.phase("4x1 exact fit, s_step=1"):
            km4, r4 = exact_fit(row4, cfg, batches, None)
        rows = N // p.b
        mosaic(jax, "assign_fused in the 4-way inner loop",
               *inner_program(jax, km4, rows, rows))
        with clock.phase(f"4x1 exact fit, planned engine ({p.engine})"):
            _, r4p = exact_fit(row4, cfg, batches, p.engine)
    lab4 = exact_labels(jax, x, r4, spec)
    lab4p = exact_labels(jax, x, r4p, spec)
    agree4p = matched_agreement(lab4, lab4p)
    log(f"4x1 fused vs {p.engine}: label agreement {agree4p:.6f}")
    precision_probe(jax, x, spec.gamma)
    check_cost("4x1 fused", r4, x, lab4, p.b, spec.gamma)   # as in phase A
    check_cost(f"4x1 {p.engine}", r4p, x, lab4p, p.b, spec.gamma)
    check(agree4p >= 0.999, f"4x1 label agreement {agree4p} < 0.999")
    with jax.default_matmul_precision("highest"):
        cfg2 = dataclasses.replace(cfg, s_step=2)
        with clock.phase("2x2 exact fit, s_step=2"):
            _, r22 = exact_fit(grid, cfg2, batches, None)
        peaks = [peak_bytes(d) for d in devices[:4]]
        log(f"peak_bytes_in_use per chip after the mesh fits: {peaks}")
        with clock.phase("1-chip exact reference fit"):
            _, r1 = exact_fit(one, cfg, batches, None)
    same = bool(np.array_equal(np.asarray(r4.state.medoids),
                               np.asarray(r1.state.medoids)))
    lab1 = exact_labels(jax, x, r1, spec)
    lab22 = exact_labels(jax, x, r22, spec)
    agree22 = matched_agreement(lab1, lab22)
    log(f"4x1 s=1 vs 1 chip: medoids bit-identical {same}; 2x2 s=2 vs "
        f"1 chip: matched label agreement {agree22:.6f} (iters "
        f"{[h.inner_iters for h in r22.history]} vs "
        f"{[h.inner_iters for h in r1.history]})")
    check(same, "4x1 s_step=1 medoids differ from the one-chip fit")
    check(agree22 == 1.0, "2x2 s_step=2 partition differs from one chip")
    check(max(peaks) <= 2 * min(peaks), f"uneven device memory {peaks}")

    ecfg = MiniBatchConfig(n_clusters=C, n_batches=p.b, kernel=spec,
                           seed=seed, method="nystrom", embed_dim=EMBED_DIM)
    e4 = DistributedEmbedKMeans(row4, ecfg)
    staged = e4.stage(batches[0])
    shards = staged.x.addressable_shards
    log(f"4-way staging: rows per device "
        f"{[(s.device.id, s.data.shape[0]) for s in shards]}")
    check(len({s.device for s in shards}) == 4
          and all(s.data.shape[0] == N // (4 * p.b) for s in shards),
          "staged batch is not split n/4 rows per device")
    with clock.phase("4-way embedded fit"):
        res4 = e4.fit([staged] + batches[1:])
        res4.state.centroids.block_until_ready()
    with clock.phase("1-chip embedded reference fit"):
        res1 = DistributedEmbedKMeans(one, ecfg).fit(batches)
        res1.state.centroids.block_until_ready()
    with jax.default_matmul_precision("highest"):
        el4 = np.asarray(res4.predict(x))
        el1 = np.asarray(res1.predict(x))
    cdiff = float(np.max(np.abs(np.asarray(res4.state.centroids)
                                - np.asarray(res1.state.centroids))))
    eagree = matched_agreement(el1, el4)
    log(f"4-way embedded vs 1 chip: matched label agreement {eagree:.6f}, "
        f"max |centroid diff| {cdiff:.3e}")
    check(eagree == 1.0, "4-way embedded partition differs from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path, on four chips")
    ap.add_argument("--bf16-control", action="store_true",
                    help="run only phase A, its fused fit at the default "
                         "matmul precision (one bf16 pass on a TPU): a "
                         "control that the phase A checks must fail")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    from repro.launch.env import configure
    configure(platform="tpu")
    import jax
    import numpy as np

    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    clock = CompileClock(jax)
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compilation cache "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}")

    from repro.core import KernelSpec, gamma_from_dmax
    from repro.data.synthetic import make_blobs
    t0 = time.perf_counter()
    x, _ = make_blobs(N, D, C, seed=args.seed)
    spec = KernelSpec("rbf", gamma=gamma_from_dmax(jax.numpy.asarray(x)))
    log(f"data {x.shape} {x.dtype} seed {args.seed}, gamma "
        f"{spec.gamma:.6e} (made in {time.perf_counter() - t0:.2f}s)")
    bytes_limit = dev.memory_stats()["bytes_limit"]

    if args.four_chips:
        four_chips(jax, clock, x, spec, args.seed, bytes_limit)
    elif args.bf16_control:
        phase_a(jax, clock, x, spec, args.seed, bytes_limit, "default")
        check(False, "bf16 control: every phase A check passed")
    else:
        n_batches = phase_a(jax, clock, x, spec, args.seed, bytes_limit,
                            "highest")
        log(f"peak_bytes_in_use after phase A {peak_bytes(dev)}")
        phase_b(jax, clock, x, spec, args.seed, n_batches,
                np.random.default_rng(args.seed))
    log(f"peak_bytes_in_use {peak_bytes(dev)}; "
        f"compile {clock.seconds:.2f}s over {clock.count} programs "
        f"({clock.cache_hits} persistent-cache hits); wall "
        f"{time.perf_counter() - t_start:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
