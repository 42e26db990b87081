"""Faults planted under the timed path, each of which a cell's comparison
with the reference has to turn into ``correct`` false.

``plant(name, patch)`` breaks the program in place; ``patch(obj, attr,
value)`` sets the attribute (a test's ``monkeypatch.setattr``, or a
``Patches`` that puts the originals back). The CPU tests under
``tests/bench`` plant each one in a whole run; ``bench/calibrate.py
--fault`` reads them on the chip at a cell's own size.

  half_batch          the inner loop's statistics are means over half of
                      the batch's landmark rows
  label_altered       one row's label changed where the inner loop
                      returns it
  max_iters_1         the inner loop cut after one iteration: its labels
                      come from the initial statistics, not their own
  state_unchanged     the Eq.12 merge returns the state it was given
  exchange_left_out   every all-reduce and all-gather of the inner loop
                      stays on its own device
  served_label_altered  one label of every served step changed
"""
from __future__ import annotations

import dataclasses
import types


class Patches:
    """A ``patch`` that remembers the originals, for ``restore``."""

    def __init__(self):
        self._saved: list = []

    def __call__(self, obj, attr, value):
        self._saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)

    def restore(self):
        for obj, attr, value in reversed(self._saved):
            setattr(obj, attr, value)
        self._saved = []


def _wrap_inner(patch, wrap):
    import repro.distributed.outer as outer
    orig = outer.distributed_kkmeans_fit

    def broken(mesh, x, landmarks, l_idx, diag_k, u0, *, cfg, wgt=None):
        return wrap(orig, mesh, x, landmarks, l_idx, diag_k, u0, cfg, wgt)
    patch(outer, "distributed_kkmeans_fit", broken)


def _half_batch(orig, mesh, x, landmarks, l_idx, diag_k, u0, cfg, wgt):
    h = l_idx.shape[0] // 2
    return orig(mesh, x, landmarks[:h], l_idx[:h], diag_k, u0, cfg=cfg,
                wgt=wgt)


def _label_altered(orig, mesh, x, landmarks, l_idx, diag_k, u0, cfg, wgt):
    res = orig(mesh, x, landmarks, l_idx, diag_k, u0, cfg=cfg, wgt=wgt)
    bad = res.labels.at[0].set((res.labels[0] + 1) % cfg.n_clusters)
    return res._replace(labels=bad)


def _max_iters_1(orig, mesh, x, landmarks, l_idx, diag_k, u0, cfg, wgt):
    return orig(mesh, x, landmarks, l_idx, diag_k, u0,
                cfg=dataclasses.replace(cfg, max_iters=1), wgt=wgt)


def _state_unchanged(patch):
    import jax.numpy as jnp

    from repro.distributed.outer import DistributedMiniBatchKMeans

    def unchanged(self, x, diag, res, k_tilde, state, first, wgt):
        return state, jnp.zeros((self.cfg.n_clusters,))
    patch(DistributedMiniBatchKMeans, "_medoid_merge", unchanged)


def _exchange_left_out(patch):
    import jax
    import jax.numpy as jnp

    import repro.distributed.inner as inner
    n_dev = len(jax.devices())
    lax = types.SimpleNamespace(**{k: getattr(jax.lax, k)
                                   for k in dir(jax.lax)
                                   if not k.startswith("__")})
    lax.psum = lambda x, axis_name, **kw: x
    lax.all_gather = lambda x, axis_name, tiled=False, **kw: (
        jnp.concatenate([x] * n_dev) if tiled else jnp.stack([x] * n_dev))
    fake = types.SimpleNamespace(**{k: getattr(jax, k) for k in dir(jax)
                                    if not k.startswith("__")})
    fake.lax = lax
    patch(inner, "jax", fake)


def _served_label_altered(patch):
    from repro.serving.assign import AssignService
    step = AssignService.step

    def altered(self):
        out = step(self)
        for lab in out.values():
            lab[0] = (lab[0] + 1) % self.artifact.n_clusters
        return out
    patch(AssignService, "step", altered)


INNER = {"half_batch": _half_batch, "label_altered": _label_altered,
         "max_iters_1": _max_iters_1}
OTHER = {"state_unchanged": _state_unchanged,
         "exchange_left_out": _exchange_left_out,
         "served_label_altered": _served_label_altered}
NAMES = tuple(INNER) + tuple(OTHER)


def plant(name: str, patch) -> None:
    if name in INNER:
        _wrap_inner(patch, INNER[name])
    elif name in OTHER:
        OTHER[name](patch)
    else:
        raise KeyError(f"no fault {name!r}; have {NAMES}")
