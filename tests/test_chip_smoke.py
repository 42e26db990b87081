"""The host-side checks of ``chip_smoke.py``, on the CPU at a small size:
the float64 cost it holds each exact fit to, and that the cost check
rejects a fit whose kernel tiles were rounded to bf16."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402

from repro.core import KernelSpec, MiniBatchConfig, gamma_from_dmax  # noqa
from repro.data.synthetic import make_blobs  # noqa: E402


def _naive_cost(x, labels, n_batches, gamma):
    """sum over batches and rows of K_ii - 2 f_i + g at s=1, in float64."""
    total = 0.0
    for b in range(n_batches):
        xb, lb = x[b::n_batches].astype(np.float64), labels[b::n_batches]
        k = np.exp(-gamma * ((xb[:, None, :] - xb[None]) ** 2).sum(-1))
        for c in np.unique(lb):
            mem = lb == c
            f = k[:, mem].mean(1)                 # f_ic for every row
            g = f[mem].mean()
            total += (1.0 - 2.0 * f[mem] + g).sum()
    return total


@pytest.mark.parametrize("n_batches", [1, 3])
def test_batch_cost64_is_the_inner_cost(n_batches):
    rng = np.random.default_rng(n_batches)
    x = rng.normal(size=(90, 6)).astype(np.float32)
    labels = rng.integers(0, chip_smoke.C, size=90)
    cost, counts = chip_smoke.batch_cost64(x, labels, n_batches, 0.05)
    np.testing.assert_allclose(cost, _naive_cost(x, labels, n_batches, 0.05),
                               rtol=1e-10)
    assert counts.sum() == 90
    assert np.array_equal(counts[0],
                          np.bincount(labels[::n_batches],
                                      minlength=chip_smoke.C))


@pytest.mark.parametrize("precision,passes", [("f32", True),
                                              ("bf16", False)])
def test_cost_check_rejects_bf16_tiles(precision, passes):
    """At the sigma = 4 d_max gamma a row's cost is ~1e-4 of 1: f32 holds
    the reported cost near its float64 value, bf16 tiles do not."""
    x, _ = make_blobs(2000, 32, chip_smoke.C, seed=0)
    spec = KernelSpec("rbf", gamma=gamma_from_dmax(jax.numpy.asarray(x)))
    cfg = MiniBatchConfig(n_clusters=chip_smoke.C, n_batches=2, kernel=spec,
                          seed=0, precision=precision)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    _, res = chip_smoke.exact_fit(mesh, cfg, [x[0::2], x[1::2]],
                                  "materialize")
    labels = chip_smoke.exact_labels(jax, x, res, spec)
    if passes:
        chip_smoke.check_cost("f32 fit", res, x, labels, 2, spec.gamma)
    else:
        with pytest.raises(SystemExit, match="cost off its float64 value"):
            chip_smoke.check_cost("bf16 fit", res, x, labels, 2, spec.gamma)
