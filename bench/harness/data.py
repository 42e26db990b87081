"""Rows, classes and keys made from ``--seed``.

The generator is the benchmark's own copy of the MNIST-envelope rule that
``repro.data.synthetic.make_mnist_like`` follows: 784-d rows, 10 classes,
each class a rank-16 affine manifold (a sparse mean plus a random basis)
with pixel noise, clipped to [0, 1]. It runs on the device, in one jitted
call per array, from a threefry key that holds all the bits of the seed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, *salt: int) -> np.ndarray:
    """Two uint32 words from a seed of any size (``jax.random.PRNGKey``
    keeps only the low 32 bits of a large seed without x64)."""
    return np.random.SeedSequence([int(seed), *map(int, salt)]) \
        .generate_state(2, np.uint32)


def key(seed: int, *salt: int):
    return jax.random.wrap_key_data(jnp.asarray(seed_words(seed, *salt)),
                                    impl="threefry2x32")


def small_seed(seed: int, *salt: int) -> int:
    """A non-negative int32 derived from (seed, salt): what the program's
    own ``MiniBatchConfig.seed`` takes."""
    return int(seed_words(seed, *salt)[0] & 0x7FFFFFFF)


def class_params(k, gen: dict, dim: int, classes: int):
    """Per-class sparse means [C, d] and bases [C, r, d]."""
    r = int(gen["rank"])
    k1, k2, k3 = jax.random.split(k, 3)
    mean = jax.random.uniform(k1, (classes, dim), jnp.float32, 0.0,
                              float(gen["mean_max"]))
    mean = mean * (jax.random.uniform(k2, (classes, dim))
                   < float(gen["mean_density"]))
    basis = jax.random.normal(k3, (classes, r, dim), jnp.float32) \
        / np.sqrt(dim)
    return mean, basis


@partial(jax.jit, static_argnames=("n", "noise"))
def _rows(k, mean, basis, *, n: int, noise: float):
    classes, r, dim = basis.shape
    ky, kz, ke = jax.random.split(k, 3)
    y = jax.random.randint(ky, (n,), 0, classes, jnp.int32)
    z = jax.random.normal(kz, (n, r), jnp.float32)
    x = mean[y] + noise * jax.random.normal(ke, (n, dim), jnp.float32)
    for c in range(classes):      # one [n, r] x [r, d] product per class
        zc = jnp.where((y == c)[:, None], z, 0.0)
        x = x + jnp.dot(zc, basis[c], precision=jax.lax.Precision.HIGHEST)
    return jnp.clip(x, 0.0, 1.0), y


def rows(k, params, n: int, gen: dict):
    """(x [n, d] float32, y [n] int32) on the host."""
    mean, basis = params
    x, y = _rows(k, mean, basis, n=int(n), noise=float(gen["noise"]))
    return np.array(x), np.array(y)


def gamma_sigma_rule(x: np.ndarray, factor: float) -> float:
    """sigma = factor * d_max with d_max the diameter of the bounding box
    (the paper's sigma = 4 d_max rule, as the repository states it);
    gamma = 1 / (2 sigma^2)."""
    span = x.max(axis=0).astype(np.float64) - x.min(axis=0)
    sigma = factor * max(float(np.sqrt((span * span).sum())), 1e-12)
    return 1.0 / (2.0 * sigma * sigma)
