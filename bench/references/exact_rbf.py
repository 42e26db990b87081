"""Plain reference of exact rbf kernel k-means (paper Eq.4-12).

Imports nothing of the program. Every quantity is built from
E = 1 - K = -expm1(-gamma d^2), which is small and positive at the paper's
gamma (sigma = 4 d_max), so nothing cancels: d^2 is an f32 product at
"highest" precision, E is summed in f32 over row blocks on the device, and
everything after the sums is float64 on the host.

For a batch x with landmark rows L and labels u (u_L their labels):

    F1[i, c] = sum_{j in L, u_j = c} E(x_i, x_j)         [n, C]
    n_c      = |{j in L: u_j = c}|,   G1_c = sum_{j in L, u_j = c} F1[j, c]
    d_i(c)   = 1 - 2 f_ic + g_c = 2 F1[i, c] / n_c - G1_c / n_c^2

d_i(c) is row i's kernel distance to cluster c's centroid (Eq.4), its
minimum over c the row's term of the inner cost (Eq.9).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 2048


@jax.jit
def _f1_block(xb, xl, onehot_l, gamma):
    with jax.default_matmul_precision("highest"):
        d2 = (jnp.sum(xb * xb, 1)[:, None] + jnp.sum(xl * xl, 1)[None, :]
              - 2.0 * xb @ xl.T)
        e = -jnp.expm1(-gamma * jnp.maximum(d2, 0.0))
        return e @ onehot_l


@jax.jit
def _e_block(xb, m, gamma):
    with jax.default_matmul_precision("highest"):
        d2 = (jnp.sum(xb * xb, 1)[:, None] + jnp.sum(m * m, 1)[None, :]
              - 2.0 * xb @ m.T)
        return -jnp.expm1(-gamma * jnp.maximum(d2, 0.0))


def _blocks(fn, x: np.ndarray, device, *args) -> np.ndarray:
    """Apply ``fn`` to fixed-size row blocks of x (the last one padded, so
    one program serves every block) and stack the float64 results."""
    n = len(x)
    out = []
    for a in range(0, n, BLOCK):
        xb = np.zeros((BLOCK, x.shape[1]), np.float32)
        xb[:min(BLOCK, n - a)] = x[a:a + BLOCK]
        r = fn(jax.device_put(xb, device), *args)
        out.append(np.asarray(r, np.float64)[:min(BLOCK, n - a)])
    return np.concatenate(out)


def e_matrix(x: np.ndarray, m: np.ndarray, gamma: float, device
             ) -> np.ndarray:
    """E(x_i, m_c) = 1 - K(x_i, m_c), float64 [n, len(m)]."""
    return _blocks(_e_block, x, device,
                   jax.device_put(np.asarray(m, np.float32), device),
                   np.float32(gamma))


class Batch:
    """Reference statistics of one batch's labels (see module docstring)."""

    def __init__(self, x: np.ndarray, l_idx: np.ndarray, labels: np.ndarray,
                 classes: int, gamma: float, device):
        self.x, self.gamma, self.device = x, gamma, device
        self.labels = np.asarray(labels)
        lab_l = self.labels[l_idx]
        onehot = np.eye(classes, dtype=np.float32)[lab_l]
        xl = jax.device_put(np.asarray(x[l_idx], np.float32), device)
        self.f1 = _blocks(_f1_block, x, device, xl,
                          jax.device_put(onehot, device), np.float32(gamma))
        self.n = np.bincount(lab_l, minlength=classes).astype(np.float64)
        g1 = (self.f1[l_idx] * onehot).sum(0)
        with np.errstate(divide="ignore", invalid="ignore"):
            d = 2.0 * self.f1 / self.n - g1 / (self.n * self.n)
        self.d = np.where(self.n > 0, d, np.inf)
        rows = np.arange(len(x))
        self.own = self.d[rows, self.labels]
        # the typical row's distance to its centroid: the unit in which
        # every gap below is stated
        self.scale = float(np.median(self.own))

    def cost(self) -> float:
        """Eq.9 inner cost of the labels, float64."""
        return float(self.own.sum())

    def eq7_scores(self) -> np.ndarray:
        """Eq.7 medoid score K_ii - 2 f_ic, shifted by -1: 2 F1 / n_c."""
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(self.n > 0, 2.0 * self.f1 / self.n, np.inf)


def rows_of(x: np.ndarray, points: np.ndarray) -> np.ndarray:
    """Index of the row of x equal to each point (-1 where none is)."""
    out = np.full(len(points), -1, np.int64)
    for c, p in enumerate(points):
        hit = np.flatnonzero((x == p).all(1))
        if len(hit):
            out[c] = hit[0]
    return out


def nearest(x: np.ndarray, medoids: np.ndarray):
    """Float64 squared distances of each row to each medoid, and the
    nearest medoid (an rbf kernel's argmax is the nearest point)."""
    x64, m64 = x.astype(np.float64), medoids.astype(np.float64)
    d2 = ((x64 * x64).sum(1)[:, None] + (m64 * m64).sum(1)[None, :]
          - 2.0 * x64 @ m64.T)
    return d2, d2.argmin(1)
