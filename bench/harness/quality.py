"""Clustering quality against the generator's classes."""
from __future__ import annotations

import numpy as np


def nmi(y: np.ndarray, u: np.ndarray) -> float:
    """Normalized mutual information, sqrt-normalized (the paper's NMI)."""
    y, u = np.asarray(y), np.asarray(u)
    o = np.zeros((y.max() + 1, u.max() + 1), np.float64)
    np.add.at(o, (y, u), 1.0)
    n = o.sum()
    py, pu = o.sum(1), o.sum(0)
    nz = o > 0
    mi = (o[nz] * np.log(n * o[nz] / np.outer(py, pu)[nz])).sum() / n
    hy = -(py[py > 0] / n * np.log(py[py > 0] / n)).sum()
    hu = -(pu[pu > 0] / n * np.log(pu[pu > 0] / n)).sum()
    denom = np.sqrt(hy * hu)
    return float(mi / denom) if denom > 0 else 0.0
