"""The comparison that decides ``correct`` in a fit cell.

For each checked batch the plain reference (``bench/references``, named by
the configuration) recomputes from the batch's rows and the labels the
inner loop returned:

  rows_mismatch     rows missing from the labels, from the reported counts'
                    total, and the gap between the reported counts and the
                    counts of the labels (exact: limit 0)
  cost_rel_err      reported Eq.9 cost against the float64 cost of the
                    same labels, relative
  label_regret      widest gap, over the batch's rows, between a row's
                    distance to its own cluster and to the nearest one,
                    both under the statistics of the labels themselves
                    (Eq.4: the labels are a fixpoint), in units of the
                    batch's median row distance d_i(u_i). Read on the
                    batches whose inner loop stopped because no label
                    changed, before ``max_inner_iters``; a loop cut by
                    that cap returns labels one step from their own
                    statistics, which the program states (0 where every
                    checked batch reached the cap)
  f_err             widest gap between the kernel means f_ic the inner loop
                    returned with the labels and the reference's, in units
                    of the batch's median row distance d_i(u_i) (the Gram
                    engine's precision)
  medoids_off_batch clusters whose new medoid is not a row of the batch
                    (or, empty in the batch, not the old medoid): exact
  medoid_regret     gap between the score of the medoid the program chose
                    and the best score: Eq.7 on a fit's first batch, Eq.12
                    after it, in the same unit as f_err
  replicas_differ   devices whose copy of the new medoids differs from the
                    first device's (exact)

Eq.12 scores a row against the batch medoids of Eq.7, which ``fit`` does not
return; where two rows are within rounding of the best Eq.7 score the
program may have taken either. So the Eq.12 gap is the least over the
``EQ7_CANDIDATES`` best Eq.7 rows of each cluster.
"""
from __future__ import annotations

import numpy as np

EQ7_CANDIDATES = 8


def _copies(state) -> list:
    return [np.asarray(s.data) for s in state.medoids.addressable_shards]


def batch_numbers(rec, classes: int, gamma: float, device, ref,
                  max_iters: int) -> dict:
    x = rec.x
    labels = np.asarray(rec.labels)
    l_idx = np.asarray(rec.l_idx)
    counts = np.asarray(rec.stats.counts, np.float64)
    out = {}
    lab_counts = np.bincount(labels[l_idx], minlength=classes)[:classes]
    out["rows_mismatch"] = float(
        abs(len(labels) - len(x)) + abs(counts.sum() - len(l_idx))
        + np.abs(counts - lab_counts).sum())
    b = ref.Batch(x, l_idx, labels, classes, gamma, device)
    cost = b.cost()
    out["cost_rel_err"] = abs(float(rec.stats.cost) - cost) / cost
    regret = 0.0
    if int(rec.stats.inner_iters) < max_iters:
        regret = float((b.own - b.d.min(1)).max() / b.scale)
    out["label_regret"] = regret
    f_ref = 1.0 - b.f1 / np.maximum(b.n, 1.0)
    live = b.n > 0
    f = np.asarray(rec.f, np.float64)
    out["f_err"] = float(np.abs(f - f_ref)[:, live].max() / b.scale)

    copies = _copies(rec.new)
    new = copies[0]
    out["replicas_differ"] = float(sum(not np.array_equal(c, new)
                                       for c in copies[1:]))
    rows = ref.rows_of(x, new)
    s7 = b.eq7_scores()
    live = counts > 0
    if rec.prev is None:
        off = int(np.sum(rows < 0))
        gaps = [s7[rows[c], c] - s7[:, c].min()
                for c in range(classes) if live[c] and rows[c] >= 0]
    else:
        prev = np.asarray(rec.prev.medoids)
        cards = np.asarray(rec.prev.cardinalities, np.float64)
        kept = np.array([np.array_equal(new[c], prev[c])
                         for c in range(classes)])
        off = int(np.sum(live & (rows < 0)) + np.sum(~live & ~kept))
        cand = np.argsort(s7, axis=0)[:EQ7_CANDIDATES]        # [K, C]
        k = len(cand)
        e = ref.e_matrix(x, np.concatenate([prev, x[cand.reshape(-1)]]),
                         gamma, device)
        a = counts / np.maximum(counts + cards, 1.0)
        s12 = (2.0 * (1.0 - a) * e[:, None, :classes]
               + 2.0 * a * e[:, classes:].reshape(len(x), k, classes))
        gaps = [min(s12[rows[c], j, c] - s12[:, j, c].min()
                    for j in range(k))
                for c in range(classes) if live[c] and rows[c] >= 0]
    out["medoids_off_batch"] = float(off)
    out["medoid_regret"] = float(max(gaps, default=0.0) / b.scale)
    return out


def numbers(records, classes: int, gamma: float, device, ref,
            max_iters: int) -> dict:
    """The worst of each number over the checked batches."""
    worst: dict = {}
    for rec in records:
        for k, v in batch_numbers(rec, classes, gamma, device, ref,
                                  max_iters).items():
            worst[k] = max(worst.get(k, -np.inf), v)
    return worst
