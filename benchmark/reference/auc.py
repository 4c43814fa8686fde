"""Area under the ROC curve by ranks (Mann-Whitney), ties at half credit.
Plain numpy, float64."""

from __future__ import annotations

import numpy as np


def auc(y: np.ndarray, score: np.ndarray) -> float:
    y = np.asarray(y).astype(bool)
    score = np.asarray(score, np.float64)
    _, inverse, counts = np.unique(score, return_inverse=True,
                                   return_counts=True)
    # average rank of each tie group, 1-based
    last = np.cumsum(counts)
    rank = (last - (counts - 1) / 2.0)[inverse]
    n_pos, n_neg = int(y.sum()), int((~y).sum())
    return float((rank[y].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))
