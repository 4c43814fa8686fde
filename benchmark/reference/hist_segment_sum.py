"""One level's histograms as a plain segment sum, numpy float64 — what the
histogram kernel (``hist_pallas``) and the sharded level histogram are held
to. hist[f, node * n_bins_tot + bin, :] = sum over the rows at that node
with that bin of (g, h, w); rows with node < 0 count nowhere."""

from __future__ import annotations

import numpy as np


def level_histograms(binned: np.ndarray, node: np.ndarray, g: np.ndarray,
                     h: np.ndarray, w: np.ndarray, n_nodes: int,
                     n_bins_tot: int) -> np.ndarray:
    """``binned`` [rows, F] integer bins; returns [F, n_nodes*n_bins_tot, 3]
    float64."""
    live = node >= 0
    base = node[live].astype(np.int64) * n_bins_tot
    stats = [np.asarray(v, np.float64)[live] for v in (g, h, w)]
    size = n_nodes * n_bins_tot
    out = np.zeros((binned.shape[1], size, 3))
    for f in range(binned.shape[1]):
        ids = base + binned[live, f]
        for k, v in enumerate(stats):
            out[f, :, k] = np.bincount(ids, v, size)
    return out
