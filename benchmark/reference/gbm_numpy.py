"""A plain level-wise histogram GBM in numpy float64 — the reference the
build cells' models are held to.

It follows the tree engine as ``h2o3_tpu/models/tree.py`` and
``models/gbm.py`` STATE it, written again from those statements, with no
kernel, no sibling subtraction, no float32 and no shared code:

- features are binned once into ``nbins`` quantile bins (edges at the
  1/nbins .. (nbins-1)/nbins quantiles of the training sample; bin = number
  of edges <= x);
- bernoulli boosting on the margin F, started at log(ybar / (1 - ybar)):
  g = p - y, h = max(p (1 - p), 1e-10), p = sigmoid(F);
- a tree grows level by level to ``max_depth``. At a node with sums G, H, W
  (W = rows), a split "bins < t go left" on one feature has
  gain = 1/2 (GL^2/(HL+lambda) + GR^2/(HR+lambda) - G^2/(H+lambda)) - gamma,
  is allowed when WL >= min_rows and WR >= min_rows, and is taken when the
  best gain > min_split_improvement; the first best in (feature, t) order
  wins a tie;
- a leaf's value is -G / (H + lambda); F += learn_rate * leaf.

Departures from the program, on purpose: float64 throughout; exact
quantiles of the whole sample where the program takes a strided sample of
100,000 rows; no missing-value direction (the benchmark's data has none);
L1 (``reg_alpha``) is refused unless 0 (no configuration sets it).

Tolerance, used by checks/auc_vs_reference.py: the model under test may
score at most 0.002 AUC below this reference on 200,000 held-out rows.
Reason: the two differ by sampling, not by method, and both are scored on
the SAME held-out rows, so their difference is far steadier than either AUC
(whose own standard error is near 0.001). The reference trains on a
200,000-row sample and the system on the whole frame, which can only help a
model of this size; the bin edges come from different samples. On the v5e
the system scored 0.0003 to 0.0009 ABOVE the reference in five runs of two
configurations (my chip run, PR 22). What 0.002 catches, measured with this
file on the CPU at 200,000 rows (my numpy runs, PR 22; GBM-64 at 10 trees
scores 0.81289): trees that stop a level early lose 0.0038 (XGBoost-256 at
3 trees: 0.0047), a tree missing of three loses 0.0036, min_rows of 2,000 in
place of 10 loses 0.0011 and a quarter of the rows 0.0012 (both inside it).
ISSUE 22 asked for 0.005, which a missing level would have passed.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Node:
    feature: int = -1        # -1: a leaf
    t: int = 0               # bins < t go left
    value: float = 0.0
    left: "Node | None" = None
    right: "Node | None" = None


@dataclasses.dataclass
class Model:
    edges: list              # per feature, ascending bin edges
    f0: float
    learn_rate: float
    trees: list              # [Node]

    def margin(self, X: np.ndarray) -> np.ndarray:
        bins = bin_features(X, self.edges)
        F = np.full(len(X), self.f0, np.float64)
        for tree in self.trees:
            F += self.learn_rate * _predict_tree(tree, bins)
        return F

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        return 1.0 / (1.0 + np.exp(-self.margin(X)))


def bin_edges(X: np.ndarray, nbins: int) -> list:
    qs = np.linspace(0, 1, nbins + 1)[1:-1]
    return [np.unique(np.quantile(X[:, j].astype(np.float64), qs,
                                  method="inverted_cdf"))
            for j in range(X.shape[1])]


def bin_features(X: np.ndarray, edges: list) -> np.ndarray:
    return np.stack([np.searchsorted(e, X[:, j].astype(np.float64),
                                     side="right")
                     for j, e in enumerate(edges)], axis=1)


def _predict_tree(root: Node, bins: np.ndarray) -> np.ndarray:
    out = np.zeros(len(bins), np.float64)
    stack = [(root, np.arange(len(bins)))]
    while stack:
        node, rows = stack.pop()
        if node.feature < 0:
            out[rows] = node.value
            continue
        left = bins[rows, node.feature] < node.t
        stack.append((node.left, rows[left]))
        stack.append((node.right, rows[~left]))
    return out


def best_split(hist: np.ndarray, nbins: int, min_rows: float, lam: float,
               gamma: float):
    """``hist`` [F, nbins, 3] of (G, H, W) for ONE node. Returns (gain,
    feature, t) of the best allowed split, or None."""
    cum = np.cumsum(hist, axis=1)
    G, H, W = cum[0, -1]
    left = cum[:, : nbins - 1]                    # split t = b + 1
    gl, hl, wl = left[..., 0], left[..., 1], left[..., 2]
    gr, hr, wr = G - gl, H - hl, W - wl
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = 0.5 * (gl * gl / (hl + lam) + gr * gr / (hr + lam)
                      - G * G / (H + lam)) - gamma
    gain = np.where((wl >= min_rows) & (wr >= min_rows), gain, -np.inf)
    flat = int(np.argmax(gain))
    f, b = divmod(flat, nbins - 1)
    if not np.isfinite(gain[f, b]):
        return None
    return float(gain[f, b]), f, b + 1


def grow_tree(bins: np.ndarray, g: np.ndarray, h: np.ndarray, *, max_depth: int,
              nbins: int, min_rows: float, lam: float, gamma: float,
              min_split_improvement: float) -> Node:
    n_feat = bins.shape[1]
    root = Node()
    level = [(root, np.arange(len(bins)))]
    for depth in range(max_depth + 1):
        nxt = []
        for node, rows in level:
            gs, hs = g[rows], h[rows]
            G, H = gs.sum(), hs.sum()
            node.value = -G / (H + lam) if len(rows) else 0.0
            if depth == max_depth or len(rows) == 0:
                continue
            hist = np.zeros((n_feat, nbins, 3))
            for j in range(n_feat):
                b = bins[rows, j]
                hist[j, :, 0] = np.bincount(b, gs, nbins)
                hist[j, :, 1] = np.bincount(b, hs, nbins)
                hist[j, :, 2] = np.bincount(b, minlength=nbins)
            found = best_split(hist, nbins, min_rows, lam, gamma)
            if found is None or not found[0] > min_split_improvement:
                continue
            _, node.feature, node.t = found
            go_left = bins[rows, node.feature] < node.t
            node.left, node.right = Node(), Node()
            nxt.append((node.left, rows[go_left]))
            nxt.append((node.right, rows[~go_left]))
        level = nxt
    return root


def fit(X: np.ndarray, y: np.ndarray, *, ntrees: int, max_depth: int,
        nbins: int, learn_rate: float, min_rows: float, reg_lambda: float,
        gamma: float = 0.0, min_split_improvement: float = 1e-5,
        reg_alpha: float = 0.0) -> Model:
    if reg_alpha:
        raise ValueError("the reference has no L1 term")
    y = np.asarray(y, np.float64)
    edges = bin_edges(X, nbins)
    bins = bin_features(X, edges)
    ybar = min(max(float(y.mean()), 1e-6), 1 - 1e-6)
    f0 = float(np.log(ybar / (1 - ybar)))
    F = np.full(len(y), f0)
    trees = []
    for _ in range(ntrees):
        p = 1.0 / (1.0 + np.exp(-F))
        g, h = p - y, np.maximum(p * (1 - p), 1e-10)
        tree = grow_tree(bins, g, h, max_depth=max_depth, nbins=nbins,
                         min_rows=min_rows, lam=reg_lambda, gamma=gamma,
                         min_split_improvement=min_split_improvement)
        trees.append(tree)
        F += learn_rate * _predict_tree(tree, bins)
    return Model(edges, f0, learn_rate, trees)
