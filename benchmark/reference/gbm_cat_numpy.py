"""A plain level-wise histogram GBM with CATEGORICAL GROUP SPLITS, numpy
float64 — the reference ``gbm100-airline-cat-build``'s models are held to.

Written from the published rule (H2O-3's ``DHistogram`` and
``DTree.findBestSplitPoint``; Fisher 1958 for the sorted-prefix search), with
no kernel, no sibling subtraction, no float32, no sampling and no code of
``h2o3_tpu/models/tree.py``:

- a numeric column is binned once into ``nbins`` quantile bins (bin = number
  of edges <= x); a categorical column of cardinality c gets a bin a level
  (its code) up to ``nbins_cats`` bins, and is range-grouped
  (``code * nbins_cats // c``) only past that, whatever ``nbins`` is; a
  missing value (NaN) has a bin of its own after the regular ones;
- bernoulli boosting on the margin F from log(ybar / (1 - ybar)):
  g = p - y, h = max(p (1 - p), 1e-10);
- a tree grows level by level to ``max_depth``. At a node with sums G, H, W
  (W = rows) a candidate sends a set L of regular bins left and the missing
  bin to one side; gain = 1/2 (GL^2/(HL+lambda) + GR^2/(HR+lambda)
  - G^2/(H+lambda)) - gamma; allowed when WL >= min_rows and WR >= min_rows;
  taken when the best gain > min_split_improvement;
- NUMERIC feature: L = bins < t, t = 1 .. B-1;
- CATEGORICAL feature: the node's occupied bins are ranked by G/H (empty
  bins last, ties by bin index), and L = the first t bins of that order,
  t = 1 .. B-1 — the best sorted prefix, which for a convex loss holds the
  best of all 2^c subsets;
- both directions of the missing bin are tried for every candidate; among
  equal gains the first in (missing left before missing right, feature, t)
  order wins;
- a leaf's value is -G / (H + lambda); F += learn_rate * leaf.

B is ONE bin count for every feature, the largest any feature needs
(``max(nbins, largest categorical bin count)``): a feature with fewer bins
leaves the upper ones empty, which adds no candidate but one — "every value
left, missing right" exists for a feature with fewer than B bins (its upper
prefixes) and not for one that fills all B.

Departures from H2O-3, each on purpose and each the engine's standing one:
global quantile bins made once where ``DHistogram`` re-bins a node's value
range at every level (QuantilesGlobal is H2O's own option for it); (g, h)
Newton statistics where H2O's GBM sums (w, wy, wyy) and fits leaves by a
per-distribution gamma pass (equal for bernoulli up to the h clamp);
exact quantiles of the whole sample where the program takes a strided sample
of 100,000 rows; no row or column sampling (the configuration has none);
L1 refused unless 0.

``best_split`` is the float64 search over ONE node's histogram, and
``split_gain`` a given split's gain from it: checks/cat_split_vs_reference.py
holds the program's ``tree._find_splits`` to them on the program's own
histograms.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Node:
    feature: int = -1                 # -1: a leaf
    left_bins: np.ndarray | None = None   # [B] bool: regular bins going left
    na_left: bool = False
    value: float = 0.0
    gain: float = 0.0
    left: "Node | None" = None
    right: "Node | None" = None


@dataclasses.dataclass
class Model:
    edges: list                # per feature: ascending edges, None if categorical
    cat_cards: np.ndarray      # [F] cardinality, 0 for a numeric feature
    nbins_cats: int
    n_bins: int                # B: regular bins of every feature; B = missing
    f0: float
    learn_rate: float
    trees: list                # [Node]

    def bins(self, X: np.ndarray) -> np.ndarray:
        return bin_features(X, self.edges, self.cat_cards, self.nbins_cats,
                            self.n_bins)

    def margin(self, X: np.ndarray) -> np.ndarray:
        bins = self.bins(X)
        F = np.full(len(X), self.f0, np.float64)
        for tree in self.trees:
            F += self.learn_rate * predict_tree(tree, bins, self.n_bins)
        return F

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        return 1.0 / (1.0 + np.exp(-self.margin(X)))


def bin_edges(X: np.ndarray, nbins: int, cat_cards) -> list:
    qs = np.linspace(0, 1, nbins + 1)[1:-1]
    out = []
    for j, card in enumerate(cat_cards):
        if card > 0:
            out.append(None)
            continue
        col = X[:, j].astype(np.float64)
        out.append(np.unique(np.quantile(col[~np.isnan(col)], qs,
                                         method="inverted_cdf")))
    return out


def engine_bins(nbins: int, cat_cards, nbins_cats: int) -> int:
    cats = [min(int(c), nbins_cats) for c in cat_cards if c > 0]
    return max([nbins] + cats)


def bin_features(X: np.ndarray, edges: list, cat_cards, nbins_cats: int,
                 n_bins: int) -> np.ndarray:
    """[rows, F] int64 bins; a NaN reads ``n_bins``, the missing bin."""
    out = np.empty(X.shape, np.int64)
    for j, card in enumerate(cat_cards):
        col = X[:, j].astype(np.float64)
        nan = np.isnan(col)
        if card > 0:
            code = np.where(nan, 0, col).astype(np.int64)
            b = code * nbins_cats // card if card > nbins_cats else code
        else:
            b = np.searchsorted(edges[j], col, side="right")
        out[:, j] = np.where(nan, n_bins, b)
    return out


def predict_tree(root: Node, bins: np.ndarray, n_bins: int) -> np.ndarray:
    out = np.zeros(len(bins), np.float64)
    stack = [(root, np.arange(len(bins)))]
    while stack:
        node, rows = stack.pop()
        if node.feature < 0:
            out[rows] = node.value
            continue
        b = bins[rows, node.feature]
        left = np.where(b >= n_bins, node.na_left,
                        node.left_bins[np.minimum(b, n_bins - 1)])
        stack.append((node.left, rows[left]))
        stack.append((node.right, rows[~left]))
    return out


def _gain(gl, hl, G, H, lam, gamma):
    gr, hr = G - gl, H - hl
    with np.errstate(divide="ignore", invalid="ignore"):
        return 0.5 * (gl * gl / (hl + lam) + gr * gr / (hr + lam)
                      - G * G / (H + lam)) - gamma


def bin_order(hist_f: np.ndarray) -> np.ndarray:
    """The order a categorical feature's regular bins are scanned in at one
    node: by G/H, empty bins last, ties by bin index. ``hist_f`` [B, 3]."""
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = hist_f[:, 0] / np.maximum(hist_f[:, 1], 1e-12)
    ratio = np.where(hist_f[:, 2] > 0, ratio, np.inf)
    return np.argsort(ratio, kind="stable")


def best_split(hist: np.ndarray, is_cat, min_rows: float, lam: float = 0.0,
               gamma: float = 0.0):
    """``hist`` [F, B+1, 3] of (G, H, W) for ONE node, the last bin the
    missing one. Returns ``(gain, feature, t, na_left, left_bins [B] bool)``
    of the best allowed split, or None."""
    hist = np.asarray(hist, np.float64)
    F, Bt, _ = hist.shape
    B = Bt - 1
    G, H, W = hist[0].sum(axis=0)
    gains = np.full((2, F, B - 1), -np.inf)
    orders = []
    for f in range(F):
        reg, na = hist[f, :B], hist[f, B]
        order = bin_order(reg) if is_cat[f] else np.arange(B)
        orders.append(order)
        cum = np.cumsum(reg[order], axis=0)[: B - 1]       # prefix t = i + 1
        for d, add in enumerate((na, np.zeros(3))):        # missing left, right
            gl, hl, wl = (cum + add).T
            gain = _gain(gl, hl, G, H, lam, gamma)
            ok = (wl >= min_rows) & (W - wl >= min_rows)
            gains[d, f] = np.where(ok, gain, -np.inf)
    flat = int(np.argmax(gains))
    d, f, i = np.unravel_index(flat, gains.shape)
    if not np.isfinite(gains[d, f, i]):
        return None
    left_bins = np.zeros(B, bool)
    left_bins[orders[f][: i + 1]] = True
    return float(gains[d, f, i]), int(f), int(i + 1), bool(d == 0), left_bins


def split_gain(hist: np.ndarray, feature: int, left_bins, na_left: bool,
               lam: float = 0.0, gamma: float = 0.0):
    """(gain, WL, WR) of sending ``left_bins`` [B] bool of ``feature`` (and
    the missing bin where ``na_left``) left, from one node's histogram."""
    hist = np.asarray(hist, np.float64)
    B = hist.shape[1] - 1
    G, H, W = hist[0].sum(axis=0)
    left = hist[feature, :B][np.asarray(left_bins, bool)].sum(axis=0)
    if na_left:
        left = left + hist[feature, B]
    gl, hl, wl = left
    return float(_gain(gl, hl, G, H, lam, gamma)), float(wl), float(W - wl)


def node_histogram(bins: np.ndarray, g: np.ndarray, h: np.ndarray,
                   n_bins: int) -> np.ndarray:
    """[F, n_bins + 1, 3] of (G, H, W) over the rows given."""
    F = bins.shape[1]
    hist = np.zeros((F, n_bins + 1, 3))
    for j in range(F):
        b = bins[:, j]
        hist[j, :, 0] = np.bincount(b, g, n_bins + 1)
        hist[j, :, 1] = np.bincount(b, h, n_bins + 1)
        hist[j, :, 2] = np.bincount(b, minlength=n_bins + 1)
    return hist


def grow_tree(bins: np.ndarray, g: np.ndarray, h: np.ndarray, is_cat, *,
              max_depth: int, n_bins: int, min_rows: float, lam: float,
              gamma: float, min_split_improvement: float) -> Node:
    root = Node()
    level = [(root, np.arange(len(bins)))]
    for depth in range(max_depth + 1):
        nxt = []
        for node, rows in level:
            gs, hs = g[rows], h[rows]
            node.value = -gs.sum() / (hs.sum() + lam) if len(rows) else 0.0
            if depth == max_depth or len(rows) == 0:
                continue
            found = best_split(node_histogram(bins[rows], gs, hs, n_bins),
                               is_cat, min_rows, lam, gamma)
            if found is None or not found[0] > min_split_improvement:
                continue
            node.gain, node.feature, _t, node.na_left, node.left_bins = found
            b = bins[rows, node.feature]
            go_left = np.where(b >= n_bins, node.na_left,
                               node.left_bins[np.minimum(b, n_bins - 1)])
            node.left, node.right = Node(), Node()
            nxt.append((node.left, rows[go_left]))
            nxt.append((node.right, rows[~go_left]))
        level = nxt
    return root


def fit(X: np.ndarray, y: np.ndarray, *, cat_cards, ntrees: int,
        max_depth: int, nbins: int, learn_rate: float, min_rows: float,
        nbins_cats: int = 1024, reg_lambda: float = 0.0, gamma: float = 0.0,
        min_split_improvement: float = 1e-5, reg_alpha: float = 0.0,
        edges: list | None = None) -> Model:
    """``X`` [rows, F]: a categorical column holds its level codes, NaN is
    missing; ``cat_cards`` [F] its cardinality, 0 for a numeric column.
    ``edges`` (per feature, None for a categorical one) takes the place of
    the sample's own quantiles: the same bins as a model under test."""
    if reg_alpha:
        raise ValueError("the reference has no L1 term")
    cat_cards = np.asarray(cat_cards, np.int64)
    is_cat = cat_cards > 0
    y = np.asarray(y, np.float64)
    if edges is None:
        edges = bin_edges(X, nbins, cat_cards)
    n_bins = engine_bins(nbins, cat_cards, nbins_cats)
    bins = bin_features(X, edges, cat_cards, nbins_cats, n_bins)
    ybar = min(max(float(y.mean()), 1e-6), 1 - 1e-6)
    f0 = float(np.log(ybar / (1 - ybar)))
    F = np.full(len(y), f0)
    trees = []
    for _ in range(ntrees):
        p = 1.0 / (1.0 + np.exp(-F))
        g, h = p - y, np.maximum(p * (1 - p), 1e-10)
        tree = grow_tree(bins, g, h, is_cat, max_depth=max_depth,
                         n_bins=n_bins, min_rows=min_rows, lam=reg_lambda,
                         gamma=gamma,
                         min_split_improvement=min_split_improvement)
        trees.append(tree)
        F += learn_rate * predict_tree(tree, bins, n_bins)
    return Model(edges, cat_cards, nbins_cats, n_bins, f0, learn_rate, trees)
