"""A plain numpy traversal of a finished tree model WITH GROUP SPLITS — what
``model.predict`` is held to in ``gbm100-airline-cat-build``
(``tree_traverse.py`` is the same walk for thresholds alone and knows no
``left_mask``).

A tree is a dense heap (children of node i at 2i+1 and 2i+2). At a split on
a NUMERIC feature a row goes left when ``x < thresh_val``; on a CATEGORICAL
feature (cardinality > 0 in the model's ``cat_card``) when the node's
``left_mask`` holds the bin of its level code: the code itself up to the
model's ``cat_bins`` (the builder's ``nbins_cats``) levels, ``code * cat_bins
// cardinality`` past that. A missing value (NaN) goes where ``na_left``
says. The margin is ``f0 + learn_rate * sum of leaves``; float64 throughout.
"""

from __future__ import annotations

import numpy as np


def heap_index(tree, X: np.ndarray, cat_card: np.ndarray, cat_bins: int,
               levels: int | None = None) -> np.ndarray:
    """Each row's heap index after ``levels`` steps down ``tree`` (the whole
    depth by default); a row stays at a node that does not split."""
    feat = np.asarray(tree.feat)
    thresh = np.asarray(tree.thresh_val, np.float64)
    na_left = np.asarray(tree.na_left)
    is_split = np.asarray(tree.is_split)
    mask = np.asarray(tree.left_mask)
    depth = int(np.log2(len(feat) + 1)) - 1
    rows = np.arange(len(X))
    idx = np.zeros(len(X), np.int64)
    for _ in range(depth if levels is None else levels):
        f = np.maximum(feat[idx], 0)
        x = X[rows, f]
        card = cat_card[f]
        code = np.where(np.isnan(x), 0, x).astype(np.int64)
        b = np.where(card > cat_bins, code * cat_bins // np.maximum(card, 1),
                     code)
        in_mask = mask[idx, np.clip(b, 0, mask.shape[1] - 1)]
        left = np.where(np.isnan(x), na_left[idx],
                        np.where(card > 0, in_mask, x < thresh[idx]))
        idx = np.where(is_split[idx], 2 * idx + np.where(left, 1, 2), idx)
    return idx


def leaf_sum(model, X: np.ndarray, nbins_cats: int | None = None) -> np.ndarray:
    """Sum over the model's trees of each row's leaf value, float64.
    ``nbins_cats``: the CONFIGURATION's, where the caller holds the model to
    it (a level has a bin of its own up to that many); the model's own
    ``cat_bins`` by default."""
    out = model.output
    X = np.asarray(X, np.float32).astype(np.float64)
    cat_card = np.asarray(out["cat_card"], np.int64)
    cat_bins = int(out["cat_bins"] if nbins_cats is None else nbins_cats)
    total = np.zeros(len(X))
    for tree in out["trees"]:
        leaf = np.asarray(tree.leaf, np.float64)
        total += leaf[heap_index(tree, X, cat_card, cat_bins)]
    return total


def bernoulli_p1(model, X: np.ndarray, nbins_cats: int | None = None) -> np.ndarray:
    out = model.output
    margin = float(out["f0"]) + float(out["learn_rate"]) * leaf_sum(
        model, X, nbins_cats)
    return 1.0 / (1.0 + np.exp(-margin))
