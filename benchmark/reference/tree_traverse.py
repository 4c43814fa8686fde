"""A plain numpy traversal of a finished tree model — what ``/3/Score`` and
``model.predict`` are held to in the serving cell.

A tree is a dense heap (children of node i at 2i+1 and 2i+2): at a split a
row goes left when ``x[feat] < thresh_val`` (a missing value goes where
``na_left`` says). The model's margin is ``f0 + learn_rate * sum of leaves``
and, for a bernoulli model, the probability of the second class is its
logistic. float64 throughout; the tolerance it is compared under, and why, is
``ATOL_VS_TRAVERSAL`` in drivers/score_open_loop.py."""

from __future__ import annotations

import numpy as np


def leaves(tree, X: np.ndarray) -> np.ndarray:
    feat = np.asarray(tree.feat)
    thresh = np.asarray(tree.thresh_val, np.float64)
    na_left = np.asarray(tree.na_left)
    is_split = np.asarray(tree.is_split)
    leaf = np.asarray(tree.leaf, np.float64)
    depth = int(np.log2(len(feat) + 1)) - 1
    rows = np.arange(len(X))
    idx = np.zeros(len(X), np.int64)
    for _ in range(depth):
        x = X[rows, np.maximum(feat[idx], 0)]
        left = np.where(np.isnan(x), na_left[idx], x < thresh[idx])
        idx = np.where(is_split[idx], 2 * idx + np.where(left, 1, 2), idx)
    return leaf[idx]


def bernoulli_p1(model, X: np.ndarray) -> np.ndarray:
    X = np.asarray(X, np.float32).astype(np.float64)
    out = model.output
    margin = np.full(len(X), float(out["f0"]))
    for tree in out["trees"]:
        margin += float(out["learn_rate"]) * leaves(tree, X)
    return 1.0 / (1.0 + np.exp(-margin))
