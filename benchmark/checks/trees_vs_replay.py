"""The TIMED build's trees, replayed in float64 on the training rows.

The other checks of ``gbm100-airline-cat-build`` build histograms of their
own (``hist_vs_segment_sum_engine``, ``cat_split_vs_reference``) or walk the
model's trees on held-out rows (``predict_vs_traversal``); this one holds the
last model of the window to the rows it was built on. The training frame is
made again from ``--seed`` (fold 0, the same call the driver makes: the same
rows, bit for bit) and fetched to the host. Then, tree by tree, in numpy
float64 and with no code of ``h2o3_tpu/models/tree.py``:

1. gradients from the replay's OWN margins (``f0`` recomputed from the
   response, then advanced by the replay's own leaf values): g = p - y,
   h = max(p (1 - p), 1e-10);
2. every row walks the tree's recorded splits to the node it rests at
   (``reference/tree_traverse_masked.heap_index``: thresholds, ``left_mask``s
   with a bin a level by the CONFIGURATION's ``nbins_cats``, ``na_left``);
3. (G, H, W) of every node of the heap: summed over the rows resting there,
   and a split node's from its two children;
4. the tree's arrays against them: ``cover`` against W at EVERY node;
   ``leaf`` against -G / (H + lambda) at every node that holds rows and does
   not split; ``gain`` against 1/2 (GL^2/(HL+lambda) + GR^2/(HR+lambda)
   - G^2/(H+lambda)) - gamma at every split, from the children's sums; both
   children of every split hold ``min_rows`` rows;
5. margins += learn_rate x the replay's leaf value of each row's node.

What it catches that no other check does, all in the program that was timed:
a row sent the wrong way at any level (the router's gather side at levels 8
and 9 included: the counts of the nodes below differ), a histogram that is
not the node's (the kernel's passes at 32 to 256 slots, the sibling
subtraction's ``chosen[par]``: ``cover`` and ``gain`` are read off the
histograms the split was chosen on), the last level's totals at 1,024
segments (``leaf`` and ``cover`` there), a build on part of the rows, and
margins that do not move between trees (tree 2's leaves are then tree 1's).

Limits, each with its two readings (PERF.md section 6, PR 30, review round:
my chip runs through this file at 20M rows, the sound program nine times on
nine seeds; the faults are scratch wrappers around ``run.py`` that change ONE
thing in the program, run through this comparison as it stands):

- ``COVER_ULPS``: |cover - W| in units of float32's spacing at W, 1 below
  2^24 rows: a count is exact or wrong. Sound: 0.0 at every one of 2,047
  nodes of every tree, the root's 20M included. One row in a thousand sent
  the other way at the two gather levels: 278 to 320 units, 1,444 to 1,478
  nodes off a tree; every tree grown on the first half of the rows: 4.2e6.
- ``LEAF_ATOL``: |leaf - replayed|, absolute (|g| <= 1 and h is 0.16 or so).
  Sound: 2.7e-3 to 3.6e-3 a run, at leaves of 85,000 to 340,000 rows, NOT
  the 1e-5 that two bf16 digits and the CPU rehearsal give: the last level's
  float32 scatter-adds (``tree._node_totals``) lose three digits on a leaf
  of 1e5 to 1e6 rows whose addends take few values (PERF.md section 6;
  ROADMAP S3 has the cure, measured). Margins that never move: 1.6 and 2.3
  (trees 2 and 3; tree 1 as sound); half the rows: 2.0 to 2.3; the misrouted
  rows: 0.15 to 2.2. Eight times the largest sound reading, a fiftieth of
  the smallest fault's. The kernel's statistics in one bf16 digit read as
  sound, 2.95e-3: these leaves do not come from the kernel.
- ``GAIN_ERR_PER_ROW``: |gain - replayed| over the node's rows, the scale
  ``cat_split_vs_reference`` gives its limits and for its reason. Sound:
  9.0e-6 to 3.9e-5 a run, at nodes of 4,000 to 53,000 rows (1.5e-6 in that
  check at 1M rows: the float32 sums' error grows with the rows). The
  kernel's statistics in one bf16 digit (the nearest precision below): 5.9e-4,
  at nodes of 128 rows; margins that never move 5.0e-2 and 8.1e-2, half the
  rows 4.9e-2 to 8.8e-2. Five times the largest sound reading, a third of
  one digit's.
"""

from __future__ import annotations

import numpy as np

COVER_ULPS = 0.5
LEAF_ATOL = 3e-2
GAIN_ERR_PER_ROW = 2e-4
#: ``f0`` against log(ybar / (1 - ybar)) of the response, one float32 rounding
F0_ATOL = 1e-5
#: rows walked at a time (a chunk's arrays stay in cache; chunks run on the
#: host's cores side by side, numpy's indexing releases the interpreter lock)
CHUNK = 1 << 17
WORKERS = 8


def host_columns(frame, names):
    """[rows, len(names)] float64 of ``frame``'s columns; a categorical
    column holds its level codes, NaN where missing."""
    import jax
    got = jax.device_get([frame.vec(n).data for n in names])
    X = np.empty((frame.nrows, len(names)), np.float64)
    for j, (n, a) in enumerate(zip(names, got)):
        a = np.asarray(a)[: frame.nrows]
        X[:, j] = np.where(a < 0, np.nan, a) if frame.vec(n).is_categorical else a
    return X


def node_sums(tree, idx, g, h):
    """[nodes, 3] float64 (G, H, W) of the rows RESTING at each heap node."""
    n = len(np.asarray(tree.feat))
    return np.stack([np.bincount(idx, g, n), np.bincount(idx, h, n),
                     np.bincount(idx, minlength=n).astype(np.float64)], axis=1)


def heap_totals(tree, resting):
    """(G, H, W) of every node: its own resting rows, and for a split node
    its two children's."""
    tot = resting.copy()
    is_split = np.asarray(tree.is_split)
    for i in range(len(tot) - 1, -1, -1):
        if is_split[i]:
            tot[i] += tot[2 * i + 1] + tot[2 * i + 2]
    return tot


def compare(tree, tot, lam: float, gamma: float, min_rows: float) -> dict:
    """One tree's ``cover``, ``leaf`` and ``gain`` against the replay's node
    sums ``tot`` [nodes, 3]."""
    G, H, W = tot.T
    is_split = np.asarray(tree.is_split)
    cover = np.asarray(tree.cover, np.float64)
    cover_ulps = np.abs(cover - W) / np.maximum(1.0, W * 2.0 ** -23)

    with np.errstate(divide="ignore", invalid="ignore"):
        value = np.where(W > 0, -G / (H + lam), 0.0)
        half = G * G / (H + lam)
    at_leaf = ~is_split & (W > 0)
    leaf_err = np.abs(np.asarray(tree.leaf, np.float64) - value)[at_leaf]

    par = np.flatnonzero(is_split)
    left, right = 2 * par + 1, 2 * par + 2
    want = 0.5 * (half[left] + half[right] - half[par]) - gamma
    empty = np.minimum(W[left], W[right]) <= 0      # no split in the replay
    gain_err = np.where(empty, 0.0, np.abs(
        np.asarray(tree.gain, np.float64)[par] - want) / np.maximum(W[par], 1))
    at = np.flatnonzero(at_leaf)
    return {"value": value,
            "cover_ulps": float(cover_ulps.max()),
            "nodes_cover_off": int((cover_ulps > COVER_ULPS).sum()),
            "leaf_err": float(leaf_err.max(initial=0.0)),
            "leaf_err_rows": int(W[at[leaf_err.argmax()]]) if len(at) else 0,
            "leaves_off": int((leaf_err > LEAF_ATOL).sum()),
            "gain_err_per_row": float(gain_err.max(initial=0.0)),
            "gain_err_rows": int(W[par[gain_err.argmax()]]) if len(par) else 0,
            "splits": int(len(par)), "leaves": int(len(at)),
            "children_under_min_rows": int(
                (np.minimum(W[left], W[right]) < min_rows).sum())}


def replay(out: dict, X, y, nbins_cats: int, lam: float, gamma: float,
           min_rows: float):
    """``(f0, [per tree: compare()'s readings])`` of the model ``out`` on its
    training rows ``X`` [rows, F] float64 (level codes, NaN missing) and
    ``y`` [rows] in {0, 1}."""
    from concurrent.futures import ThreadPoolExecutor

    from benchmark.reference.tree_traverse_masked import heap_index

    rows = len(y)
    cat_card = np.asarray(out["cat_card"], np.int64)
    lr = float(out["learn_rate"])
    ybar = min(max(float(y.mean()), 1e-6), 1 - 1e-6)
    f0 = float(np.log(ybar / (1 - ybar)))
    margin = np.full(rows, f0)
    idx = np.empty(rows, np.int64)
    spans = [slice(a, min(a + CHUNK, rows)) for a in range(0, rows, CHUNK)]

    trees = []
    with ThreadPoolExecutor(WORKERS) as pool:
        for tree in out["trees"]:
            def walk(s, tree=tree):
                p = 1.0 / (1.0 + np.exp(-margin[s]))
                idx[s] = heap_index(tree, X[s], cat_card, nbins_cats)
                return node_sums(tree, idx[s], p - y[s],
                                 np.maximum(p * (1 - p), 1e-10))
            tot = heap_totals(tree, sum(pool.map(walk, spans)))
            got = compare(tree, tot, lam, gamma, min_rows)
            margin += lr * got.pop("value")[idx]
            trees.append(got)
    return f0, trees


def verdict(out: dict, f0: float, trees: list) -> dict:
    def worst(key):
        return max(t[key] for t in trees)

    counts = {k: sum(t[k] for t in trees) for k in (
        "nodes_cover_off", "leaves_off", "children_under_min_rows")}
    f0_err = abs(float(out["f0"]) - f0)
    ok = (worst("cover_ulps") <= COVER_ULPS and worst("leaf_err") <= LEAF_ATOL
          and worst("gain_err_per_row") <= GAIN_ERR_PER_ROW
          and counts["children_under_min_rows"] == 0 and f0_err <= F0_ATOL)
    return {"ok": bool(ok), "trees": len(trees),
            "cover_ulps": worst("cover_ulps"), "leaf_err": worst("leaf_err"),
            "gain_err_per_row": worst("gain_err_per_row"), "f0_err": f0_err,
            **counts, "by_tree": trees,
            "limits": [COVER_ULPS, LEAF_ATOL, GAIN_ERR_PER_ROW]}


def check(ctx) -> dict:
    from benchmark import plugins

    out, params = ctx.model.output, ctx.params
    generator = plugins.load("generators", ctx.data["generator"])
    frame = generator.make(ctx.cell.seed, 0, ctx.data)
    X = host_columns(frame, list(out["x_cols"]))
    y = host_columns(frame, [ctx.data["response"]])[:, 0]
    del frame
    f0, trees = replay(out, X, y, int(params["nbins_cats"]),
                       float(params.get("reg_lambda", 0.0)),
                       float(params.get("gamma", 0.0)),
                       float(params["min_rows"]))
    return dict(verdict(out, f0, trees), rows=len(y))
