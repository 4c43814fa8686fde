"""The split search (``tree._find_splits``, group splits and thresholds) at
the cell's deepest histogram level, against the float64 sorted-prefix
optimum of ``reference/gbm_cat_numpy.py``.

``hist_check_rows`` rows drawn as the timed frame is (fold 0, the training
frame's cardinalities) are binned as the model bins them, 3% of the entries
moved to the missing bin (the generator makes none, and the missing bin's
direction is part of the search), and sent down the model's FIRST tree to
the level of 2^(depth-2) nodes (256 at depth 10), with the first round's
gradients (p = sigmoid(f0)). Every node's histogram is then built twice:

- by the PROGRAM, on the path the cell takes (the Pallas kernel on one chip)
  -> ``tree._find_splits`` with the builder's parameters: every node's
  chosen feature, left-membership set, missing direction and reported gain;
- by numpy in float64 (``reference/hist_segment_sum.py``) ->
  ``gbm_cat_numpy.best_split``: the best allowed gain of every node.

A node of W rows passes when, with the chosen set's gain RECOMPUTED in
float64 from the float64 histogram,

- ``loss``: optimum - recomputed <= LOSS_PER_ROW x W. The search found the
  best split but for near-ties that float32 sums decide the other way;
- ``err``: |reported - recomputed| <= ERR_PER_ROW x W. The program's own
  statistics (kernel sums in two bf16 digits, float32 cumulative sums and
  gain arithmetic) are what the split was chosen on;
- both children hold ``min_rows`` rows, and a node has a split in the
  program exactly when the reference finds an allowed one.

Before any of it, the configuration's own guarantee: a categorical column of
c levels has ``min(c, params.nbins_cats)`` bins, so the engine's bin count
(the width of the model's ``edges``) is at least the largest of those. A
program that caps a level's bins at ``nbins`` (PR 30's parent: 300 airports
in 100 bins) builds another model than the configuration states and fails
here, whatever its speed.

Scale: |g| <= 1 and h >= 0.1 or so, hence each of the gain's three terms
G^2/H is at most about 10 W: the limits are per row of the node.

Limits: beside each constant, with its two readings (PERF.md section 6,
PR 30: my chip runs through this file, 256 nodes, 1M rows; the worse
variants are scratch wrappers around ``run.py`` that change one thing).
"""

from __future__ import annotations

import numpy as np

#: the program as committed read 9.4e-7 to 1.9e-6 on the v5e (fourteen runs,
#: eleven seeds, 10M to 40M training rows); the statistics in ONE bf16 digit
#: (the nearest precision below: g and h cast to bfloat16 before the kernel)
#: read 1.08e-3. Five times the largest reading, a hundredth of bf16's
ERR_PER_ROW = 1e-5
#: as committed 0.0 in eleven runs of fourteen, 4.1e-9 at the most (a near tie
#: that float32 decides the other way); the split search without the missing
#: bin's left direction (a dropped direction) read 5.2e-2. One bf16 digit
#: read 0.0 here: it is ``ERR_PER_ROW`` and the histogram check that fail it
LOSS_PER_ROW = 1e-6
NA_SHARE = 0.03


def check(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark import datagen, plugins
    from benchmark.reference import gbm_cat_numpy as ref
    from benchmark.reference.hist_segment_sum import level_histograms
    from benchmark.reference.tree_traverse_masked import heap_index
    from h2o3_tpu.models import tree
    from h2o3_tpu.ops.quantile import bin_dtype
    from h2o3_tpu.parallel.mesh import row_sharding

    cell, data, out = ctx.cell, ctx.data, ctx.model.output
    params = ctx.params
    generator = plugins.load("generators", data["generator"])
    rows = cell.size(ctx.traffic, "hist_check_rows")
    frame = generator.make(cell.seed, 0, dict(
        data, rows=rows, levels_for_rows=data["rows"]))
    X, y = datagen.to_host(frame, data["response"])
    X = X.astype(np.float64)

    edges = np.asarray(out["edges"], np.float64)
    n_bins = edges.shape[1] + 1
    cat_card = np.asarray(out["cat_card"], np.int64)
    cat_bins = int(params["nbins_cats"])      # the configuration's rule
    is_cat = cat_card > 0
    needed = int(np.minimum(cat_card[is_cat], cat_bins).max())
    if n_bins < needed:
        return {"ok": False, "n_bins": int(n_bins), "bins_needed": needed,
                "why": "a categorical column has fewer bins than "
                       "min(levels, nbins_cats): levels share bins"}
    level = max(int(params["max_depth"]) - 2, 0)
    n_nodes = 2 ** level
    min_rows, lam = float(params["min_rows"]), float(params["reg_lambda"])
    gamma = float(params.get("gamma", 0.0))

    bins = ref.bin_features(
        X, [None if c else e[np.isfinite(e)] for c, e in zip(is_cat, edges)],
        cat_card, cat_bins, n_bins)
    rng = np.random.default_rng(cell.seed)
    bins[rng.random(bins.shape) < NA_SHARE] = n_bins
    idx = heap_index(out["trees"][0], X, cat_card, cat_bins, levels=level)
    node = np.where(idx >= n_nodes - 1, idx - (n_nodes - 1), -1).astype(np.int32)
    p = 1.0 / (1.0 + np.exp(-float(out["f0"])))
    g = (p - y).astype(np.float32)
    h = np.full(rows, max(p * (1 - p), 1e-10), np.float32)
    w = np.ones(rows, np.float32)

    d_binned = jax.device_put(bins.astype(np.dtype(bin_dtype(n_bins))),
                              row_sharding(2))
    d_node, d_g, d_h, d_w = (jax.device_put(v, row_sharding(1))
                             for v in (node, g, h, w))
    mesh = tree.hist_mesh(d_binned)
    paths_before = dict(tree.HIST_PATHS)

    @jax.jit
    def search(binned, node, g, h, w):
        hists = tree._histograms(binned, binned.T, node, g, h, w, n_nodes,
                                 n_bins + 1, mesh=mesh)
        found = tree._find_splits(
            hists, n_bins, min_rows, lam, float(params.get("reg_alpha", 0.0)),
            gamma, jnp.ones(binned.shape[1], bool),
            cat_feats=jnp.asarray(is_cat))
        return found[0], found[1], found[3], found[-1]

    gain, feat, na_left, member = (np.asarray(v) for v in search(
        d_binned, d_node, d_g, d_h, d_w))
    path = next((k for k, v in tree.HIST_PATHS.items()
                 if v > paths_before.get(k, 0)), None)

    hist64 = level_histograms(bins, node, g, h, w, n_nodes, n_bins + 1)
    hist64 = hist64.reshape(len(is_cat), n_nodes, n_bins + 1, 3)
    worst_err = worst_loss = 0.0
    searched = group = disagree = small = 0
    for n in range(n_nodes):
        node_hist = hist64[:, n]
        W = node_hist[0, :, 2].sum()
        want = ref.best_split(node_hist, is_cat, min_rows, lam, gamma)
        if (want is not None) != bool(np.isfinite(gain[n])):
            disagree += 1
            continue
        if want is None:
            continue
        searched += 1
        group += int(is_cat[feat[n]])
        true_gain, wl, wr = ref.split_gain(node_hist, int(feat[n]), member[n],
                                           bool(na_left[n]), lam, gamma)
        small += int(min(wl, wr) < min_rows)
        worst_err = max(worst_err, abs(float(gain[n]) - true_gain) / W)
        worst_loss = max(worst_loss, (want[0] - true_gain) / W)
    ok = (disagree == 0 and small == 0 and searched > 0
          and worst_err <= ERR_PER_ROW and worst_loss <= LOSS_PER_ROW)
    return {"ok": bool(ok), "path": path, "rows": int(rows),
            "n_nodes": n_nodes, "n_bins": n_bins,
            "nodes_searched": searched, "group_splits": group,
            "nodes_disagreeing": disagree, "children_under_min_rows": small,
            "gain_err_per_row": worst_err, "gain_loss_per_row": worst_loss,
            "limits": [ERR_PER_ROW, LOSS_PER_ROW]}
