"""``model.predict`` on held-out rows (fold 2) against a plain numpy
traversal of the model's own fetched trees with their ``left_mask``s
(``reference/tree_traverse_masked.py``).

The held-out frame is written with every categorical domain in REVERSE order
(``domain_order`` of the generator), so ``predict`` has to adapt it to the
training layout by level name (``gbm.tree_matrix`` -> ``_remap_codes``); the
reference matches the names itself, on the host, and walks the trees with
the TRAINING codes: level code -> bin -> the node's mask, the bin by the
CONFIGURATION's rule (a bin a level up to ``params.nbins_cats``), not by what
the model says of itself: a program that range-groups the 300 airports into
``nbins`` bins (PR 30's parent) scores through other bins than the rule's
and fails here (0.11 on the v5e, 195,217 of 200,000 rows past the limit).

Limit: the probabilities of the second class agree to ``P1_ATOL`` = 1e-5.
Both sum the same few float32 leaf values (the program in float32, the
reference in float64) and take one logistic, the chip's own: 8.0e-7 to
1.02e-6 on the v5e (PERF.md section 6, PR 30: fourteen runs, eleven seeds; 4.9e-8
on the CPU, so it is the TPU's exponential, not the sums). The nearest
precision below, the traversal's leaf values rounded to bfloat16, reads
4.3e-4 with 190,054 of 200,000 rows past the limit; a row sent to another
leaf differs by that leaf's value times ``learn_rate`` times p(1-p), 1e-4 or
more for nearly every pair of leaves. Ten times the largest reading, a
fortieth of bf16's. Reported beside it: the rows further than the limit, the
held-out AUC, and the leaf sums' own largest difference (the margin less
``f0`` over ``learn_rate``, which float32's logit inflates where p is near 0
or 1: 4.9e-5).
"""

from __future__ import annotations

P1_ATOL = 1e-5


def check(ctx) -> dict:
    import numpy as np

    from benchmark import plugins
    from benchmark.reference import tree_traverse_masked as walk
    from benchmark.reference.auc import auc

    cell, data, out = ctx.cell, ctx.data, ctx.model.output
    generator = plugins.load("generators", data["generator"])
    rows = cell.size(ctx.traffic, "heldout_rows")
    held = generator.make(cell.seed, 2, dict(
        data, rows=rows, levels_for_rows=data["rows"],
        domain_order="reversed"))
    pred = ctx.model.predict(held)
    p1 = np.asarray(pred.vecs[-1].to_numpy()[:rows], np.float64)

    cols = []
    for name in out["x_cols"]:
        vec = held.vec(name)
        a = np.asarray(vec.data)[:rows]
        if vec.is_categorical:
            # by level name, into the training domain's codes
            train = {lvl: j for j, lvl in enumerate(out["feat_domains"][name])}
            to_train = np.array([train.get(lvl, -1) for lvl in vec.domain])
            code = np.where(a >= 0, to_train[np.maximum(a, 0)], -1)
            a = np.where(code >= 0, code, np.nan)
        cols.append(np.asarray(a, np.float64))
    X = np.stack(cols, axis=1)
    nbins_cats = int(ctx.params["nbins_cats"])
    want = walk.bernoulli_p1(ctx.model, X, nbins_cats)
    y = np.asarray(held.vec(data["response"]).data)[:rows]

    diff = np.abs(p1 - want)
    f0, lr = float(out["f0"]), float(out["learn_rate"])
    p1c = np.clip(p1, 1e-12, 1 - 1e-12)
    leaf_sum = (np.log(p1c) - np.log1p(-p1c) - f0) / lr
    return {"ok": bool(diff.max() <= P1_ATOL), "rows": int(rows),
            "p1_max_diff": float(diff.max()),
            "rows_past_limit": int((diff > P1_ATOL).sum()),
            "leaf_sum_max_diff": float(np.abs(
                leaf_sum - walk.leaf_sum(ctx.model, X, nbins_cats)).max()),
            "trees": len(out["trees"]), "auc": auc(y, p1), "limit": P1_ATOL}
