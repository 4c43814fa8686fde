"""``auc_vs_reference``'s rule for a model with categorical columns: the
last model of the window against the configuration's plain reference
(``reference/gbm_cat_numpy.py``, which has to be told which columns are
categorical and how many bins a level may take: ``auc_vs_reference`` hands a
reference neither) and against the data's own generating score, on held-out
rows.

Passes when the model's held-out AUC is no more than ``TOLERANCE`` below
that of the reference (trained with the same parameters on a seeded sample
of ``reference_rows`` rows) and not above the AUC of the score the response
was drawn from. The tolerance and what it catches are in
reference/gbm_numpy.py: the two differ by sampling, not by method, and both
are scored on the same held-out rows. The held-out frame and the sample
take the TRAINING frame's cardinalities (``levels_for_rows``).

What it does NOT hold in ``gbm100-airline-cat-build`` (PERF.md section 7
(p)): the reference, trained on 200,000 rows at depth 10 and ``min_rows`` 10,
overfits to 0.654-0.657 held-out AUC where the model on 20M rows reads
0.698-0.703, so the lower limit is 0.04 away and has no second reading: a
model of 100 bins (PR 30's parent, 0.693-0.696), a build on half the rows or
one whose margins never move pass it. It is kept as the harness's standing
rule (the ceiling, the generating score's AUC, is 0.003-0.006 above the
model and does bind); what proves the timed model is
``trees_vs_replay``, and ``predict_vs_traversal`` for scoring.
"""

from __future__ import annotations

TOLERANCE = 0.002
#: the builder's parameters the reference takes, by the builder's own names
REFERENCE_PARAMS = ("ntrees", "max_depth", "nbins", "nbins_cats",
                    "learn_rate", "min_rows", "reg_lambda", "gamma",
                    "min_split_improvement")


def check(ctx) -> dict:
    import numpy as np

    from benchmark import datagen, plugins
    from benchmark.reference.auc import auc

    cell, data = ctx.cell, ctx.data
    generator = plugins.load("generators", data["generator"])
    response = data["response"]
    cards = generator.cardinalities(data["rows"])

    def sample(fold: int, key: str):
        rows = cell.size(ctx.traffic, key)
        return generator.make(cell.seed, fold, dict(
            data, rows=rows, levels_for_rows=data["rows"]))

    held = sample(2, "heldout_rows")
    names = [n for n in held.names if n != response]
    cat_cards = [len(held.vec(n).domain) if held.vec(n).is_categorical else 0
                 for n in names]
    X_held, y_held = datagen.to_host(held, response)
    pred = ctx.model.predict(held)
    p_model = pred.vecs[-1].to_numpy()[: held.nrows]

    X_ref, y_ref = datagen.to_host(sample(1, "reference_rows"), response)
    reference = plugins.load("reference", ctx.config["reference"])
    params = {k: ctx.params[k] for k in REFERENCE_PARAMS if k in ctx.params}
    ref_model = reference.fit(X_ref.astype(np.float64), y_ref,
                              cat_cards=cat_cards, **params)

    got = auc(y_held, p_model)
    want = auc(y_held, ref_model.predict_proba(X_held.astype(np.float64)))
    ceiling = auc(y_held, generator.ideal_score(list(X_held.T), cards))
    return {"ok": bool(want - TOLERANCE <= got <= ceiling),
            "auc": got, "reference_auc": want, "generating_score_auc": ceiling,
            "tolerance": TOLERANCE, "reference_bins": int(ref_model.n_bins)}
