"""The last model of the window against the configuration's plain reference
and against the data's own generating score, on held-out rows.

Passes when the model's held-out AUC is no more than ``TOLERANCE`` below
that of the reference (trained with the same parameters on a seeded sample
the size of ``reference_rows``) and not above the AUC of the score the
response was drawn from, which no model can beat but by chance. The
tolerance and its reason are written in reference/gbm_numpy.py.
"""

from __future__ import annotations

TOLERANCE = 0.002
#: the builder's parameters the reference takes, by the builder's own names
REFERENCE_PARAMS = ("ntrees", "max_depth", "nbins", "learn_rate", "min_rows",
                    "reg_lambda", "reg_alpha", "gamma",
                    "min_split_improvement")


def check(ctx) -> dict:
    from benchmark import datagen, plugins
    from benchmark.reference.auc import auc

    cell, data = ctx.cell, ctx.data
    generator = plugins.load("generators", data["generator"])
    ideal_score = getattr(generator, "ideal_score", None)
    response = data["response"]

    def sample(fold: int, key: str):
        rows = cell.size(ctx.traffic, key)
        return generator.make(cell.seed, fold, dict(data, rows=rows))

    held = sample(2, "heldout_rows")
    X_held, y_held = datagen.to_host(held, response)
    pred = ctx.model.predict(held)
    p_model = pred.vecs[-1].to_numpy()[: held.nrows]

    X_ref, y_ref = datagen.to_host(sample(1, "reference_rows"), response)
    reference = plugins.load("reference", ctx.config["reference"])
    params = {k: ctx.params[k] for k in REFERENCE_PARAMS if k in ctx.params}
    ref_model = reference.fit(X_ref, y_ref, **params)

    got = auc(y_held, p_model)
    want = auc(y_held, ref_model.predict_proba(X_held))
    ceiling = auc(y_held, ideal_score(X_held.T)) if ideal_score else 1.0
    return {"ok": bool(want - TOLERANCE <= got <= ceiling),
            "auc": got, "reference_auc": want, "generating_score_auc": ceiling,
            "tolerance": TOLERANCE}
