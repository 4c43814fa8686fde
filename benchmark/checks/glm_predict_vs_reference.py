"""``model.predict`` on held-out rows (fold 2, nothing trains on them)
against the float64 reference's coefficients on the same rows, as LINEAR
PREDICTORS: logit of the model's P(Y) against X·beta_ref. Classes and
probabilities hide a difference; the margin does not.

The held-out frame is written with every categorical domain in REVERSE order
(``domain_order`` of the generator), so ``predict`` has to adapt it to the
training layout by level name (``DataInfo.expand`` -> ``_remap_codes``); the
reference matches the names itself, on the host.

Limits (my chip runs of PR 26 at the cell's 2M training rows, through this
file, calls 12 and 14, PERF.md section 6: float32 is the program as committed,
fifteen seeds; bf16 a scratch copy with the inputs of ``eta``'s product cast to
bfloat16, one seed; 200,000 held-out rows):

- ``ETA_RMS``: root mean square difference over the rows. The program's
  ridge jitter (see ``glm_coef_vs_reference``) consumes most of it: 8e-5 to
  2.7e-4; float32 itself (one float32 product a row, the logit of a float32
  probability, the coefficients' rounding) reads 1.4e-6 (a scratch version
  whose jitter was proximal and could not bias, earlier calls). bf16 inputs
  round the standardised numerics and every coefficient to 8 bits and read
  1.7e-3 and 1.9e-3 (2.7e-3 in the earlier calls' copy). The limit is just
  under three times the largest reading and under half of bf16's smallest.
- reported, not limited: ``eta_max_diff`` (the jitter: 3.0e-3 to 1.0e-2, on the
  rows of the rare dropped Origin level; bf16 9.3e-3: no room for a limit).
- the held-out AUC may not pass that of the score the response was drawn
  from by more than 1e-3 (read: 3e-4 to 5e-4 UNDER it; a model that beats
  the generating score has seen the held-out rows).
"""

from __future__ import annotations

ETA_RMS = 8e-4
AUC_CHANCE = 1e-3


def check(ctx) -> dict:
    import numpy as np

    from benchmark.plugins import load
    from benchmark.reference.auc import auc
    glm = load("checks", "_glm")
    ref, design, domains, _y, fit = glm.reference(ctx)
    cell, data = ctx.cell, ctx.data
    gen = glm.generator(ctx)
    rows = cell.size(ctx.traffic, "heldout_rows")
    held = gen.make(cell.seed, 2, dict(data, rows=rows,
                                       levels_for_rows=data["rows"],
                                       domain_order="reversed"))
    pred = ctx.model.predict(held)
    p1 = np.asarray(pred.vecs[-1].to_numpy()[:rows], np.float64)
    eta_model = np.log(p1) - np.log1p(-p1)
    held_design, _doms, y_held = ref.from_frame(
        held, data["response"], like=(design, domains),
        standardize=bool(ctx.params["standardize"]))
    eta_ref = ref.eta_of(held_design, fit.beta)
    diff = eta_model - eta_ref
    rms, worst = float(np.sqrt(np.mean(diff ** 2))), float(np.max(np.abs(diff)))
    got = auc(y_held, eta_model)
    ideal = _ideal_auc(gen, held, data, y_held)
    return {"ok": bool(rms <= ETA_RMS and got <= ideal + AUC_CHANCE),
            "rows": int(rows), "eta_rms_diff": rms, "eta_max_diff": worst,
            "auc": got, "reference_auc": auc(y_held, eta_ref),
            "generating_score_auc": ideal,
            "limits": [ETA_RMS, AUC_CHANCE]}


def _ideal_auc(gen, held, data, y_held) -> float:
    """AUC of the score the response was drawn from, on the held-out rows in
    the generator's own level order (the reversal undone)."""
    import numpy as np

    from benchmark.reference.auc import auc
    cards = gen.cardinalities(data["rows"])
    cols = []
    for name, card in zip(gen.NAMES, list(cards) + [None, None]):
        a = held.vec(name).to_numpy()[: held.nrows]
        # undo the reversed domain: the generator's own code of a level
        cols.append(a if card is None else card - 1 - a.astype(np.int64))
    return auc(y_held, gen.ideal_score([np.asarray(c) for c in cols], cards))
