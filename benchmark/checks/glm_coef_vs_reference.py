"""The last model of the window against a float64 IRLS of the same rows
(``reference/glm_irls_numpy.py``: no jitter, the same stopping rule), on the
WHOLE training frame: coefficients, de-standardised, and residual deviance.

Three limits, each with its reason and its readings; ``ok`` needs all three.
The readings are my chip runs of PR 26 at the cell's 2M rows, through this
file (calls 12 and 14, PERF.md section 6): "float32" is the program as
committed, fifteen seeds; the controls are scratch copies of ``models/glm.py``, one seed
each: "bf16" with the inputs of ``eta``'s product cast to bfloat16 (and, in a
second copy, the Gram's and the right-hand side's too), "the parent's
right-hand side" with ``[X,1]'W z_working`` solved for the new coefficients
themselves, in float32 throughout, as the parent commit does.

- ``RIDGE_EXCESS``: the tight one. The model's coefficients against the
  program's OWN fixed point worked in float64 (``_glm.programs_ridge``: IRLS
  with the ridge ``glm._irls_step`` puts on the diagonal, 1e-5 x the mean
  diagonal), as the excess of the ridge-penalised deviance, i.e. the squared
  distance in the norm of ``X'WX + jI``. Nothing but the program's own
  arithmetic is in it: float32 reads 1.5e-7 to 2.0e-7. The parent's
  right-hand side, a difference of two float32 sums of 1e5, reads 1.04e-4
  (and the parent commit itself 1.04e-4): the rounding that this PR's Newton
  step repairs is ten times over the limit. bf16 reads 0.67 and 0.77. The
  limit is fifty times float32's largest reading and a tenth of the
  smallest reading of anything coarser.
- ``EXCESS_DEVIANCE``: the float64 deviance of the MODEL's coefficients less
  the reference's minimum, i.e. the coefficient error's squared length in the
  information matrix's own norm (both are printed; they agree): how far the
  program is from PLAIN IRLS. ALL OF THIS TOLERANCE IS CONSUMED BY THE
  PROGRAM'S RIDGE JITTER (a departure from plain IRLS that the program
  keeps because collinear and separable designs lean on it): the design's
  weak direction (intercept against the 299 Origin columns, the dropped
  level holding 0.07% of the rows: condition number 1e6, smallest
  eigenvalue a tenth of a unit per million rows against a jitter of 0.038)
  moves by 1e-2, which reads 0.0021 to 0.023 here (0.0014 to 0.042 at 4M
  rows over eight seeds, earlier calls). bf16 reads 0.68 and 0.77. The limit
  is five times the largest reading at any size and under a third of bf16.
- ``DEVIANCE_RTOL``: the model's reported residual deviance (the float32 sum
  the loop's last step made, of the iterate that step started from) against
  the reference's. On the v5e it reads 1.00e-5 LOW on every seed, in float32
  and bf16 alike (0.997e-5 to 1.013e-5; bf16 0.74e-5): the chip's float32
  ``log`` in the deviance's terms (mean error of log(1 - mu) 4.4e-6,
  measured apart), not the sum and not the coefficients (the CPU reads
  1e-7). The limit is three times that reading; it would catch a deviance
  summed in bf16, and it is not the limit that tells precisions apart.
- reported, not limited: ``max_se_units``, the largest coefficient difference
  from plain IRLS over that coefficient's standard error (the jitter: 0.04 to
  0.13; bf16 0.65: too close to carry a limit), ``max_se_units_off_ridge``
  (float32 1.7e-5 to 4.1e-5, the parent's right-hand side 9.5e-3) and
  ``max_abs_coef_diff``.
"""

from __future__ import annotations

EXCESS_DEVIANCE = 0.2
RIDGE_EXCESS = 1e-5
DEVIANCE_RTOL = 3e-5


def check(ctx) -> dict:
    import numpy as np

    from benchmark.plugins import load
    glm = load("checks", "_glm")
    ref, design, _domains, y, fit = glm.reference(ctx)
    out = ctx.model.output
    if list(out["coef_names"]) != design.names:
        return {"ok": False, "why": "coefficient names differ"}
    coef = np.asarray(out["coef"], np.float64)
    beta = ref.standardized(coef, design)
    diff = beta - fit.beta
    gram, _rhs, _dev = ref.normal_equations(design.X, y, fit.beta)
    se = np.sqrt(np.diag(np.linalg.inv(gram)))
    excess = ref.deviance(y, ref.eta_of(design, beta)) - fit.deviance
    ridge, j = glm.programs_ridge(ref, design, y, fit.beta)
    off_ridge = beta - ridge
    ridge_excess = float(off_ridge @ gram @ off_ridge + j * off_ridge @ off_ridge)
    in_se = float(np.max(np.abs(diff) / se))
    dev_rel = abs(float(out["residual_deviance"]) - fit.deviance) / fit.deviance
    worst = int(np.argmax(np.abs(diff) / se))
    names = design.names + ["Intercept"]
    return {"ok": bool(excess <= EXCESS_DEVIANCE
                       and ridge_excess <= RIDGE_EXCESS
                       and dev_rel <= DEVIANCE_RTOL),
            "rows": int(design.X.shape[0]), "columns": len(design.names),
            "excess_deviance": float(excess),
            "information_norm_sq": float(diff @ gram @ diff),
            "excess_over_float64_ridge": ridge_excess,
            "max_se_units_off_ridge": float(np.max(np.abs(off_ridge) / se)),
            "ridge": float(j),
            "max_se_units": in_se, "worst": names[worst],
            "max_abs_coef_diff": float(np.max(np.abs(coef - fit.coef))),
            "deviance_rel_diff": float(dev_rel),
            "residual_deviance": float(out["residual_deviance"]),
            "reference_deviance": fit.deviance,
            "iterations": int(out["iterations"]),
            "reference_iterations": fit.iterations,
            "limits": [EXCESS_DEVIANCE, RIDGE_EXCESS, DEVIANCE_RTOL]}
