"""THE LAST MODEL OF THE WINDOW on held-out rows (fold 2, nothing trains on
them): its log-loss and error against those of the plain reference
(``reference/dl_mlp_jnp.py``, float32-highest) trained on the WHOLE training
frame for the same updates, from the build's own initial parameters, on the
same minibatches under the same dropout masks (on the chip, outside the
window; about as long as a build).

Two trajectories of 31,250 noisy updates part (bf16-rounded products against
float32 ones, and every rounding after), so weights are not compared here:
``dl_steps_vs_reference`` does that while they still agree. This check is
statistical: two runs of the same mathematics on the same data end at models
of the same quality, and a build that did something else (fewer rows, no
input dropout, an optimiser that forgets) does not.

Limits (my chip runs of PR 32 at the cell's 1M rows, through this file: the
committed program on THIRTEEN seeds, calls 1 to 3; whole builds with ONE thing
wrong through a scratch wrapper, seed 2222222222, against the same reference
run; 200,000 held-out rows; PERF.md section 6 has the table). The sound model
reads log-loss 0.482-0.496 and error 13.6-14.8% against the reference's
0.481-0.504 and 13.5-14.6% (the ceiling: 0.130, 1.8%):

- ``LOGLOSS_DIFF``: |held-out log-loss of the model - the reference's|, over
  the reference's. Sound 0.05% to 2.3% over the thirteen seeds (either sign). Half
  an epoch 13.4%, no input dropout 7.4% (BETTER: one epoch is short of where
  dropout pays), ADADELTA state reset every update 111%, parameters in
  bfloat16 28.9%. The limit is 2.2 times the largest sound reading and two
  thirds of the nearest fault's. ADADELTA state in bfloat16 reads 0.46%: sound.
- ``ERROR_DIFF``: |held-out error of the model - the reference's|, absolute,
  beside what two models of that error may differ by on this many rows by the
  luck of the sample (``SAMPLE_Z`` binomial standard deviations of a
  difference: 0.0033 at the cell's 200,000 rows, 0.046 at the rehearsal's
  2,048, where the limit would otherwise judge the sample and not the model):
  at the cell's size the sum is 0.0133. Sound 0.0005 to 0.0071 over the
  thirteen seeds (the first three read 0.0027 at most and the limit first
  stood at 0.0053: the fourth seed read 0.0054 and a later one 0.0071, two
  trajectories of 31,250 updates part that far). No input dropout 0.0116, parameters in bfloat16 0.0254,
  half an epoch 0.0274, state reset 0.147: every one of them already fails
  by the log-loss, so this limit is the second line: 1.9 times the largest
  sound reading, half of what bfloat16 parameters or half an epoch read. ADADELTA state in bfloat16 reads 0.0078, inside what sound
  seeds spread over: NO check of this cell tells bfloat16 optimiser state
  (1% increments of a running mean are above bfloat16's 0.4% steps, so
  nothing stalls; the model it trains is as good).
- the ceiling: the held-out log-loss and error may not be under those of the
  model that knows every row's true class (``generators/mnist_like.ceiling``
  on these very rows) by more than ``CEILING_SLACK`` of it: a model that beats
  the generator has seen the held-out rows. After one epoch the model is far
  above it (0.49 against 0.13): this limit guards, it does not discriminate.
"""

from __future__ import annotations

LOGLOSS_DIFF = 0.05
ERROR_DIFF = 0.010
CEILING_SLACK = 0.02
SAMPLE_Z = 3.0


def check(ctx) -> dict:
    from benchmark.plugins import load
    d = load("checks", "_dl")
    ref = d.reference_module(ctx)
    theta, updates = d.reference_trained(ctx)
    held = d.heldout(ctx)
    want = d.reference_proba(ref, theta, held.pixels, d.training_rows(ctx))
    got_ll, got_err = ref.logloss_and_error(held.proba, held.labels)
    ref_ll, ref_err = ref.logloss_and_error(want, held.labels)
    floor_err, floor_ll = held.ceiling
    luck = SAMPLE_Z * (2.0 * ref_err * (1.0 - ref_err)
                       / len(held.labels)) ** 0.5
    ok = (abs(got_ll - ref_ll) <= LOGLOSS_DIFF * ref_ll
          and abs(got_err - ref_err) <= ERROR_DIFF + luck
          and got_ll >= floor_ll * (1.0 - CEILING_SLACK)
          and got_err >= floor_err * (1.0 - CEILING_SLACK))
    return {"ok": bool(ok), "rows": int(held.labels.shape[0]),
            "reference_updates": updates,
            "logloss": got_ll, "reference_logloss": ref_ll,
            "logloss_rel_diff": abs(got_ll - ref_ll) / ref_ll,
            "error": got_err, "reference_error": ref_err,
            "error_diff": abs(got_err - ref_err),
            "ceiling_logloss": floor_ll, "ceiling_error": floor_err,
            "sample_luck": luck,
            "limits": [LOGLOSS_DIFF, ERROR_DIFF, CEILING_SLACK]}
