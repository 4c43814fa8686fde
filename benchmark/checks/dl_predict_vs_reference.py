"""``model.predict`` on held-out rows (fold 2) against the plain reference's
forward pass (``reference/dl_mlp_jnp.py``, float32, every product at HIGHEST)
from the model's OWN fetched weights and the reference's own non-constant
columns, means and deviations: class probabilities, row by row.

The held-out frame still has its 67 constant columns (``predict`` has to
leave them out by name) and its response domain is written REVERSED (so the
predicted label has to be a level NAME, not a code).

Limits (my chip runs of PR 32 at the cell's 1M rows, through this file, call
1: the committed program on seeds 2718281828 / 3141592653 / 2222222222; the
fault through a scratch wrapper on the last seed's model; 200,000 rows; PERF.md
section 6 has the table):

- ``P_RMS``: root mean square difference of the ten probabilities over the
  rows. The program's products round their operands to bfloat16 and sum in
  float32 (the configuration states it); the reference's do not: 1.95e-4 to
  2.17e-4 over nine seeds (the same scoring pass with its products at HIGHEST reads
  3.8e-6). A pass that ALSO keeps its activations and logits in bfloat16
  between layers (``lax.reduce_precision`` after every layer) reads 7.8e-4.
  The limit is 1.85 times the largest sound reading and half the fault's.
- ``LABEL_MISMATCH``: the share of rows whose predicted label is not the
  reference's most probable class (near ties fall either way): 2.8e-4 to
  4.7e-4 sound, 1.9e-3 with bfloat16 activations; two rows of grace for the
  rehearsal's 2,048. And every label has to be a NAME of the response's
  domain, whatever the frame's codes.
- reported, not limited: ``p_max_diff`` (one row's worst: 9e-3 sound, 2.9e-2
  with bfloat16 activations; one row is too few to carry a limit).
"""

from __future__ import annotations

P_RMS = 4e-4
LABEL_MISMATCH = 1e-3


def check(ctx) -> dict:
    import jax
    import numpy as np

    from benchmark.plugins import load
    d = load("checks", "_dl")
    ref = d.reference_module(ctx)
    held = d.heldout(ctx)
    theta = jax.device_get(ctx.model.output["params"])
    want = np.asarray(d.reference_proba(ref, theta, held.pixels,
                                        d.training_rows(ctx)), np.float64)
    diff = np.asarray(held.proba, np.float64) - want
    rms = float(np.sqrt(np.mean(diff ** 2)))
    mismatch = float(np.mean(held.predicted != want.argmax(axis=1)))
    dropped = tuple(ctx.model.data_info.ignored_const_cols)
    gen = d.generator(ctx)
    return {"ok": bool(rms <= P_RMS
                       and mismatch <= LABEL_MISMATCH + 2.0 / len(want)
                       and len(dropped) == len(gen.CONSTANT_PIXELS)),
            "rows": int(want.shape[0]), "p_rms_diff": rms,
            "p_max_diff": float(np.max(np.abs(diff))),
            "label_mismatch_share": mismatch,
            "constant_columns_left_out": len(dropped),
            "limits": [P_RMS, LABEL_MISMATCH]}
