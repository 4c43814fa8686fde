"""The timed program itself — ``_train_epochs`` with the cell's ``cfg``,
widths and minibatch — run for 8 and for 64 updates from the build's own
initial parameters on the training frame's design matrix, against the plain
reference's updates (``reference/dl_mlp_jnp.py``: float32, every product
at HIGHEST, a backward pass written by hand) on the same minibatches under
the same dropout masks: every ``W_i`` and ``b_i``, and ADADELTA's ``E_g`` and
``E_delta`` for each, layer by layer.

An error is the root sum of squares of an array's difference over that of
the CHANGE the reference made to it (``_dl.scaled_errors``, ``"l2"``): a
weight moves by 1e-2 of itself in 64 updates, so an error against the
weight's own size would hide everything; the largest single element over the
largest change is reported beside it and not limited (one rectifier that
falls the other way moves one element a long way). Two counts of updates:
after 8 the two sides differ by their arithmetic alone; by 64 training itself
has magnified what they differed by (a rectifier or a dropped unit that falls
the other way changes a whole gradient; on the CPU, float32 against float32,
seeds read 2e-6 after 8 updates and 3e-2 to 0.4 after 64). ``ok`` needs every
limit.

What is in the sound program's reading, and what this check can and cannot
see (my chip runs of PR 32 at the cell's 1M rows, through this file, call 1,
seeds 2718281828 / 3141592653 / 2222222222, and call 2's six; faults through a scratch wrapper
that changes ONE thing in the program, seed 2222222222; PERF.md section 6 has
the table). The configuration states products from bf16-rounded operands with
float32 sums (one MXU pass; read on the chip: a DEFAULT product agrees with
bf16-rounded operands summed exactly to 8e-8 and is 2.3e-3 of itself off
float64), the reference computes them to float32, and THAT ALONE reads 0.30
after 8 updates: the same program with its products at HIGHEST reads 5e-5. So
at the stated precision this check holds the program to the reference only as
far as a gross fault shows through the products' own noise:

                       after 8 updates               after 64
                       theta   E_g     E_delta       theta   E_g     E_delta
    sound, 9 seeds     0.295-  0.253-  0.177-        0.641-  0.437-  0.357-
                       0.327   0.290   0.196         0.659   0.454   0.380
    no dropout rescale 1.55    0.996   0.811         1.03    0.986   1.13
    rho 0.9            0.800   6.48    0.489         0.840   2.91    0.864
    parameters bf16    0.317   0.262   0.185         0.659   0.445   0.379
    E_g, E_delta bf16  0.301   0.261   0.182         0.653   0.450   0.380
    l1 left out        0.305   0.262   0.183         0.650   0.446   0.355
    products HIGHEST   5.3e-5  2.2e-5  4.1e-5        0.447   0.324   0.219

Each limit stands between the sound program's largest reading and the
smallest of the two faults it can see (no dropout rescale, ``rho`` 0.9).
Parameters or state rounded to bfloat16 (``lax.reduce_precision``: a cast pair
inside a program is removed on the TPU, PERF.md section 6, PR 30) and ``l1``
left out read what the sound program reads: THIS check does not tell them.
``dl_heldout_vs_reference`` fails a build with bfloat16 parameters (log-loss
29% off); bfloat16 state trains as good a model and no check of this cell
tells it; ``l1`` 1e-5 is held on the
CPU only, where both sides are float32 and the same comparison is tight
(``tests/test_dl_reference.py``: 2e-4, ``l1`` left out reads 2e-2). The last
row is why the count of 64 the issue asked for cannot be tight either: by 64
updates training itself has magnified a 5e-5 difference to 0.45.
"""

from __future__ import annotations

#: update counts, and at each the limits on theta, E_g, E_delta
LIMITS = {8: (0.5, 0.5, 0.3), 64: (0.74, 0.7, 0.6)}


def check(ctx) -> dict:
    from benchmark.plugins import load
    d = load("checks", "_dl")
    hp = d.hyper(ctx.params)
    prep = d.prepared(ctx)
    got = {n: d.program_updates(prep, n, hp.B) for n in LIMITS}
    start, _key, _plen = d.start_of(ctx, prep)
    del prep                       # the second design matrix goes
    rows = d.training_rows(ctx)
    out = {"ok": True, "minibatch": hp.B, "inputs": len(rows.kept),
           "limits": {str(n): list(lim) for n, lim in LIMITS.items()}}
    for n, limits in LIMITS.items():
        want_p, want_s = d.reference_at(ctx, n)
        pairs = {"theta": (got[n][0], want_p, start),
                 "e_g": (got[n][1]["Eg"], want_s["Eg"], None),
                 "e_delta": (got[n][1]["Edx"], want_s["Ed"], None)}
        for (what, (a, b, c)), limit in zip(pairs.items(), limits):
            # W_1 .. W_n, then b_1 .. b_n
            by_array = d.scaled_errors(a, b, c, "l2")
            out[f"{what}_err_{n}"] = max(by_array)
            out[f"{what}_err_{n}_by_array"] = by_array
            out[f"{what}_max_err_{n}"] = max(d.scaled_errors(a, b, c, "max"))
            out["ok"] = bool(out["ok"] and max(by_array) <= limit)
    return out
