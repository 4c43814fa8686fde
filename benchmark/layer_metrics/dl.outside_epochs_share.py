"""Share of ``train()``'s wall that is NOT inside the epochs: the roll-ups
and the design matrix's expansion, the initial weights, training metrics (a
second expansion and a scoring pass), the model's assembly, the Job and the
DKV. 1 - sum of the program's ``deeplearning:epochs`` spans / sum of the
benchmark's ``bench:train`` spans, in percent, over the window's builds. Both
are host spans on the trace's clock; the epochs' span ends with the fetch of
the loss series, so it is a synced time."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "builder", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    scopes = load("layer_metrics", "_scopes")
    epochs = scopes.program_spans(r, "epochs")
    if not epochs:
        return None
    train = [(a, min(b, r.trace.t1)) for a, b in r.trace.spans("bench:train")
             if r.trace.t0 <= a < r.trace.t1]
    total = sum(b - a for a, b in train)
    if total <= 0:
        return None
    return 100.0 * (1.0 - sum(b - a for a, b in epochs) / total)
