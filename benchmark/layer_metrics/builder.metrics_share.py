"""Share of ``train()``'s wall spent on the training metrics: the scoring of
the training frame where the fit left no predictions (GLM: a second
expansion and ``_glm_score``) and the metric pass over all rows
(``metrics._binomial_pass``: log-loss, MSE and the 400-bucket score histogram
that AUC, the threshold table and gains/lift are read from). Sum of the
program's ``<algo>:metrics`` spans / sum of the benchmark's ``bench:train``
spans, in percent, over the window's builds. Both are host spans on the
trace's clock; the metrics' span ends with the fetch of the pass's result, so
it is a synced time and nothing of it overlaps the rest of the build."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "builder", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    scopes = load("layer_metrics", "_scopes")
    metrics = scopes.program_spans(r, "metrics")
    if not metrics:
        return None
    train = [(a, min(b, r.trace.t1)) for a, b in r.trace.spans("bench:train")
             if r.trace.t0 <= a < r.trace.t1]
    total = sum(b - a for a, b in train)
    if total <= 0:
        return None
    return 100.0 * sum(b - a for a, b in metrics) / total
