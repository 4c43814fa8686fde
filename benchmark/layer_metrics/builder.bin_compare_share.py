"""Share of the columns the builders binned in the window that went through
compare-and-count (``ops/quantile.py``: the count of edges <= x by fused
broadcast compares, no gather): ``h2o3_bin_columns_total{path="compare"}``
over every ``path`` of that counter, in percent. The program counts one a
numeric column binned; a categorical column takes its level code and is not
counted. 100 where every column took the compare path; a program without
the counter (PR 25's parent) leaves the metric out."""

LAYER, UNIT, MOVES = "builder", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    total = counters.delta(r.before, r.after, "h2o3_bin_columns_total")
    if total <= 0:
        return None
    compare = counters.delta(r.before, r.after, "h2o3_bin_columns_total",
                             path="compare")
    return 100.0 * compare / total
