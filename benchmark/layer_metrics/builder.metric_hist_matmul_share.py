"""Share of the binomial metric passes the program traced in this process
whose 400-bucket score histogram is a blocked one-hot product on the MXU
(``metrics._bucket_sums``) and not scatter-adds:
``h2o3_metric_hist_total{path="matmul"}`` over every ``path`` of that
counter, in percent. The counter moves when ``metrics._binomial_pass`` is
TRACED, which happens in the warm-up build, so its ABSOLUTE value at the
window's end is read (a delta over the window is 0). A program without the
counter (PR 34's parent) leaves the metric out."""

LAYER, UNIT, MOVES = "builder", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    total = counters.value(r.after, "h2o3_metric_hist_total")
    if total <= 0:
        return None
    matmul = counters.value(r.after, "h2o3_metric_hist_total", path="matmul")
    return 100.0 * matmul / total
