"""Mean rows fused into one scoring dispatch in the window
(``h2o3_score_batch_size`` sum over count): how much the micro-batcher
coalesces at this rate."""

LAYER, UNIT, MOVES = "serving", "rows", "score_p99_ms"
DRIVERS = ("score_open_loop",)


def read(r):
    from benchmark import counters
    n = counters.delta(r.before, r.after, "h2o3_score_batch_size_count")
    s = counters.delta(r.before, r.after, "h2o3_score_batch_size_sum")
    return s / n if n > 0 else None
