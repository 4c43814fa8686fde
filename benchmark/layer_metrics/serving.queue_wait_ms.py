"""Median wait of a scoring request from enqueue to the start of its
dispatch, in ms: the window's observations of the program's
``h2o3_score_queue_wait_seconds``, read from its buckets (so as fine as the
buckets are)."""

LAYER, UNIT, MOVES = "serving", "ms", "score_p50_ms"
DRIVERS = ("score_open_loop",)


def read(r):
    from benchmark import counters
    buckets = counters.bucket_deltas(r.before, r.after,
                                     "h2o3_score_queue_wait_seconds")
    q = counters.bucket_quantile(buckets, 0.5)
    return None if q is None else 1e3 * q
