"""Share of the rows the scorer computed in the window that were bucket
padding, in percent: 1 - rows scored / bucket rows dispatched.

Rows scored is the sum of the program's ``h2o3_score_batch_size`` (rows fused
into one dispatch); its buckets are the powers of two, and a dispatch of n
rows runs in the scorer bucket ``max(8, next power of two >= n)``
(``serving/scorer.bucket_for``), so a histogram bucket's upper bound is the
padded size of every dispatch counted in it."""

LAYER, UNIT, MOVES = "serving", "%", "score_p50_ms"
DRIVERS = ("score_open_loop",)
MIN_BUCKET = 8


def read(r):
    from benchmark import counters
    scored = counters.delta(r.before, r.after, "h2o3_score_batch_size_sum")
    buckets = counters.bucket_deltas(r.before, r.after,
                                     "h2o3_score_batch_size")
    finite = [le for le, _ in buckets if le != float("inf")]
    if not finite or scored <= 0:
        return None
    padded = sum(c * max(MIN_BUCKET, min(le, max(finite)))
                 for le, c in buckets)
    return 100.0 * (1.0 - scored / padded) if padded > 0 else None
