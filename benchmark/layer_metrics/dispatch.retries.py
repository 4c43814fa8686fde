"""Dispatch retries in the window (``h2o3_dispatch_retries_total``, every
site and outcome). Expected 0."""

LAYER, UNIT, MOVES = "dispatch", "count", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    return float(counters.delta(r.before, r.after,
                                "h2o3_dispatch_retries_total"))
