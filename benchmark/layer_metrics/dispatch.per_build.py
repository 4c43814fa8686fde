"""``map_reduce`` dispatches per build in the window
(``h2o3_mapreduce_dispatches_total``, all ``fn`` labels). A count: expected
flat."""

LAYER, UNIT, MOVES = "dispatch", "count", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    if not r.facts["builds"]:
        return None
    n = counters.delta(r.before, r.after, "h2o3_mapreduce_dispatches_total")
    return n / r.facts["builds"]
