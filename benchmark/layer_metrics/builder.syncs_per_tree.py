"""Boosting chunks (one blocking fetch each) per tree built in the window:
the count of ``h2o3_iteration_seconds{loop=<algo>_chunk}`` over trees."""

LAYER, UNIT, MOVES = "builder", "count", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    chunks = counters.delta(r.before, r.after, "h2o3_iteration_seconds_count",
                            loop=f"{r.facts['algo']}_chunk")
    if chunks <= 0 or not r.facts["trees"]:
        return None
    return chunks / r.facts["trees"]
