"""Share of ``train()``'s wall that is NOT inside a boosting chunk: binning,
training metrics, model assembly, the Job. 1 - sum of chunk walls / sum of
train walls, in percent, over the window's builds.

The chunk wall is the program's own ``h2o3_iteration_seconds{loop=
<algo>_chunk}`` (``timed_event`` around a chunk that ends in one
``device_get``, so it is a synced time); the train wall is the benchmark's
span around ``train()``."""

LAYER, UNIT, MOVES = "builder", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    chunk = counters.delta(r.before, r.after, "h2o3_iteration_seconds_sum",
                           loop=f"{r.facts['algo']}_chunk")
    train = sum(r.facts["train_walls"])
    if chunk <= 0 or train <= 0:
        return None
    return 100.0 * (1.0 - chunk / train)
