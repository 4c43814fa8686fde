"""The part of ``collective.share`` during which no other operation ran on
that chip: collective time that compute does not hide, as a share of busy
time, in percent."""

LAYER, UNIT, MOVES = "collective", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark.trace_reduce import is_collective
    if r.trace is None or r.facts["chips"] < 2 or r.trace.busy_s <= 0:
        return None
    return 100.0 * r.trace.exposed_seconds(is_collective) / r.trace.busy_s
