"""Share of the device's busy time inside the window that went to
collectives (all-reduce and kin), in percent, averaged over the chips. Only
a cell across chips has any."""

LAYER, UNIT, MOVES = "collective", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark.trace_reduce import is_collective
    if r.trace is None or r.facts["chips"] < 2 or r.trace.busy_s <= 0:
        return None
    return 100.0 * r.trace.op_seconds(is_collective) / r.trace.busy_s
