"""Share of the device's busy time inside the window that the histogram
build takes, in percent: the Pallas kernel on one chip, the ``segment_sum``
scatter fusions across chips (see ``_hist_ops.py``)."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "kernel", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    s = load("layer_metrics", "_hist_ops").hist_seconds(r)
    if s is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * s / r.trace.busy_s
