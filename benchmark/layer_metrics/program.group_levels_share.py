"""Share of the tree levels the program traced in this process whose split
search was a GROUP-split search (``tree._find_splits`` with ``cat_feats``:
categorical bins ranked by G/H, sorted prefixes scanned):
``h2o3_split_levels_total{kind="group"}`` over every ``kind`` of that
counter, in percent. The counter moves when a program is TRACED, which
happens in the warm-up build, so its ABSOLUTE value at the window's end is
read (a delta over the window is 0). 100 in a cell whose frame has
categorical columns under ``categorical_encoding`` AUTO, 0 in a numeric one;
a program without the counter (PR 30's parent) leaves the metric out."""

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    total = counters.value(r.after, "h2o3_split_levels_total")
    if total <= 0:
        return None
    group = counters.value(r.after, "h2o3_split_levels_total", kind="group")
    return 100.0 * group / total
