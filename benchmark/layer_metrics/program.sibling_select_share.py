"""Share of the sibling-subtraction levels the program traced in this
process that read each row's parent's chosen child by compare-and-select
(``tree._sibling_slots`` through ``tree._lookup``, no gather):
``h2o3_sibling_levels_total{path="select"}`` over every ``path`` of that
counter, in percent. The counter moves when a program is TRACED, which happens
in the warm-up build, so its ABSOLUTE value at the window's end is read (a
delta over the window is 0). 100 while every level has no more parents than
``tree._SELECT_MAX_ENTRIES``; a program without the counter leaves the metric
out."""

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    total = counters.value(r.after, "h2o3_sibling_levels_total")
    if total <= 0:
        return None
    select = counters.value(r.after, "h2o3_sibling_levels_total",
                            path="select")
    return 100.0 * select / total
