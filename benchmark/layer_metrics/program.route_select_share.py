"""Share of the tree levels the program traced in this process whose row
routing read its node tables by compare-and-select (``tree._route_rows``, no
gather): ``h2o3_route_levels_total{path="select"}`` over every ``path`` of
that counter, in percent. The counter moves when a program is TRACED, which
happens in the warm-up build, so its ABSOLUTE value at the window's end is
read (a delta over the window is 0). 100 while every level's tables hold no
more entries than ``tree._SELECT_MAX_ENTRIES``; a program without the counter
(PR 27's parent) leaves the metric out."""

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    total = counters.value(r.after, "h2o3_route_levels_total")
    if total <= 0:
        return None
    select = counters.value(r.after, "h2o3_route_levels_total", path="select")
    return 100.0 * select / total
