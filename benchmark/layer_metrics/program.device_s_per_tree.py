"""Seconds the device was busy inside the traced build(s), per tree, averaged
over the chips: the union of the device's operation intervals inside the
benchmark's ``train`` spans. The ratio of two cells' values is their scaling
efficiency per tree, whatever their ``ntrees``."""

LAYER, UNIT, MOVES = "program", "s", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    if r.trace is None or not r.facts["trees"]:
        return None
    busy = sum(r.trace.busy_within(max(a, r.trace.t0), min(b, r.trace.t1))
               for a, b in r.trace.spans("bench:train"))
    return busy / r.facts["trees"] if busy > 0 else None
