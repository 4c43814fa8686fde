"""Seconds to make the training frame: the benchmark's span around the
on-device generator, ``Vec.from_device`` and ``Frame(...)``, ending when
every column is ready."""

LAYER, UNIT, MOVES = "frame", "s", "setup_s"
DRIVERS = ("build_loop",)


def read(r):
    walls = r.spans.walls("frame.make")
    return float(sum(walls)) if walls else None
