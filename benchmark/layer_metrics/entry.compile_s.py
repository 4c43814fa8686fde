"""Seconds the program spent compiling, or loading from the persistent
cache, in set-up: ``COSTS.snapshot()``'s host wall around
``lower().compile()``, summed over the ``accounted_jit`` sites."""

LAYER, UNIT, MOVES = "entry", "s", "setup_s"
DRIVERS = ("build_loop", "score_open_loop")


def read(r):
    return float(sum(s["seconds"] for s in r.before["compile_sites"].values()))
