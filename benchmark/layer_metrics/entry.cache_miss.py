"""Programs set-up had to compile because the persistent cache did not hold
them (``compile_cache.stats()``; JAX counts a miss when it writes the entry).
0 in every run of a checkout but the first."""

LAYER, UNIT, MOVES = "entry", "count", "setup_s"
DRIVERS = ("build_loop", "score_open_loop")


def read(r):
    return float(r.before["cache"]["misses"])
