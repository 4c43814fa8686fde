"""Executables set-up asked the backend for: one a
``backend_compile_duration`` span JAX reported, loaded from the persistent
cache or compiled (``h2o3_executables_total``, every phase and source).
Every eager operation's first use is one. Absolute at the end of set-up."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "entry", "count", "setup_s"
DRIVERS = ("build_loop", "score_open_loop")


def read(r):
    first = load("layer_metrics", "_first_calls")
    return first.total(r.before, first.EXECUTABLES)
