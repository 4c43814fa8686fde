"""Seconds set-up spent in the backend's compile-or-load: JAX's
``backend_compile_duration`` spans, every phase
(``h2o3_first_call_seconds_total{stage="backend"}``): loads on a warm
persistent cache, compiles on a first run (``entry.cache_miss`` says
which)."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "entry", "s", "setup_s"
DRIVERS = ("build_loop", "score_open_loop")


def read(r):
    return load("layer_metrics", "_first_calls").stage_seconds(r, "backend")
