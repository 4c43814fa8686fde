"""Seconds set-up spent tracing Python functions into jaxprs: JAX's
``jaxpr_trace_duration`` spans (a nested jit's trace is inside its caller's
and counted once; one made by a lowering rule is the lowering's), every phase
(``h2o3_first_call_seconds_total{stage="trace"}``). No cache saves them.
Also logs the ten dearest rows of the program's by-function table."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "entry", "s", "setup_s"
DRIVERS = ("build_loop", "score_open_loop")


def read(r):
    first = load("layer_metrics", "_first_calls")
    seconds = first.stage_seconds(r, "trace")
    if seconds is not None:
        first.log_dearest(10)
    return seconds
