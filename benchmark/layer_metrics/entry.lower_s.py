"""Seconds set-up spent lowering jaxprs to MLIR modules (a Pallas kernel's
Mosaic lowering included): seconds of JAX's
``jaxpr_to_mlir_module_duration`` spans, every phase
(``h2o3_first_call_seconds_total{stage="lower"}``). No cache saves them."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "entry", "s", "setup_s"
DRIVERS = ("build_loop", "score_open_loop")


def read(r):
    return load("layer_metrics", "_first_calls").stage_seconds(r, "lower")
