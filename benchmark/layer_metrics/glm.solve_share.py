"""Share of the device's busy time inside the window that runs under the IRLS
step's scope ``solve`` (``cho_factor`` and ``cho_solve`` of the [K+1, K+1]
system: a chain of small dependent operations, bound by latency, not by the
MXU or by bandwidth), in percent. See ``_glm_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_glm_scopes").parts_share(r, ("solve",))
