"""Device microseconds one minibatch update costs: the seconds of the
configuration's program (``jit__train_epochs``, from the trace's ``XLA
Modules`` line) inside the window over ``h2o3_dl_updates_total`` in it. The
epoch's shuffle is inside the program, so it is in the number."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "us", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    s = load("layer_metrics", "_dl_scopes").update_seconds(r)
    return None if s is None else 1e6 * s
