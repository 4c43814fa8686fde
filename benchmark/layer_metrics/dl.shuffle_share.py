"""Share of the device's busy time inside the window that runs under the
scope ``shuffle`` of ``_train_epochs``: an epoch's permutation (a sort of
random keys) and the gather of the WHOLE design matrix into minibatch order,
a second design matrix while the epoch runs; in percent. See
``_dl_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_dl_scopes").scopes_share(r, ("shuffle",))
