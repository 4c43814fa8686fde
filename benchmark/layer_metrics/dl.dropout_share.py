"""Share of the device's busy time inside the window that runs under the
scope ``dropout`` of ``_train_epochs``: the random bits of an update's
keep-masks (threefry over 4,813 units a row), the masks and their
application, forward and backward; in percent. See ``_dl_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_dl_scopes").scopes_share(r, ("dropout",))
