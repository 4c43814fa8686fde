"""Share of the device's busy time inside the window that runs under the
scopes ``optimizer`` (ADADELTA, or momentum SGD: an element-wise pass over
every parameter and its state arrays), ``regularize`` (``l1`` / ``l2`` added
to the gradient) and ``constrain`` (``max_w2``) of ``_train_epochs``, in
percent. At a minibatch of 32 these passes read and write 94 MB an update
and are expected to set the pace, not the products. See ``_dl_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_dl_scopes").scopes_share(
        r, ("optimizer", "regularize", "constrain"))
