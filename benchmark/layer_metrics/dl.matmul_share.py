"""Share of the device's busy time inside the window that runs under the
scopes ``forward`` (the layers' products, bias adds and rectifiers), its
transposes (the backward pass: ``transpose(jvp(forward))``) and ``loss``
(the softmax cross-entropy and its gradient), in percent: the work the MXU
is there for. See ``_dl_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_dl_scopes").scopes_share(
        r, ("forward", "forward'", "loss"))
