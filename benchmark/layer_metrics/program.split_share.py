"""Share of the device's busy time inside the window that runs under the
boost program's scope ``split`` (``jax.named_scope`` in
``tree._grow_tree_device``: a level's column sample, ``_find_splits`` over the
level's histograms, the level's heap arrays and the monotone / interaction
bookkeeping), in percent. See ``_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_scopes").part_share(r, "split")
