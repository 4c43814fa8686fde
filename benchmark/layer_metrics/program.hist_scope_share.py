"""Share of the device's busy time inside the window that runs under the
boost program's scope ``hist`` (``jax.named_scope`` in
``tree._grow_tree_device``: everything that produces a level's histograms, on
every path: the node slots, the kernel or the scatter-adds, and the sibling
subtraction), in percent. ``kernel.hist_share`` times the kernel alone, by
pattern; the difference is what wraps it: the ``[3, R]`` stack, pads, the
output's transpose, the subtraction. See ``_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_scopes").part_share(r, "hist")
