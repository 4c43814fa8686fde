"""Share of the device's busy time inside the window that runs under the
boost program's scope ``leaves`` (``jax.named_scope`` in
``tree._grow_tree_device``: the last level's per-node totals
``tree._node_totals``, the leaf values made of them and the rows' read of
their own), in percent. See ``_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_scopes").part_share(r, "leaves")
