"""Share of the device's busy time inside the window that runs under the
boost program's scope ``route`` (``jax.named_scope`` in
``tree._grow_tree_device``: the frozen rows' leaf values and ``_route_rows``,
a level: the gather of each row's bin at its node's feature and the gather
of that bin's side), in percent. See ``_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_scopes").part_share(r, "route")
