"""Share of the device's busy time inside the window that runs under the
group-split ranking: the scope ``rank`` inside ``split``
(``jax.named_scope`` in ``tree._find_splits``: the argsort of a level's
categorical bins by G/H, the gather into that order, the sorted cumulative
sum and the inverse permutation), in percent. An instruction's ``op_name``
holds the scopes as path components (``.../level7/split/rank/...``); a
fusion carries its root's. A program without the scope (PR 30's parent, or a
model without categorical columns) leaves the metric out."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def under_rank(op_name: str) -> bool:
    parts = op_name.split("/")
    return "split" in parts and "rank" in parts[parts.index("split"):]


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    module = load("layer_metrics", "_scopes").program_module(r) or ""
    s = r.trace.op_seconds(
        lambda name, stats: name.startswith(module + "/")
        and under_rank(stats.get("op_name", "")))
    return 100.0 * s / r.trace.busy_s if s > 0 else None
