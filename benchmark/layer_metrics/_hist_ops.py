"""Which device operations are the histogram build — shared by the
``kernel.hist_*`` readers, and the one place their match patterns live.

As the program stands at PR 22 it has no ``jax.named_scope`` and its
``pallas_call`` has no ``name=``, so the match is on what a trace and the
compiled module show today:

- one chip: the Pallas kernel is a ``custom-call`` whose HLO instruction is
  named after the jitted function around it, ``hist_pallas.<n>``;
- across chips: the level histogram is ``segment_sum`` inside ``shard_map``,
  which the compiler turns into fusions whose ``op_name`` ends in
  ``.../shard_map/.../scatter-add`` (the scatter-adds OUTSIDE ``shard_map``
  are the last level's per-node totals, not a histogram).

The ``tracing`` PR that gives these stable names brings new reader files
with new patterns, and edits none.
"""


def is_hist(name: str, stats: dict) -> bool:
    if name.rpartition("/")[2].startswith("hist_pallas"):
        return True
    op_name = stats.get("op_name", "")
    return "shard_map" in op_name and op_name.endswith("scatter-add")


def hist_seconds(r) -> float | None:
    """Self seconds of the histogram operations inside the traced window,
    averaged over the chips; None where there is no trace or no such
    operation."""
    if r.trace is None:
        return None
    s = r.trace.op_seconds(is_hist)
    return s if s > 0 else None
