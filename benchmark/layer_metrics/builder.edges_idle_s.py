"""Seconds a build the device ran nothing while the builder computed bin
edges on the host: over the program's ``<algo>:prepare.edges`` spans in the
window (``GBM._prepare``: numpy quantiles of the row sample in
``quantile.compute_bin_edges``, and the edges' upload), the span less the
device's busy time inside it, divided by the window's builds. The same gap
that ``breakdown.idle_gaps`` names ``quantile.py:compute_bin_edges`` through
the Python tracer; this reads the program's own span."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "builder", "s", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    scopes = load("layer_metrics", "_scopes")
    spans = scopes.program_spans(r, "prepare.edges")
    if not spans or not r.facts["builds"]:
        return None
    return scopes.idle_within(r, spans) / r.facts["builds"]
