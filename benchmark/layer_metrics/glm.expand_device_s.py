"""Device seconds a build spends expanding the frame into the dense design
matrix: the self seconds of the expansion program's operations
(``jit__expand/...``: the broadcast compares a categorical column and the
concatenate) inside the window, over the window's builds — the training
expansion and the training metrics' second one (``GLMModel._score_raw``
expands the frame again) both.

The program's ``glm:expand`` spans say that there were expansions and how
many; they cannot time them, because the span closes when the expansion is
DISPATCHED and the device runs it later. Without the spans (PR 26's parent)
the metric is left out."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "builder", "s", "train_work_per_s_chip"
DRIVERS = ("build_loop",)
MODULE = "jit__expand"


def in_expand(name: str, _stats: dict) -> bool:
    return name.startswith(MODULE + "/")


def read(r):
    scopes = load("layer_metrics", "_scopes")
    spans = scopes.program_spans(r, "expand")
    if not spans or not r.facts["builds"]:
        return None
    s = r.trace.op_seconds(in_expand)
    if s <= 0:
        return None
    scopes.log(f"{len(spans)} glm:expand span(s) in {r.facts['builds']} "
               f"build(s), {MODULE} {s:.4f} s on the device")
    return s / r.facts["builds"]
