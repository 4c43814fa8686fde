"""The Gram's share of its roofline, in percent: the least time the chip's
peaks allow for what the ALGORITHM needs an iteration at the cell's shapes
(``roofline_glm.gram_floor``: read each row's eight predictors, response and
weight once, or do the 2 x rows x 9^2 multiply-adds of the row's own
non-zeros, whichever takes longer) times the window's iterations, over the
measured seconds under the scope ``gram``. It counts the same work whatever
implements the Gram — a dense [rows, 668] matmul in six bf16 passes today —
so it cannot pass 100%, and it reads a small fraction of a percent: that is
the distance between a dense one-hot matmul and the structure of the design,
not how well the matmul runs."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "kernel", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import roofline_glm
    glm = load("layer_metrics", "_glm_scopes")
    by_part = glm.seconds_by_part(r)
    its = glm.iterations(r)
    if not by_part or "gram" not in by_part or r.peak is None or its <= 0:
        return None
    floor, _bound = roofline_glm.gram_floor(
        r.facts["rows_per_chip"], r.facts["features"], its, r.peak)
    return 100.0 * floor / by_part["gram"]
