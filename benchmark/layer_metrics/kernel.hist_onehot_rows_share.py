"""One-hot rows the Pallas histogram kernel's traced calls build and stream
through the MXU, over the rows the same calls would stream if every feature
held the engine's whole bin count:
``h2o3_hist_onehot_rows_total{kind="streamed"}`` over ``{kind="dense"}``, in
percent. A call's cost is rows/128 x (one-hot rows a row) x passes MXU
row-cycles, and a call told what each column can hold (``bins_used``, from
the frame's cardinalities) skips the 8-row groups no bin id can match. The
counter moves where ``hist_pallas`` is TRACED, which happens in the warm-up
build, so its ABSOLUTE value at the window's end is read (the histogram
checks trace their own calls after that, dense). 100 where every column
holds ``nbins`` (the HIGGS cells); about 41 in ``gbm100-airline-cat-build``
(ten level calls a tree at 944 of 2,432 rows, the totals' call dense). A
program without the counter (PR 36's parent) leaves the metric out."""

LAYER, UNIT, MOVES = "kernel", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    dense = counters.value(r.after, "h2o3_hist_onehot_rows_total",
                           kind="dense")
    if dense <= 0:
        return None
    return 100.0 * counters.value(r.after, "h2o3_hist_onehot_rows_total",
                                  kind="streamed") / dense
