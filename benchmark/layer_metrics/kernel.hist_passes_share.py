"""Share of the Pallas histogram kernel's traced calls that contract the
statistics' bf16 digits in PASSES (a pass of the one-hot through the MXU a
digit, node blocks of more than 21 slots) and not ``packed`` side by side in
the MXU's lanes: ``h2o3_hist_kernel_levels_total{contraction="passes"}`` over
every ``contraction`` of that counter, in percent. The counter moves where
``hist_pallas`` is TRACED, which happens in the warm-up build, so its
ABSOLUTE value at the window's end is read (the histogram checks trace their
own calls after that). 0 in a cell of depth 6 (at most 16 slots a call);
strictly between 0 and 100 at depth 10 (1 to 16 slots packed; 32, 64, 128
and 256 in passes). A program without the counter (PR 29's parent) leaves
the metric out."""

LAYER, UNIT, MOVES = "kernel", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    calls = counters.value(r.after, "h2o3_hist_kernel_levels_total")
    if calls <= 0:
        return None
    passes = counters.value(r.after, "h2o3_hist_kernel_levels_total",
                            contraction="passes")
    return 100.0 * passes / calls
