"""Grid steps a traced call of the Pallas histogram kernel runs:
``h2o3_hist_grid_steps_total`` over every ``contraction`` of
``h2o3_hist_kernel_levels_total``. Both move where ``hist_pallas`` is TRACED,
which happens in the warm-up build, so their ABSOLUTE values at the
window's end are read (a delta over the window is 0; the histogram check
traces its own call after that). A step costs the
kernel a fixed 0.2-0.4 us whatever it computes: 601,580 steps a call at one
(row tile, feature) a step, a few thousand at a row tile's whole feature
block a step. How the calls contract the statistics' bf16 digits
(``packed`` side by side in the MXU's lanes, or ``passes``) is logged beside
it. A program without the counters (PR 29's parent) leaves the metric out."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "kernel", "count", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    calls = counters.value(r.after, "h2o3_hist_kernel_levels_total")
    if calls <= 0:
        return None
    packed = counters.value(r.after, "h2o3_hist_kernel_levels_total",
                            contraction="packed")
    load("layer_metrics", "_scopes").log(
        f"histogram kernel calls traced: {packed:.0f} packed, "
        f"{calls - packed:.0f} passes")
    return counters.value(r.after, "h2o3_hist_grid_steps_total") / calls
