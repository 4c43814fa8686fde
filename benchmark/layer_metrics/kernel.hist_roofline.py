"""The histogram build's share of its roofline, in percent: the least time
the chip's peaks allow for the bytes and operations the ALGORITHM needs at
the cell's shapes (``roofline.hist_build_floor``: every level reads every
row's bins, node id and three statistics once — bound by memory) over the
measured time of the histogram operations, per chip. It says how far the
whole approach is from the chip's limit, not how well the MXU formulation
the kernel chose is executed."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "kernel", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import roofline
    f = r.facts
    s = load("layer_metrics", "_hist_ops").hist_seconds(r)
    if s is None or r.peak is None or not f.get("depth") or not f["trees"]:
        return None
    floor, _bound = roofline.hist_build_floor(
        f["rows_per_chip"], f["features"], roofline.bin_bytes(f["nbins"]),
        levels=f["depth"] * f["trees"], peak=r.peak)
    return 100.0 * floor / s
