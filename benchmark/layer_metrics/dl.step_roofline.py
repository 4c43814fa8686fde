"""The update's share of its roofline, in percent: the least time the chip's
peaks allow for what ONE update needs (``roofline_dl.update_floor``: 6 x B x P
operations at the bf16 peak, or the batch's rows read once at the memory's
peak, whichever takes longer: compute, 3.8 us, at the cell's B = 32 and
P = 3.9M; the parameters and ADADELTA's state stay on the chip between
updates and are not charged to HBM, ``roofline_dl.py`` says why) over the
measured device time of an update (``dl.update_us``). It counts what the
algorithm needs whatever implements it, so it cannot pass 100% while the
program does every update's products."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "kernel", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import roofline_dl
    dl = load("layer_metrics", "_dl_scopes")
    s, shape = dl.update_seconds(r), dl.shape(r)
    if s is None or shape is None or r.peak is None:
        return None
    floor, _bound = roofline_dl.update_floor(*shape, r.peak)
    return 100.0 * floor / s
