"""The whole update's share of the MXU's peak, in percent: 6 x B x P
operations an update (``roofline_dl.mfu_seconds``: every weight meets every
row once forward and twice backward) at the chip's bf16 peak, over the
measured device time of an update (``dl.update_us``). Expected under 3% at a
minibatch of 32: the number is here so that the whole step's share exists
under that name; ``dl.step_roofline`` says what bounds the step."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "kernel", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import roofline_dl
    dl = load("layer_metrics", "_dl_scopes")
    s, shape = dl.update_seconds(r), dl.shape(r)
    if s is None or shape is None or r.peak is None:
        return None
    P, B, _K = shape
    return 100.0 * roofline_dl.mfu_seconds(P, B, r.peak) / s
