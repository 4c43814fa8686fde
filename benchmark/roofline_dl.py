"""What ONE minibatch update of a dense network NEEDS, from shapes (beside
``roofline.py``, which holds the peaks and is not edited, and
``roofline_glm.py``, its pattern).

An update of a network with ``P`` parameters on ``B`` rows of ``K`` inputs:

- operations: every weight meets every row once forward (a multiply-add: 2)
  and twice backward (the gradient by the weight and the gradient by the
  layer's input): ``6 x B x P``. The first layer's gradient by its input is
  not needed and the biases do no products; both are left in, so the count is
  the usual 6BP and a hair HIGH (0.4% here): the shares read a hair high,
  never over 100% for that reason (the first layer is 19% of ``P``: leaving
  its input gradient out altogether would be 6% less).
- bytes: the batch's rows are read once (``4 x B x K``), and that is all that
  has to come through the chip's memory system an update. The parameters and
  ADADELTA's two state arrays (``3 x 4 x P`` = 47 MB here) are carried from
  one update to the next and need not leave the chip between them: the first
  version of this file counted them read and written once (94 MB, 114 us at
  819 GB/s, as ISSUE 32 reckoned), and the v5e ran a whole update in 107 us
  and ADADELTA's pass over all three in 21 us (4.4 TB/s; my chip run, PR 32):
  the compiler keeps the scan's carried state in on-chip memory, so a floor
  that charges it to HBM reads 107%. Activations, masks and gradients are
  small at a batch that fits the chip's fast memory and are left out, as is
  the shuffled copy of the design an epoch makes (how the program gets its
  rows, not what the update needs).

They count what the algorithm needs whatever implements it, in float32 state
and one bf16 pass a product, which is what the configuration states. By the
published peaks the floor is then the products' (compute-bound, 3.8 us at
B = 32): ``dl.step_roofline`` and ``dl.step_mfu`` read the same number until a
batch or a network is large enough for its rows, or for state that no longer
fits the chip, to set the pace.
"""

from __future__ import annotations

from benchmark.roofline import least_seconds


def parameters(widths: list[int]) -> int:
    """Weights and biases of a dense network ``widths[0]-...-widths[-1]``."""
    return sum(a * b + b for a, b in zip(widths, widths[1:]))


def update(P: int, B: int, K: int) -> tuple[float, float]:
    """(operations, bytes) one update needs."""
    return 6.0 * B * P, 4.0 * B * K


def update_floor(P: int, B: int, K: int, peak: dict) -> tuple[float, str]:
    """Least seconds for one update by the chip's peaks, and the bound."""
    return least_seconds(*update(P, B, K), peak)


def mfu_seconds(P: int, B: int, peak: dict) -> float:
    """Seconds the update's operations take at the bf16 peak: the numerator
    of the whole step's share of the MXU."""
    return 6.0 * B * P / peak["bf16_flops_per_s"]
