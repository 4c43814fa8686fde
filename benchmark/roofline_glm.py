"""What an IRLS iteration's Gram NEEDS, from shapes (beside ``roofline.py``,
which holds the peaks and the tree engine's floors and is not edited).

The design matrix is one-hot: a row has one non-zero a categorical predictor
and one value a numeric one, so with the intercept ``predictors + 1``
non-zeros whatever the expanded width. The normal equations X'WX and X'Wz
need, a row: its predictors (a level code or a float, 4 bytes each), its
response and its weight read once, and the outer product of its non-zeros
added into the [K+1, K+1] system — ``(predictors + 1)^2`` multiply-adds. The
system itself (669^2 floats) is small beside the rows and is left out. The
dense formulation the program chose (2 x rows x 668^2 in six bf16 passes) is
how it gets there, not what the algorithm needs, so it is not counted.
"""

from __future__ import annotations

from benchmark.roofline import least_seconds


def gram_iteration(rows: int, predictors: int) -> tuple[float, float]:
    """(operations, bytes) one iteration's normal equations need."""
    ops = 2.0 * rows * (predictors + 1) ** 2
    nbytes = 4.0 * rows * (predictors + 2)
    return ops, nbytes


def gram_floor(rows: int, predictors: int, iterations: float,
               peak: dict) -> tuple[float, str]:
    """Least seconds for ``iterations`` Gram builds, and the bound."""
    s, bound = least_seconds(*gram_iteration(rows, predictors), peak)
    return s * iterations, bound
