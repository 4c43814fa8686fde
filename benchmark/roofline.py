"""Peaks, and what an algorithm needs at least: operations and bytes from
shapes. A kernel's ``<kernel>_roofline`` is this floor over its measured
time, in percent. The functions here are the yardstick; the program's own
accounting (``utils/costs.py``, ROOFLINE.md) is not read.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peak_row(device_kind: str) -> dict:
    """The device's row of ``peaks.json``. A device that is not in the
    table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device_kind "
                         f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak sets it."""
    by_ops = ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops > by_bytes else (by_bytes, "memory")


def bin_bytes(nbins: int) -> int:
    """Bytes the narrowest integer takes that tells ``nbins`` regular bins
    and the missing-value bin apart."""
    return 1 if nbins + 1 <= 256 else 2


def hist_level(rows: int, features: int, bin_bytes: int) -> tuple[float, float]:
    """(operations, bytes) one level's histogram build NEEDS on one chip:
    every row's bin of every feature is read once, with its node id (int32)
    and its three statistics g, h, w (float32), and each statistic is added
    into one cell per feature. The histograms themselves (nodes x bins x
    features x 3 floats) are small beside the rows and are left out, and no
    credit is taken for sibling subtraction (which needs only the smaller
    child's rows, but then has to partition rows to read only those).

    The MXU formulation the kernel chose (a one-hot contraction, 2 x bins x
    nodes x 3 multiply-adds a row and feature, in two bf16 passes) is how the
    program gets there, not what the algorithm needs, so it is not counted.
    """
    ops = float(rows) * features * 3
    nbytes = float(rows) * (features * bin_bytes + 4 + 3 * 4)
    return ops, nbytes


def hist_build_floor(rows: int, features: int, bin_bytes: int, levels: int,
                     peak: dict) -> tuple[float, str]:
    """Least seconds for ``levels`` histogram passes, and the bound."""
    ops, nbytes = hist_level(rows, features, bin_bytes)
    s, bound = least_seconds(ops, nbytes, peak)
    return s * levels, bound
