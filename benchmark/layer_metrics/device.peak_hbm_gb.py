"""Peak device memory of the fullest chip over the whole process, in GB
(``memory_stats()["peak_bytes_in_use"]``): what sizes a cell."""

LAYER, UNIT, MOVES = "device", "GB", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return r.memory_peak_bytes / 1e9 if r.memory_peak_bytes else None
