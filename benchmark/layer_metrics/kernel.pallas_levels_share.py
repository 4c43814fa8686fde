"""Share of the tree levels the program traced in this process that took the
Pallas kernel: ``tree.HIST_PATHS["pallas"]`` over all its counts, in percent.
The counter moves when a program is TRACED, which happens in the warm-up
build, so it is read at the end of set-up. 100 on one chip; 0 across chips
today (ROADMAP S5)."""

LAYER, UNIT, MOVES = "kernel", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    paths = r.after["hist_paths"]
    total = sum(paths.values())
    return 100.0 * paths.get("pallas", 0) / total if total else None
