"""Share of the device's busy time inside the window that runs inside the
configuration's compiled program (``program`` in its file: the boosting scan,
``jit__boost_scan_jit``), in percent, from the trace's ``XLA Modules`` line.
The rest is what the builder dispatches around it op by op: binning
(``searchsorted`` a column), training metrics, the row mask."""

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    program = r.cell.config.get("program")
    if r.trace is None or not program or r.trace.busy_s <= 0:
        return None
    module = "jit_" + program.rpartition(":")[2]
    s = r.trace.module_s.get(module)
    return 100.0 * s / r.trace.busy_s if s else None
