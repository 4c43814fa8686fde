"""What the harness hands a driver, what the driver hands back, and what a
per-layer metric's reader is handed."""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import time

#: toy sizes of the CPU rehearsal (``run.py --selftest``), by the key a
#: configuration or a traffic mix uses for the full size
SELFTEST_SIZES = {"rows": 8192, "heldout_rows": 2048, "reference_rows": 4096,
                  "hist_check_rows": 4096, "train_rows": 4096,
                  "traversal_rows": 512, "pool": 16, "connections": 4,
                  "rate_per_s": 40.0, "max_rows": 64}


@dataclasses.dataclass
class Cell:
    """One cell, one run."""
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    selftest: bool
    work_dir: str                 # benchmark/.cache/work/<cell>: scratch
    spans: object                 # spans.Spans
    compiles: object              # counters.CompileWatch
    t_window: float | None = None

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.work_dir, "trace")

    def size(self, where: dict, key: str):
        """A size from a data file — or its toy value in the rehearsal."""
        if self.selftest and key in SELFTEST_SIZES:
            return SELFTEST_SIZES[key]
        return where[key]

    @contextlib.contextmanager
    def profiler(self):
        """The JAX profiler around a traced run's window; nothing with
        ``--trace 0``. The traffic mix says whether Python calls are traced
        too (``python_tracer``: they name the host's part in an idle gap, and
        are too many where a server answers requests)."""
        if not self.trace:
            yield
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = int(bool(self.traffic.get("python_tracer")))
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def open_window(self) -> float:
        """The first instant of the measured window; set-up ends here."""
        self.t_window = time.perf_counter()
        return self.t_window


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    checks: dict                  # name -> {"ok": bool, ...detail}
    end_to_end: dict              # metric name -> value
    facts: dict                   # the driver's facts, for the readers
    before: dict                  # counters.snapshot() at the window's start
    after: dict                   # ... and at its end


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader is handed."""
    cell: Cell
    facts: dict                   # the driver's own facts about the window
    spans: object                 # spans.Spans: the benchmark's host spans
    before: dict                  # counters.snapshot() at the window's start
    after: dict                   # ... and at its end
    trace: object | None          # trace_reduce.Reduction of a traced run
    peak: dict | None             # the device's row of peaks.json
    memory_peak_bytes: int
