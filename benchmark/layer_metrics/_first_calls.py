"""What set-up's first calls paid, from the program's own record — shared by
the ``entry.*`` readers this record feeds, and the one place its names live.

Since PR 35 the program books what JAX reports of every first call
(``h2o3_tpu/utils/compile_cache.py``'s listeners): seconds of tracing,
lowering and the backend's compile-or-load in
``h2o3_first_call_seconds_total{phase, stage}``, one executable request a
backend span in ``h2o3_executables_total{phase, source}``, a column's lazy
roll-up in ``h2o3_rollups_total{kind}`` and, by the phase that asked for it,
``h2o3_rollup_seconds_total{phase}``. The phase is the innermost open
``timed_event`` of the program, else ``(outside a build)``; a roll-up's own
first call has ``frame:rollups``.

Set-up runs outside the profiler, so the readers take a counter's ABSOLUTE
value in ``r.before``, the snapshot at the window's first instant. A program
without the counters (the parent of PR 35) leaves nothing to find: every
function here then returns None, never 0.
"""

import sys

SECONDS = "h2o3_first_call_seconds_total"
EXECUTABLES = "h2o3_executables_total"
ROLLUP_SECONDS = "h2o3_rollup_seconds_total"
ROLLUP_COUNT = "h2o3_rollups_total"
OUTSIDE = "(outside a build)"
ROLLUPS = "frame:rollups"


def log(msg: str) -> None:
    print(f"# benchmark: {msg}", file=sys.stderr, flush=True)


def total(snap: dict, name: str, keep=lambda labels: True) -> float | None:
    """Sum of the counter's rows whose labels ``keep`` takes; None where the
    snapshot holds no row of that name at all."""
    rows = [(labels, v) for n, labels, v in snap["metrics"] if n == name]
    if not rows:
        return None
    return float(sum(v for labels, v in rows if keep(labels)))


def stage_seconds(r, stage: str) -> float | None:
    return total(r.before, SECONDS, lambda lab: lab.get("stage") == stage)


def build_seconds(r, stage: str | None = None) -> float | None:
    """Trace, lower and backend seconds of set-up (or those of one stage)
    under a build's own phases: not ``(outside a build)`` (the frame, the
    benchmark's own operations) and not ``frame:rollups`` (inside the
    roll-ups' wall)."""
    return total(r.before, SECONDS,
                 lambda lab: lab.get("phase") not in (OUTSIDE, ROLLUPS)
                 and stage in (None, lab.get("stage")))


def in_a_build(labels: dict) -> bool:
    return labels.get("phase") != OUTSIDE


def log_dearest(n: int = 10) -> None:
    """The ``n`` dearest rows of the program's by-function table, as it
    stands when the readers run: set-up's first calls and, under
    ``(outside a build)``, those of the checks after the window."""
    from h2o3_tpu.utils.costs import COSTS
    table = COSTS.snapshot().get("first_calls") or []
    for row in table[:n]:
        log(f"first calls in set-up: {row['phase']} {row['fun_name']} "
            f"{row['requests']} {row['trace_seconds']:.3f} "
            f"{row['lower_seconds']:.3f} {row['backend_seconds']:.3f}")
    if table:
        log(f"first calls in set-up: (phase fun_name n trace lower backend, "
            f"seconds; {len(table)} rows in all; rows under "
            f"'{OUTSIDE}' hold the checks' programs too)")
