"""What set-up's first build pays that still has no name: the ``warmup``
span's wall, less a steady build (the median of the window's ``train()``
walls), less the trace, lower and backend seconds booked under a build's own
phases (not ``(outside a build)``, not ``frame:rollups``), less the wall of
the roll-ups a build asked for (``h2o3_rollup_seconds_total`` but for
``(outside a build)``, which holds the frame's making; the wall holds the
roll-ups' own first calls). NOT floored at 0: a reading under 0 says
something was counted twice. Left out where the program has no such counters
and where set-up made no warm-up build or more than one: the counters do not
say which build a first call or a roll-up belonged to."""

import statistics

from benchmark.plugins import load

LAYER, UNIT, MOVES = "entry", "s", "setup_s"
DRIVERS = ("build_loop",)


def read(r):
    first = load("layer_metrics", "_first_calls")
    warmups = r.spans.walls("warmup")
    steady = r.facts.get("train_walls")
    named = first.build_seconds(r)
    rollups = first.total(r.before, first.ROLLUP_SECONDS, first.in_a_build)
    if len(warmups) != 1 or not steady or named is None or rollups is None:
        return None
    steady = statistics.median(steady)
    dark = warmups[0] - steady - named - rollups
    parts = {stage: first.build_seconds(r, stage)
             for stage in ("trace", "lower", "backend")}
    first.log(f"first build {warmups[0]:.3f} s = a steady build "
              f"{steady:.3f} s + under its own phases "
              + " + ".join(f"{k} {v:.3f} s" for k, v in parts.items())
              + f" + its roll-ups {rollups:.3f} s + dark {dark:.3f} s")
    return float(dark)
