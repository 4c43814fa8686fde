"""Seconds set-up spent computing columns' lazy roll-ups
(``h2o3_rollup_seconds_total``, every phase: the wall of each
``Vec.rollups()`` that ran its program, with its fetch): one dispatch and one
fetch a column the first time a builder asks, so a first build on a new frame
pays one round trip a predictor. Absolute at the end of set-up. Also logs how
many ran (``h2o3_rollups_total`` by kind) and what one cost: many cheap round
trips say batch them, a few dear ones say look at the roll-up program."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "frame", "s", "setup_s"
DRIVERS = ("build_loop", "score_open_loop")


def read(r):
    first = load("layer_metrics", "_first_calls")
    seconds = first.total(r.before, first.ROLLUP_SECONDS)
    if seconds is None:
        return None
    kinds = {labels["kind"]: int(v) for n, labels, v in r.before["metrics"]
             if n == first.ROLLUP_COUNT}
    count = sum(kinds.values())
    inside = first.total(r.before, first.ROLLUP_SECONDS, first.in_a_build)
    first.log(f"roll-ups in set-up: {count} ("
              + ", ".join(f"{k} {v}" for k, v in sorted(kinds.items()))
              + f") in {seconds:.3f} s, {inside:.3f} s of them asked for "
              "inside a build"
              + (f"; {1e3 * seconds / count:.2f} ms a round trip"
                 if count else ""))
    return seconds
