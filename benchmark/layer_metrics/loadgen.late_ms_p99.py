"""99th percentile of how late the load generator's sends left, in ms: the
validity of the serving cell, not a layer of the program. Small against
``score_p50_ms``, or the generator — not the server — set the latencies."""

LAYER, UNIT, MOVES = "loadgen", "ms", "score_p50_ms"
DRIVERS = ("score_open_loop",)


def read(r):
    return r.facts["summary"]["late_ms_p99"]
