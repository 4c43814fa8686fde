"""Median host time of a ``/3/Score`` request outside the scoring dispatch,
in ms: the server's request span minus its ``score:dispatch`` span, over the
completed traces the program's ``TRACER`` ring still holds at the end of the
window that carry a dispatch span (the batch leaders; at most the last 128
requests). JSON decode, row coercion, batching wait and the reply."""

LAYER, UNIT, MOVES = "serving", "ms", "score_p50_ms"
DRIVERS = ("score_open_loop",)


def read(r):
    traces = r.facts.get("request_traces") or []
    if not traces:
        return None
    host = sorted(t["request_s"] - t["dispatch_s"] for t in traces)
    return 1e3 * host[len(host) // 2]
