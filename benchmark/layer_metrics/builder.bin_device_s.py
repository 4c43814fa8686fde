"""Device busy seconds a build from the start of the program's
``<algo>:prepare.bin`` span to the start of the first ``<algo>:chunk`` span
after it: what binning the frame costs on the device.

The span's START is the instant ``_bin_frame`` first dispatches (a
``searchsorted`` a column); the work is asynchronous and the span's end means
nothing. What drains it is ``_fit``'s ``f0`` fetch (``float(device_get(...))``)
before the first chunk opens, and the reading leans on that: were the fetch to
go, binning would run on into the chunk and read short. Also inside: the
response's conversion to float, the row mask and the ``f0`` reduction itself
(all small beside a frame's binning), and a validation frame's binning where
there is one."""

import bisect

from benchmark.plugins import load

LAYER, UNIT, MOVES = "builder", "s", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    scopes = load("layer_metrics", "_scopes")
    bins = scopes.program_spans(r, "prepare.bin")
    chunks = [a for a, _ in scopes.program_spans(r, "chunk")]
    if not bins or not chunks or not r.facts["builds"]:
        return None
    busy = 0.0
    for start, _ in bins:
        i = bisect.bisect_left(chunks, start)
        if i < len(chunks):
            busy += r.trace.busy_within(start, chunks[i])
    return busy / r.facts["builds"] if busy > 0 else None
