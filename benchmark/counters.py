"""The one place the benchmark reads the program's counters.

Only what ISSUE 22's inventory found sound: telemetry counts and histogram
sums (``METRICS``), compile seconds by ``accounted_jit`` site
(``COSTS.snapshot()``), persistent-cache hits and misses
(``compile_cache.stats()``), the trace-time histogram path counter
(``tree.HIST_PATHS``). Plus one counter of the benchmark's own: every request
JAX makes for an executable, whether it compiles or loads from the
persistent cache (``CompileWatch``) — the count that must stay flat inside a
measured window.
"""

from __future__ import annotations

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Executables JAX asked its backend for since ``install`` — a compile
    or a load from the persistent cache, both recorded by JAX around
    ``compile_or_get_cached``."""

    def __init__(self) -> None:
        self.requests = 0
        self.seconds = 0.0

    def install(self) -> "CompileWatch":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.requests += 1
            self.seconds += duration

    def check_since(self, requests_then: int, before: dict, after: dict) -> dict:
        """The check every driver makes of its window: no executable
        requested since ``requests_then``, and no new signature at the
        program's own ``accounted_jit`` sites between the two snapshots."""
        requested = self.requests - requests_then
        new = after["signatures"] - before["signatures"]
        return {"ok": requested == 0 and new == 0,
                "executables_requested": requested, "new_signatures": new}


def snapshot() -> dict:
    """The program's counters now. ``metrics`` rows are ``(name, labels,
    value)`` as ``METRICS.snapshot()`` names them (``_total``, ``_sum``,
    ``_count``, ``_bucket`` suffixes)."""
    from h2o3_tpu.models.tree import HIST_PATHS
    from h2o3_tpu.utils import compile_cache
    from h2o3_tpu.utils.costs import COSTS
    from h2o3_tpu.utils.telemetry import METRICS
    cache = compile_cache.stats()
    return {
        "metrics": [(r["name"], r["labels"], float(r["value"]))
                    for r in METRICS.snapshot(include_buckets=True)],
        "compile_sites": {s["site"]: {"compiles": s["compiles"],
                                      "seconds": s["compile_seconds"]}
                          for s in COSTS.snapshot()["sites"]},
        "signatures": COSTS.signature_count(),
        "cache": {k: cache[k] for k in ("dir", "hits", "misses", "entries")},
        "hist_paths": dict(HIST_PATHS),
    }


def value(snap: dict, name: str, **labels) -> float:
    """Sum of the rows called ``name`` whose labels include ``labels``."""
    return sum(v for n, lab, v in snap["metrics"]
               if n == name and all(lab.get(k) == str(w)
                                    for k, w in labels.items()))


def delta(before: dict, after: dict, name: str, **labels) -> float:
    return value(after, name, **labels) - value(before, name, **labels)


def bucket_deltas(before: dict, after: dict, name: str) -> list[tuple[float, float]]:
    """``[(upper bound, observations in the window), ...]`` of a histogram
    family, from its cumulative ``_bucket`` rows; the last bound is inf."""
    def cumulative(snap):
        out = {}
        for n, lab, v in snap["metrics"]:
            if n == f"{name}_bucket":
                le = float("inf") if lab["le"] == "+Inf" else float(lab["le"])
                out[le] = out.get(le, 0.0) + v
        return out
    b, a = cumulative(before), cumulative(after)
    out, prev = [], 0.0
    for le in sorted(a):
        cum = a[le] - b.get(le, 0.0)
        out.append((le, cum - prev))
        prev = cum
    return out


def bucket_quantile(buckets: list[tuple[float, float]], q: float) -> float | None:
    """The ``q`` quantile of a bucketed histogram, linear inside the bucket
    it falls in (so it is an estimate, as fine as the buckets)."""
    total = sum(c for _, c in buckets)
    if total <= 0:
        return None
    want, seen, lo = q * total, 0.0, 0.0
    for le, c in buckets:
        if c > 0 and seen + c >= want:
            hi = le if le != float("inf") else lo
            return lo + (hi - lo) * (want - seen) / c
        seen += c
        lo = le if le != float("inf") else lo
    return lo
