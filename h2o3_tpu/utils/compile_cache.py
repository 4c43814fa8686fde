"""Persistent XLA compilation cache — one switch, observable hit/miss counts.

The standard TPU production setup: JAX's persistent compilation cache keeps
compiled executables across processes, so repeated same-shape programs
(an AutoML leaderboard's many model configs, every serving cold start)
stop paying compile time.

Where the cache lives is decided OUTSIDE the code, by one rule:

- ``JAX_COMPILATION_CACHE_DIR`` set → JAX already reads it; :func:`enable`
  sets no directory and only reports it in ``stats()["dir"]``;
- otherwise → ``<checkout>/.jax_cache``, derived from this package's own
  location (a fixed path: the path is part of the cache key's context, so a
  directory that moves — a home directory, a temp name, a pid — never hits).

Whether it is on is ``H2O3TPU_COMPILE_CACHE``: unset → the caller's default
(``chip_smoke.py`` and ``benchmark/run.py`` pass ``default_on=True``; session init
and the launcher leave it off), ``0``/``off`` → disabled, ``1``/``on`` →
enabled. Any other value is an error — the directory is not this variable's
business.

Hit/miss counts come from JAX's own monitoring events
(``/jax/compilation_cache/cache_hits`` / ``cache_misses``; a miss is
recorded when an entry is written, so programs under JAX's minimum compile
time count as neither); :func:`stats` snapshots them plus the on-disk entry
count.

**First calls.** The same set of listeners (:func:`listen`: registered once
a process, at ``utils/costs.py``'s import or :func:`enable`, whichever comes
first, so it is on with the persistent cache off) records what a first call
pays: JAX reports tracing, lowering and the backend's compile-or-load as
spans with the function's name (``/jax/core/compile/jaxpr_trace_duration``,
``jaxpr_to_mlir_module_duration``, ``backend_compile_duration``). A span is
counted only if no other of these spans on its thread contains it (JAX fires
a trace event a NESTED jit too, and a lowering rule may trace: those seconds
are the outer span's), so the stages' seconds of a call never sum past its
wall. They go to ``h2o3_first_call_seconds_total{phase, stage}``; a backend
span is one *executable request*, counted in ``h2o3_executables_total{phase,
source}`` (``cache`` when a cache hit fell inside it, else ``compiler``) and
as one row of ``COSTS.snapshot()["first_calls"]`` by ``(phase, fun_name)``
with the trace and lower seconds of the SAME function that preceded it on
that thread (a trace that ends in no request, as ``jax.eval_shape``'s does,
is counted in the seconds and booked to no other function's row). The phase
is the innermost open ``timed_event`` (``utils/timeline.py`` ``PHASE``), else
:data:`OUTSIDE`. A steady build fires no event, so a measured window pays
nothing.
"""

from __future__ import annotations

import os
import threading

from h2o3_tpu.utils import telemetry as _tm
from h2o3_tpu.utils.timeline import OUTSIDE, PHASE

_lock = threading.Lock()
_state = {"enabled": False, "dir": None, "hits": 0, "misses": 0,
          "listener": False, "by_site": {}}

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}

_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "backend"}


class _Thread(threading.local):
    """What one thread's listeners carry from event to event."""

    depth = 0             # compile spans open on this thread
    trace = ("", 0.0)     # the last outermost trace span: (fun_name, seconds)
    lower = ("", 0.0)     # the last outermost lowering: ("jit(fun_name)", s)
    hit = False           # a cache hit since the last executable request


_thread = _Thread()
#: the CostMeter that keeps the by-function table and names the active site;
#: ``utils/costs.py`` hands it to :func:`listen` at its import
_meter = None


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the package, identical for every
    process that imports this checkout."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def _on_event(event: str, **_kw) -> None:
    kind = _EVENTS.get(event)
    if kind is None:
        return
    if kind == "hits":
        _thread.hit = True
    # per-site attribution: the CostMeter site scope active at compile time
    # (an AccountedJit AOT compile, a builder's fit scope) names which loop
    # hit/missed the persistent cache
    site = (_meter and _meter.active_site()) or "(unattributed)"
    with _lock:
        _state[kind] += 1
        per = _state["by_site"].setdefault(site, {"hits": 0, "misses": 0})
        per[kind] += 1


def _on_span_start(event: str, _value=None, **_kw) -> None:
    """JAX records a scalar where one of its compile spans opens."""
    if event in _STAGES:
        _thread.depth += 1


def _on_span(event: str, start: float, end: float, fun_name=None,
             **_kw) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    t = _thread
    inside = t.depth = max(t.depth - 1, 0)
    if inside and stage != "backend":
        return        # a span inside another: its seconds are the outer one's
    seconds = max(end - start, 0.0)
    phase = PHASE.get() or OUTSIDE
    name = str(fun_name)
    if not inside:
        _tm.FIRST_CALL_SECONDS.labels(phase=phase, stage=stage).inc(seconds)
    if stage == "trace":
        t.trace = (name, seconds)
    elif stage == "lower":
        t.lower = (name, seconds)
    else:
        # JAX names a trace `f` and its lowering and request `jit(f)`: only
        # the same function's seconds go to this request's row
        hit, t.hit = t.hit, False
        (traced, trace), t.trace = t.trace, ("", 0.0)
        (lowered, lower), t.lower = t.lower, ("", 0.0)
        _tm.EXECUTABLES.labels(
            phase=phase, source="cache" if hit else "compiler").inc()
        if _meter is not None:
            _meter.record_first_call(
                phase, name,
                trace if name.endswith(f"({traced})") else 0.0,
                lower if name == lowered else 0.0, seconds, hit)


def listen(meter=None) -> None:
    """Register the one set of listeners; every later call registers
    nothing. ``meter`` is the CostMeter to keep the table in."""
    global _meter
    with _lock:
        if meter is not None:
            _meter = meter
        if _state["listener"]:
            return
        _state["listener"] = True
    import jax
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_span_start)
    jax.monitoring.register_event_time_span_listener(_on_span)


def enable(*, default_on: bool = False) -> bool:
    """Turn the persistent compile cache on per the policy above. Returns
    True when the cache is active. Idempotent."""
    env = os.environ.get("H2O3TPU_COMPILE_CACHE", "").strip().lower()
    if env in ("0", "off", "false"):
        return False
    if env and env not in ("1", "on", "true"):
        raise ValueError(
            f"H2O3TPU_COMPILE_CACHE={env!r}: expected 0/off or 1/on — place "
            "the cache with JAX_COMPILATION_CACHE_DIR")
    if not env and not default_on:
        return False
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not cache_dir:
        cache_dir = default_dir()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    with _lock:
        _state["enabled"] = True
        _state["dir"] = cache_dir
    listen()
    return True


def stats() -> dict:
    """{enabled, dir, entries, hits, misses, by_site} — ``entries`` counts
    on-disk cache files (an absolute view; hits/misses are this process
    only, ``by_site`` splits them by the CostMeter site active at compile
    time)."""
    with _lock:
        out = {"enabled": _state["enabled"], "dir": _state["dir"],
               "hits": _state["hits"], "misses": _state["misses"],
               "by_site": {k: dict(v)
                           for k, v in _state["by_site"].items()}}
    entries = 0
    if out["dir"]:
        try:
            entries = sum(1 for _ in os.scandir(out["dir"]))
        except OSError:
            pass
    out["entries"] = entries
    return out
