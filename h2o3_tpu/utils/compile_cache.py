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
time count as neither), registered once at enable time; :func:`stats`
snapshots them plus the on-disk entry count.
"""

from __future__ import annotations

import os
import threading

_lock = threading.Lock()
_state = {"enabled": False, "dir": None, "hits": 0, "misses": 0,
          "listener": False, "by_site": {}}

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the package, identical for every
    process that imports this checkout."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def _on_event(event: str, **_kw) -> None:
    kind = _EVENTS.get(event)
    if kind is None:
        return
    # per-site attribution: the CostMeter site scope active at compile time
    # (an AccountedJit AOT compile, a builder's fit scope) names which loop
    # hit/missed the persistent cache
    from h2o3_tpu.utils.costs import COSTS
    site = COSTS.active_site() or "(unattributed)"
    with _lock:
        _state[kind] += 1
        per = _state["by_site"].setdefault(site, {"hits": 0, "misses": 0})
        per[kind] += 1


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
        if not _state["listener"]:
            jax.monitoring.register_event_listener(_on_event)
            _state["listener"] = True
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
