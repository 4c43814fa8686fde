"""Compute observatory — XLA cost accounting, recompile attribution, MFU.

Every timing elsewhere in the stack is host wall-clock; this module is the
fourth observability pillar (after metrics, traces, and memory): it answers
*what fraction of the hardware are we using, and where did the compile time
go* — automatically, instead of by redoing ROOFLINE.md's FLOP arithmetic by
hand.

Three pieces:

- :class:`AccountedJit` (via :func:`accounted_jit`) — a drop-in for
  ``jax.jit`` at the host-dispatched compile sites (serving scorers, GLM/DL
  megasteps, the GBM tree program, ``map_reduce`` collectives). It compiles
  ahead-of-time (``jit().lower().compile()``), so every compile is observed:
  the wrapper holds one executable per **signature** (static values + dynamic
  shapes/dtypes/shardings) and records, per logical *site*, the signature,
  the compile wall time, and the executable's ``cost_analysis()`` FLOPs /
  bytes. When a site compiles a *second* signature the :class:`CostMeter`
  records a **recompile event** with the signature diff (which dim / dtype /
  device set / static changed) — recompile attribution becomes a live table
  instead of forensic archaeology.
- :class:`CostMeter` (``COSTS``) — the process-wide registry behind
  ``GET /3/Compute``. Sampled execution probes (the wrapper's own, or the
  ``map_reduce`` dispatch probe feeding :meth:`CostMeter.observe`) combine
  the recorded FLOPs with measured wall time into achieved FLOP/s and
  bytes/s per loop, rated against :data:`PEAK_TABLE` —
  ``h2o3_compute_utilization{loop}`` plus arithmetic-intensity / roofline
  gauges. Unknown backends (this CPU-only container) report utilization as
  ``None``, never 0 and never an exception.
- the **site scope** (:meth:`CostMeter.scope`) — a contextvar naming the
  logical site active at compile time, consulted by the persistent
  compile-cache listeners (``utils/compile_cache.py``) so cache hits/misses
  credit the loop that caused them.

Always-on and host-side: the per-call overhead is a pytree flatten + dict
lookup (~µs); the only device syncs are on SAMPLED calls (every
``H2O3TPU_COSTS_SAMPLE``-th, first always), exactly like the ``map_reduce``
dispatch probe. ``H2O3TPU_COSTS_OFF=1`` bypasses the wrapper entirely
(plain ``jax.jit`` dispatch, nothing recorded).
"""

from __future__ import annotations

import contextlib
import contextvars
import inspect
import itertools
import os
import sys
import threading
import time
import weakref
from collections import OrderedDict

from h2o3_tpu.utils import compile_cache, tracing

#: logical site active for compile attribution (innermost scope wins)
_SITE: contextvars.ContextVar["str | None"] = \
    contextvars.ContextVar("h2o3_cost_site", default=None)


def enabled() -> bool:
    """Cost accounting on? (``H2O3TPU_COSTS_OFF=1`` disables; read per call
    so tests can flip it at runtime.)"""
    return os.environ.get("H2O3TPU_COSTS_OFF", "") != "1"


def sample_every() -> int:
    """Execution-probe sampling period (``H2O3TPU_COSTS_SAMPLE``, default
    16; the first call per wrapper always samples so short sessions still
    measure something — same contract as the map_reduce dispatch probe)."""
    try:
        return max(int(os.environ.get("H2O3TPU_COSTS_SAMPLE", "") or 16), 1)
    except ValueError:
        return 16


# ---------------------------------------------------------------------------
# Per-backend peak table. Provenance: the v5e numbers are ROOFLINE.md's
# (~819 GB/s HBM measured there; 197 TFLOP/s bf16 is the published chip
# peak the MFU in that document rates against); other generations are the
# published per-chip peaks. Keyed by substring of `device.device_kind`
# (lowercased) — "TPU v5 lite" and "TPU v5e" both resolve to the v5e row.
# An unmatched kind (CPU, GPU, future chips) yields None: utilization is
# then reported as null, NEVER 0 and never an exception.

PEAK_TABLE = (
    ("v5 lite", {"name": "TPU v5e", "flops_per_sec": 197e12,
                 "hbm_bytes_per_sec": 819e9}),
    ("v5e", {"name": "TPU v5e", "flops_per_sec": 197e12,
             "hbm_bytes_per_sec": 819e9}),
    ("v5p", {"name": "TPU v5p", "flops_per_sec": 459e12,
             "hbm_bytes_per_sec": 2765e9}),
    ("v6", {"name": "TPU v6e", "flops_per_sec": 918e12,
            "hbm_bytes_per_sec": 1640e9}),
    ("v4", {"name": "TPU v4", "flops_per_sec": 275e12,
            "hbm_bytes_per_sec": 1228e9}),
    ("v3", {"name": "TPU v3", "flops_per_sec": 123e12,
            "hbm_bytes_per_sec": 900e9}),
    ("v2", {"name": "TPU v2", "flops_per_sec": 46e12,
            "hbm_bytes_per_sec": 700e9}),
)

_peak_cache: "dict[str, dict | None]" = {}


def backend_peak(device_kind: str | None = None) -> dict | None:
    """Peak {name, flops_per_sec, hbm_bytes_per_sec} for the (default)
    backend's device kind, or None when the kind is not in the table (a
    CPU container, an unknown accelerator). Peaks are bf16 MXU peaks —
    utilization is MFU against the bf16 peak, the convention ROOFLINE.md's
    hand accounting used."""
    if device_kind is None:
        try:
            import jax
            device_kind = jax.devices()[0].device_kind
        except Exception:   # noqa: BLE001 — no backend → no peak
            return None
    kind = str(device_kind).lower()
    if kind not in _peak_cache:
        _peak_cache[kind] = next(
            (row for sub, row in PEAK_TABLE if sub in kind), None)
    return _peak_cache[kind]


# ---------------------------------------------------------------------------
# Signatures: canonical hashable keys + human-readable descriptors + diffs.


def _leaf_key(x):
    """Hashable signature component for one dynamic pytree leaf: shape /
    dtype / sharding for arrays, value-independent type name for Python
    scalars (they trace as weak-typed scalars — the value never forces a
    recompile, so it must not split the signature)."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), str(x.dtype), getattr(x, "sharding", None))
    return (type(x).__name__,)


def _leaf_descr(x) -> dict:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        d = {"shape": list(x.shape), "dtype": str(x.dtype)}
        sh = getattr(x, "sharding", None)
        devs = getattr(sh, "device_set", None) if sh is not None else None
        if devs:
            d["devices"] = sorted(getattr(dv, "id", -1) for dv in devs)
        return d
    return {"scalar": type(x).__name__}


def signature_diff(old: dict, new: dict) -> list[str]:
    """Human-readable per-component diff between two recorded signatures —
    the payload of a recompile event: WHICH dimension / dtype / device set /
    static argument changed. ``old``/``new`` are the ``signature`` dicts
    :meth:`CostMeter.record_compile` stores ({"args": [...], "statics": {}}).
    """
    out: list[str] = []
    oa, na = old.get("args", []), new.get("args", [])
    if len(oa) != len(na):
        out.append(f"arg count: {len(oa)} -> {len(na)}")
    for i, (a, b) in enumerate(zip(oa, na)):
        if a == b:
            continue
        if "shape" in a and "shape" in b:
            sa, sb = a["shape"], b["shape"]
            if len(sa) != len(sb):
                out.append(f"arg{i}.rank: {len(sa)} -> {len(sb)}")
            else:
                for d, (x, y) in enumerate(zip(sa, sb)):
                    if x != y:
                        out.append(f"arg{i}.shape[{d}]: {x} -> {y}")
            if a.get("dtype") != b.get("dtype"):
                out.append(f"arg{i}.dtype: {a.get('dtype')} -> "
                           f"{b.get('dtype')}")
            if a.get("devices") != b.get("devices"):
                out.append(f"arg{i}.devices: {a.get('devices')} -> "
                           f"{b.get('devices')}")
        else:
            out.append(f"arg{i}: {a} -> {b}")
    os_, ns = old.get("statics", {}), new.get("statics", {})
    for k in sorted(set(os_) | set(ns)):
        if os_.get(k) != ns.get(k):
            out.append(f"static {k}: {os_.get(k)} -> {ns.get(k)}")
    return out or ["signature structure changed"]


def cost_of(compiled) -> tuple[float | None, float | None]:
    """(flops, bytes accessed) from an executable's ``cost_analysis()``;
    (None, None) when the backend doesn't provide it."""
    try:
        ca = compiled.cost_analysis()
    except (NotImplementedError, RuntimeError):   # optional on some backends
        return None, None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(nbytes) if nbytes is not None else None)


# ---------------------------------------------------------------------------
# The registry.

#: recompile events kept per site / process-wide cap on stored signatures
MAX_SIGNATURES_PER_SITE = 32
MAX_RECOMPILE_EVENTS = 64
#: rows of the first-call table, by (phase, fun_name); least seconds go first
MAX_FIRST_CALL_ROWS = 256


class CostMeter:
    """Process-wide per-site compile/cost registry (``GET /3/Compute``)."""

    def __init__(self):
        self._lock = threading.Lock()
        # site -> {"loop": str|None, "signatures": OrderedDict[key, rec],
        #          "recompiles": [event], "compiles": int,
        #          "compile_seconds": float}
        self._sites: "OrderedDict[str, dict]" = OrderedDict()
        # loop -> {"samples": int, "achieved_flops_per_sec": float, ...}
        self._loops: dict[str, dict] = {}
        # (phase, fun_name) -> [requests, trace s, lower s, backend s, hits]
        self._first_calls: dict[tuple, list] = {}
        self._wrappers: "weakref.WeakSet[AccountedJit]" = weakref.WeakSet()

    # -- site scope (compile-cache attribution) ------------------------------

    @contextlib.contextmanager
    def scope(self, site: str):
        """Name the logical site active for compile attribution in this
        context (innermost wins). The persistent compile-cache listeners
        read it at event time (``utils/compile_cache.py``)."""
        token = _SITE.set(site)
        try:
            yield
        finally:
            _SITE.reset(token)

    @staticmethod
    def active_site() -> str | None:
        return _SITE.get()

    # -- recording -----------------------------------------------------------

    def _site_locked(self, site: str, loop: str | None) -> dict:
        rec = self._sites.get(site)
        if rec is None:
            # graftlint: ok(_locked suffix: every caller holds self._lock)
            rec = self._sites[site] = {
                "loop": loop, "signatures": OrderedDict(), "recompiles": [],
                "compiles": 0, "compile_seconds": 0.0}
        elif loop is not None and rec["loop"] is None:
            rec["loop"] = loop
        return rec

    def record_compile(self, site: str, signature: dict, seconds: float,
                       flops: float | None, nbytes: float | None,
                       loop: str | None = None, key=None) -> None:
        """One observed compile at ``site``. ``signature`` is the
        {"args": [...], "statics": {...}} descriptor; ``key`` its canonical
        hashable form (a repr of the descriptor when omitted). A compile of
        an already-recorded signature (fresh-lambda churn, an executable
        cache cleared between test modules) increments counts but is NOT a
        recompile event; a genuinely new second+ signature is."""
        from h2o3_tpu.utils import telemetry as _tm
        key = key if key is not None else repr(signature)
        with self._lock:
            rec = self._site_locked(site, loop)
            rec["compiles"] += 1
            rec["compile_seconds"] = round(
                rec["compile_seconds"] + float(seconds), 6)
            known = key in rec["signatures"]
            if not known:
                prev = next(reversed(rec["signatures"].values()), None)
                rec["signatures"][key] = {
                    "signature": signature, "compile_seconds": round(
                        float(seconds), 6),
                    "flops": flops, "bytes": nbytes,
                    "compiles": 1}
                while len(rec["signatures"]) > MAX_SIGNATURES_PER_SITE:
                    rec["signatures"].popitem(last=False)
                if prev is not None:
                    rec["recompiles"].append({
                        "site": site,
                        "from": prev["signature"], "to": signature,
                        "diff": signature_diff(prev["signature"], signature),
                        "compile_seconds": round(float(seconds), 6)})
                    del rec["recompiles"][:-MAX_RECOMPILE_EVENTS]
            else:
                rec["signatures"][key]["compiles"] += 1
                rec["signatures"].move_to_end(key)
            recompiled = (not known) and len(rec["signatures"]) > 1
        _tm.COMPILES.labels(site=site).inc()
        _tm.COMPILE_SECONDS.labels(site=site).inc(float(seconds))
        if recompiled:
            _tm.RECOMPILES.labels(site=site).inc()

    def record_first_call(self, phase: str, fun_name: str, trace: float,
                          lower: float, backend: float, hit: bool) -> None:
        """One executable request (``utils/compile_cache.py``'s listener on
        JAX's ``backend_compile_duration``): the backend's seconds (a load
        on a cache hit, a compile otherwise) with the trace and lower
        seconds of the same function that preceded it on its thread, under
        the program's phase: the one per-function record of the stages.
        The table is bounded; the row with the least seconds goes first."""
        with self._lock:
            row = self._first_calls.get((phase, fun_name))
            if row is None:
                if len(self._first_calls) >= MAX_FIRST_CALL_ROWS:
                    del self._first_calls[min(
                        self._first_calls,
                        key=lambda k: sum(self._first_calls[k][1:4]))]
                row = self._first_calls[(phase, fun_name)] = [0, 0.0, 0.0,
                                                              0.0, 0]
            row[0] += 1
            row[1] += trace
            row[2] += lower
            row[3] += backend
            row[4] += bool(hit)

    def latest_cost(self, site: str) -> tuple[float | None, float | None]:
        """(flops, bytes) of the site's most recently compiled signature —
        the fallback when the caller cannot name which signature ran."""
        with self._lock:
            rec = self._sites.get(site)
            if rec is None:
                return None, None
            sig = next(reversed(rec["signatures"].values()), None)
            if sig is None:
                return None, None
            return sig["flops"], sig["bytes"]

    def cost_for(self, site: str, key) -> tuple[float | None, float | None]:
        """(flops, bytes) of one SPECIFIC recorded signature, so a sampled
        probe attributes the cost of the program that actually ran — a site
        holding several live signatures (full GBM chunk + remainder chunk,
        wide + narrow IRLS) must not rate one signature's wall time against
        another's FLOPs. (None, None) when evicted/unknown."""
        with self._lock:
            rec = self._sites.get(site)
            sig = rec["signatures"].get(key) if rec is not None else None
            if sig is None:
                return None, None
            return sig["flops"], sig["bytes"]

    # -- execution probes → achieved FLOP/s / roofline gauges ----------------

    def observe(self, site: str, seconds: float,
                flops: float | None = None,
                nbytes: float | None = None) -> None:
        """Fold one SAMPLED, synced execution of ``site``'s program (wall
        ``seconds``) into the per-loop achieved-throughput view. Cost
        defaults to the site's most recent signature (the ``map_reduce``
        dispatch probe calls this with its own measured duration). Unknown
        backends publish achieved FLOP/s but no utilization gauge — the
        REST view reports utilization null there."""
        if seconds <= 0:
            return
        from h2o3_tpu.utils import telemetry as _tm
        if flops is None:
            flops, nbytes = self.latest_cost(site)
        if flops is None or flops <= 0:
            return
        achieved = flops / seconds
        achieved_b = (nbytes / seconds) if nbytes else None
        intensity = (flops / nbytes) if nbytes else None
        peak = backend_peak()
        util = (achieved / peak["flops_per_sec"]) if peak else None
        with self._lock:
            loop = (self._sites.get(site) or {}).get("loop") or site
            st = self._loops.setdefault(loop, {"samples": 0})
            st["samples"] += 1
            st["achieved_flops_per_sec"] = round(achieved, 1)
            st["achieved_bytes_per_sec"] = (round(achieved_b, 1)
                                            if achieved_b else None)
            st["arithmetic_intensity"] = (round(intensity, 3)
                                          if intensity else None)
            st["utilization"] = round(util, 6) if util is not None else None
            if peak and intensity is not None:
                ridge = peak["flops_per_sec"] / peak["hbm_bytes_per_sec"]
                st["roofline"] = ("compute-bound" if intensity >= ridge
                                  else "memory-bound")
            else:
                st["roofline"] = None
        _tm.ACHIEVED_FLOPS.labels(loop=loop).set(achieved)
        if achieved_b is not None:
            _tm.ACHIEVED_BYTES.labels(loop=loop).set(achieved_b)
        if intensity is not None:
            _tm.ARITH_INTENSITY.labels(loop=loop).set(intensity)
        if util is not None:
            _tm.COMPUTE_UTILIZATION.labels(loop=loop).set(util)
        # per-slice achieved-FLOPs fold into the PR 9 mesh_slices view —
        # only when the scheduler is actually loaded (no import cost here)
        sched = sys.modules.get("h2o3_tpu.orchestration.scheduler")
        if sched is not None:
            label = sched.active_slice_label()
            if label is not None:
                sched.SLICE_STATS.add_flops(label, flops)

    # -- views ---------------------------------------------------------------

    def snapshot(self) -> dict:
        """The ``GET /3/Compute`` payload: per-site compiles / signatures /
        costs / recompile events, per-loop achieved throughput + roofline
        position, and the backend peak row (null on unknown backends)."""
        peak = backend_peak()
        try:
            import jax
            kind = jax.devices()[0].device_kind
            backend = jax.default_backend()
        except Exception:   # noqa: BLE001
            kind = backend = None
        with self._lock:
            sites = []
            for name, rec in self._sites.items():
                sigs = list(rec["signatures"].values())
                sites.append({
                    "site": name, "loop": rec["loop"],
                    "compiles": rec["compiles"],
                    "compile_seconds": rec["compile_seconds"],
                    "flops": next((s["flops"] for s in reversed(sigs)
                                   if s["flops"] is not None), None),
                    "bytes": next((s["bytes"] for s in reversed(sigs)
                                   if s["bytes"] is not None), None),
                    "signatures": [dict(s) for s in sigs],
                    "recompile_events": [dict(e) for e in rec["recompiles"]],
                })
            loops = {k: dict(v) for k, v in self._loops.items()}
            first = sorted(self._first_calls.items(),
                           key=lambda kv: -sum(kv[1][1:4]))
        return {"backend": backend, "device_kind": kind,
                "peak": dict(peak) if peak else None,
                "sites": sites, "loops": loops,
                "first_calls": [
                    {"phase": phase, "fun_name": fun, "requests": n,
                     "trace_seconds": round(tr, 6),
                     "lower_seconds": round(lo, 6),
                     "backend_seconds": round(be, 6), "cache_hits": hits}
                    for (phase, fun), (n, tr, lo, be, hits) in first],
                "signature_count": sum(len(s["signatures"]) for s in sites),
                "recompile_events": sum(len(s["recompile_events"])
                                        for s in sites)}

    def loops(self) -> dict:
        """Per-loop achieved-throughput rows only (utilization / samples /
        roofline) — the health evaluator's MFU-collapse probe reads this
        every sweep, so it must not pay :meth:`snapshot`'s full per-site
        signature copy."""
        with self._lock:
            return {k: dict(v) for k, v in self._loops.items()}

    def signature_count(self) -> int:
        """Total distinct signatures across sites — a steady-state
        recompile probe: a warm re-run must not grow this
        (``benchmark/counters.py`` reads it around a window)."""
        with self._lock:
            return sum(len(r["signatures"]) for r in self._sites.values())

    def recompile_count(self) -> int:
        with self._lock:
            return sum(len(r["recompiles"]) for r in self._sites.values())

    # -- lifecycle -----------------------------------------------------------

    def _register_wrapper(self, w: "AccountedJit") -> None:
        self._wrappers.add(w)

    def clear_executables(self) -> None:
        """Drop every wrapper's held executables (recorded costs stay).
        Called alongside ``jax.clear_caches()`` between test modules — the
        AOT handles the wrappers hold are live XLA executables the global
        cache clear cannot see."""
        for w in list(self._wrappers):
            w.clear_executables()

    def clear(self) -> None:
        """Tests only: drop every record AND held executable (so a
        rebuilt-same-shape scenario re-records from a clean slate)."""
        self.clear_executables()
        with self._lock:
            self._sites.clear()
            self._loops.clear()
            self._first_calls.clear()


COSTS = CostMeter()

# what a first call pays is recorded from here on, with the persistent cache
# on or off (compile_cache.enable() may come first; never twice)
compile_cache.listen(COSTS)


# ---------------------------------------------------------------------------
# The accounted jit wrapper.

_MAX_EXECUTABLES = 64


class AccountedJit:
    """``jax.jit`` with per-signature AOT compilation and cost accounting.

    One executable per (static values, dynamic tree structure, per-leaf
    shape/dtype/sharding); compiles happen through
    ``jit().lower().compile()`` under the site scope so compile time, FLOPs
    and bytes are recorded per site. Calls whose leaves are tracers (the
    site nested inside another jit trace) and calls under
    ``H2O3TPU_COSTS_OFF=1`` fall through to the plain jit path unchanged.
    """

    def __init__(self, site: str, fun, *, static_argnames=(),
                 donate_argnums=(), loop: str | None = None,
                 sample: bool = True):
        import jax
        self.site = site
        self.loop = loop
        self._fun = fun
        self._jit = jax.jit(fun, static_argnames=tuple(static_argnames),
                            donate_argnums=tuple(donate_argnums))
        self._static = frozenset(static_argnames)
        self._param_names: "list[str] | None" = None
        if self._static:
            try:
                self._param_names = [
                    p.name for p in
                    inspect.signature(fun).parameters.values()]
            except (ValueError, TypeError):   # C callables, odd wrappers
                self._param_names = None
        self._sample = sample
        self._calls = itertools.count()
        self._lock = threading.Lock()
        self._compiled: "OrderedDict[tuple, object]" = OrderedDict()
        self._last_key = None
        COSTS._register_wrapper(self)

    # functools.wraps-ish surface so callers can introspect
    @property
    def __name__(self):
        return getattr(self._fun, "__name__", self.site)

    def clear_executables(self) -> None:
        with self._lock:
            self._compiled.clear()

    def executables(self) -> list:
        """The compiled programs this wrapper holds, oldest first — what
        actually ran, for inspection (``as_text()``, ``memory_analysis()``)."""
        with self._lock:
            return list(self._compiled.values())

    def lower(self, *args, **kwargs):
        """AOT escape hatch — delegate to the underlying ``jax.jit``'s
        ``lower`` for diagnostic compiles (the entry point's comm-volume
        audit inspects the HLO this way). Compiles made through it bypass
        the wrapper's executable cache and are not accounted."""
        return self._jit.lower(*args, **kwargs)

    def last_cost(self) -> tuple[float | None, float | None]:
        """(flops, bytes) of the most recently dispatched signature — the
        ``map_reduce`` dispatch probe reads this so its sampled duration is
        rated against the program that actually ran, not the site's most
        recent compile."""
        key = self._last_key
        if key is None:
            return None, None
        return COSTS.cost_for(self.site, key)

    # -- call path -----------------------------------------------------------

    def _split(self, args, kwargs):
        """(statics, dyn_args, dyn_kwargs) or None when the statics cannot
        be mapped to positions (vararg functions with statics — none of the
        instrumented sites, but fail safe to the jit path)."""
        if not self._static:
            return (), args, kwargs
        names = self._param_names
        if names is None or len(args) > len(names):
            return None
        statics, dyn_args = [], []
        for i, a in enumerate(args):
            if names[i] in self._static:
                statics.append((names[i], a))
            else:
                dyn_args.append(a)
        dyn_kwargs = {}
        for k, v in kwargs.items():
            if k in self._static:
                statics.append((k, v))
            else:
                dyn_kwargs[k] = v
        return (tuple(sorted(statics)), tuple(dyn_args), dyn_kwargs)

    def __call__(self, *args, **kwargs):
        import jax
        if not enabled():
            return self._jit(*args, **kwargs)
        split = self._split(args, kwargs)
        if split is None:
            return self._jit(*args, **kwargs)
        statics, dyn_args, dyn_kwargs = split
        leaves, treedef = jax.tree.flatten((dyn_args, dyn_kwargs))
        if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
            # nested inside another trace: the outer program owns the
            # compile; calling an executable with tracers would throw
            return self._jit(*args, **kwargs)
        try:
            key = (statics, treedef, tuple(_leaf_key(x) for x in leaves))
            hash(key)
        except TypeError:        # unhashable static/sharding: unaccountable
            return self._jit(*args, **kwargs)
        with self._lock:
            entry = self._compiled.get(key)
            if entry is not None:
                self._compiled.move_to_end(key)
        if entry is None:
            entry = self._compile(key, statics, leaves, args, kwargs)
        self._last_key = key      # unsynchronized: observability-only hint
        n = next(self._calls)
        if self._sample and (n == 0 or n % sample_every() == 0):
            t0 = time.perf_counter()
            out = entry(*dyn_args, **dyn_kwargs)
            out = jax.block_until_ready(out)  # graftlint: ok(sampled achieved-FLOPs probe — the sync is the measurement)
            dt = time.perf_counter() - t0
            # the EXECUTED signature's cost, not the site's latest compile
            flops, nbytes = COSTS.cost_for(self.site, key)
            COSTS.observe(self.site, dt, flops=flops, nbytes=nbytes)
            return out
        return entry(*dyn_args, **dyn_kwargs)

    def _compile(self, key, statics, leaves, args, kwargs):
        # a trace or compiler error surfaces here, ONCE: the plain jit path
        # would trace and compile the same program into the same failure
        # an annotation of its own, so that a recompile inside ANY profiler
        # session is a host event beside the gap it caused
        ann = tracing.annotation(f"compile:{self.site}")
        try:
            with COSTS.scope(self.site):
                t0 = time.perf_counter()
                compiled = self._jit.lower(*args, **kwargs).compile()
                dt = time.perf_counter() - t0
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
        flops, nbytes = cost_of(compiled)
        signature = {"args": [_leaf_descr(x) for x in leaves],
                     "statics": {k: repr(v) for k, v in statics}}
        COSTS.record_compile(self.site, signature, dt, flops, nbytes,
                             loop=self.loop, key=key)
        with self._lock:
            won = self._compiled.setdefault(key, compiled)
            while len(self._compiled) > _MAX_EXECUTABLES:
                self._compiled.popitem(last=False)
        return won


def accounted_jit(site: str, fun=None, *, static_argnames=(),
                  donate_argnums=(), loop: str | None = None,
                  sample: bool = True):
    """``jax.jit`` replacement that registers the executable with the
    compute observatory under ``site`` (decorator or direct form)::

        @accounted_jit("glm:irls_megastep", static_argnames=("k",),
                       loop="glm_irls")
        def _irls_megastep(...): ...
    """
    if fun is None:
        return lambda f: AccountedJit(site, f,
                                      static_argnames=static_argnames,
                                      donate_argnums=donate_argnums,
                                      loop=loop, sample=sample)
    return AccountedJit(site, fun, static_argnames=static_argnames,
                        donate_argnums=donate_argnums, loop=loop,
                        sample=sample)
