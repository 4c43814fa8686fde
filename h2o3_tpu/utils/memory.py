"""Memory observability — device/host byte accounting with attribution.

Reference: H2O-3's substrate is *in-memory*, so the reference meters its heap
everywhere — ``water/H2O.java`` CloudV3 ``free_mem``/``max_mem``/``pojo_mem``
per node, ``water/MemoryManager.java`` budgeting the K/V store, and the
``WaterMeter*`` handlers. An in-memory ML platform dies by OOM, not by crash.
On TPUs the gap is sharper: device HBM is the scarce resource, and JAX exposes
``device.memory_stats()`` precisely so frameworks can meter it.

The :class:`MemoryMeter` accounts bytes at three levels:

1. **Per-DKV-key** — frames report summed chunk ``nbytes`` (``Vec.nbytes`` /
   ``Frame.nbytes``), models report artifact size (the byte total of their
   array tree), raw uploads their payload length. Registered at
   ``DKV.put``/``remove`` so the ``h2o3_dkv_bytes{kind}`` gauges and the
   top-N-keys view are always current.
2. **Per-process/device** — host RSS sampled from ``/proc/self/status``
   plus ``device.memory_stats()`` per JAX device, with a graceful fallback
   to live-array accounting (``jax.live_arrays()``) on backends without
   stats (CPU). Monotonic high-water marks are kept for both.
3. **Per-span** — model builds and ``map_reduce`` dispatches record
   device-byte peaks/deltas as span attrs through the existing
   ``timed_event``/tracing hooks (see :mod:`h2o3_tpu.utils.timeline` and
   :mod:`h2o3_tpu.ops.map_reduce`), so a trace tree shows *which* build ate
   HBM.

On top of the keyed accounting a **leak detector** snapshots keyed bytes
across :class:`~h2o3_tpu.utils.cleaner.Cleaner` sweeps and flags keys that
keep growing, or that stay resident above a size floor with no DKV access,
for N consecutive sweeps. Surfaced via ``GET /3/Memory``, the ``/metrics``
gauges and real numbers in ``/3/Cloud``.

Everything here is host-side stdlib bookkeeping: byte registration is a
dict write under one lock, and nothing is ever traced into an XLA program.
"""

from __future__ import annotations

import os

import numpy as np

from h2o3_tpu.utils import lockwitness
from h2o3_tpu.utils import telemetry as _tm

#: consecutive sweeps of growth / idleness before a key is flagged
LEAK_SWEEPS = int(os.environ.get("H2O3TPU_LEAK_SWEEPS", "4"))

#: keys below this byte floor are never flagged (jobs, tiny models, stubs)
LEAK_MIN_BYTES = int(os.environ.get("H2O3TPU_LEAK_MIN_BYTES", str(1 << 20)))

_KB = 1024


# ---------------------------------------------------------------------------
# Byte measurement — one definition each for frames, models, raw payloads.


def array_tree_bytes(obj, _depth: int = 0, host_only: bool = False) -> int:
    """Summed ``nbytes`` of every numpy/jax array reachable through dicts,
    lists/tuples, and plain object attributes (depth-limited like the
    persist layer's ``_to_host`` walker). The model-artifact size measure:
    coefficients, tree arrays, DL weights — without pickling anything.
    ``host_only`` counts numpy arrays but skips jax (device) arrays — the
    host-RSS attribution needed by CloudV3's heap arithmetic."""
    if _depth > 8 or obj is None:
        return 0
    nb = getattr(obj, "nbytes", None)
    if nb is not None and getattr(obj, "dtype", None) is not None:
        if host_only and not isinstance(obj, np.ndarray):
            return 0
        try:
            return int(nb)
        except TypeError:
            return 0
    if isinstance(obj, dict):
        return sum(array_tree_bytes(v, _depth + 1, host_only)
                   for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(array_tree_bytes(v, _depth + 1, host_only)
                   for v in obj)
    if isinstance(obj, (str, bytes, int, float, bool)):
        return len(obj) if isinstance(obj, bytes) else 0
    if hasattr(obj, "__dict__") and not isinstance(obj, type):
        return sum(array_tree_bytes(v, _depth + 1, host_only)
                   for v in vars(obj).values())
    return 0


def value_kind_bytes(value) -> tuple[str, int]:
    """(kind, bytes) for a DKV-resident value. Type-name dispatch (not
    isinstance) so the meter never imports frame/model modules at put time;
    models are duck-typed on their ``algo``/``output`` surface so every
    Model subclass lands in the ``model`` kind."""
    tname = type(value).__name__
    if tname == "Frame":
        # Frame.nbytes delegates back to vec_nbytes below — one definition
        # of a frame's resident bytes, so /3/Memory's per-key view can
        # never drift from what the frame reports about itself
        return "frame", int(value.nbytes)
    if tname in ("SwappedFrame", "SwappedValue"):
        # spilled to persist — zero RESIDENT bytes, but the on-disk size is
        # registered under its own kind so the /3/Memory view reconciles
        # across a sweep (bytes move frame→spilled instead of vanishing)
        return "spilled", int(getattr(value, "disk_bytes", 0) or 0)
    if tname == "RawFile":
        return "raw", len(getattr(value, "data", b"") or b"")
    if tname == "Job":
        return "job", 0
    if hasattr(value, "algo") and hasattr(value, "output"):
        # prefer the sizes stamped at build/save time: registration runs on
        # every put AND every refresh/leak sweep, and models are immutable
        # post-build — re-walking each one's object graph per sweep would
        # make frame puts O(sum of model sizes) under an HBM budget
        out = getattr(value, "output", None) or {}
        stamped = out.get("artifact_bytes") \
            or getattr(value, "artifact_file_bytes", None)
        return "model", int(stamped) if stamped else array_tree_bytes(value)
    return "other", 0


def value_host_bytes(value) -> int:
    """The host-RSS-resident portion of a DKV value: frames' host payloads
    (STR/UUID object arrays, exact TIME ms), raw upload bytes, and model
    artifacts. Device (HBM) chunk bytes are EXCLUDED — CloudV3's
    heap-shaped fields must never subtract HBM from host RSS (on the CPU
    backend device arrays do live in RSS, so this understates there, which
    only makes ``pojo_mem`` conservative)."""
    if type(value).__name__ == "Frame":
        total = 0
        for v in getattr(value, "vecs", []):
            host = getattr(v, "host_values", None)
            if host is not None:
                try:
                    total += int(host.nbytes)
                except (TypeError, AttributeError):
                    pass
            comp = getattr(v, "compressed", None)
            if comp is not None:   # compressed column payloads live in RSS
                total += int(comp.nbytes)
        return total
    kind, nbytes = value_kind_bytes(value)
    if kind == "raw":
        return nbytes
    if kind == "model":
        # a freshly-built model's arrays are jax (HBM) buffers; a loaded
        # one's are numpy — count only the numpy side as RSS-resident
        return array_tree_bytes(value, host_only=True)
    return 0


def vec_nbytes(vec) -> int:
    """One column's resident bytes: the padded device chunk (when it is
    materialized — NEVER forced: accounting must not trigger the compressed
    seam's decompress-on-access), any compressed host payload, plus any
    host-side payload (STR/UUID object arrays, exact TIME ms)."""
    total = 0
    # ``_data`` is the raw slot behind the lazily-materializing ``data``
    # property; plain attribute-carriers without it fall back to ``data``
    data = vec._data if hasattr(vec, "_data") else getattr(vec, "data", None)
    if data is not None:
        try:
            total += int(data.nbytes)
        except (TypeError, AttributeError):
            pass
    comp = getattr(vec, "compressed", None)
    if comp is not None:
        total += int(comp.nbytes)
    host = getattr(vec, "host_values", None)
    if host is not None:
        try:
            total += int(host.nbytes)
        except (TypeError, AttributeError):
            pass
    return total


# ---------------------------------------------------------------------------
# Host / device sampling.


def host_stats() -> dict:
    """Process + machine memory from /proc (reference: the per-node heap
    numbers CloudV3 serves). Keys: rss_bytes, rss_peak_bytes (VmHWM),
    total_bytes, available_bytes. Zeros when /proc is unreadable."""
    out = {"rss_bytes": 0, "rss_peak_bytes": 0,
           "total_bytes": 0, "available_bytes": 0}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    out["rss_bytes"] = int(line.split()[1]) * _KB
                elif line.startswith("VmHWM:"):
                    out["rss_peak_bytes"] = int(line.split()[1]) * _KB
    except (OSError, ValueError, IndexError):
        pass
    # containers on older kernels omit VmHWM; the current RSS is then the
    # best kernel-side floor (the meter's own monotonic watermark covers
    # the rest)
    if out["rss_peak_bytes"] < out["rss_bytes"]:
        out["rss_peak_bytes"] = out["rss_bytes"]
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    out["total_bytes"] = int(line.split()[1]) * _KB
                elif line.startswith("MemAvailable:"):
                    out["available_bytes"] = int(line.split()[1]) * _KB
    except (OSError, ValueError, IndexError):
        pass
    return out


def device_stats() -> dict:
    """Per-device HBM accounting. Primary source: ``device.memory_stats()``
    (TPU/GPU runtimes). Backends without it (CPU) fall back to live-array
    accounting — every ``jax.live_arrays()`` buffer attributed evenly over
    the devices it is sharded across. ``source`` names which path ran."""
    import jax
    devices = []
    total = peak = limit = 0
    have_stats = True
    for d in jax.devices():
        try:
            ms = d.memory_stats()
        except Exception:   # noqa: BLE001 — any backend may refuse
            ms = None
        if not ms:
            have_stats = False
            break
        in_use = int(ms.get("bytes_in_use", 0))
        d_peak = int(ms.get("peak_bytes_in_use", in_use))
        d_limit = int(ms.get("bytes_limit", 0))
        devices.append({"device": str(d), "bytes_in_use": in_use,
                        "peak_bytes_in_use": d_peak, "bytes_limit": d_limit})
        total += in_use
        peak += d_peak
        limit += d_limit
    if have_stats:
        return {"source": "memory_stats", "bytes_in_use": total,
                "peak_bytes_in_use": peak, "bytes_limit": limit,
                "devices": devices}
    per: dict[str, int] = {}
    total = 0
    for a in jax.live_arrays():
        try:
            n = int(a.nbytes)
            ds = [str(d) for d in a.devices()]
        except Exception:   # noqa: BLE001 — deleted/donated buffers race
            continue
        total += n
        if ds:
            share = n // len(ds)
            for dev in ds:
                per[dev] = per.get(dev, 0) + share
    return {"source": "live_arrays", "bytes_in_use": total,
            "peak_bytes_in_use": 0, "bytes_limit": 0,
            "devices": [{"device": k, "bytes_in_use": v,
                         "peak_bytes_in_use": 0, "bytes_limit": 0}
                        for k, v in sorted(per.items())]}


def fast_device_bytes() -> tuple[int, int] | None:
    """(bytes_in_use, peak_bytes_in_use) summed over devices, or None when
    the backend has no ``memory_stats`` — the dispatch-hot-path probe:
    reading runtime counters is ~µs, while the live-array fallback walks
    every resident buffer and has no place inside a per-iteration loop."""
    import jax
    total = peak = 0
    for d in jax.devices():
        try:
            ms = d.memory_stats()
        except Exception:   # noqa: BLE001
            return None
        if not ms:
            return None
        total += int(ms.get("bytes_in_use", 0))
        peak += int(ms.get("peak_bytes_in_use", 0))
    return total, peak


# ---------------------------------------------------------------------------
# Leak detection.


class LeakDetector:
    """Flags keys whose bytes grow, or that sit resident and untouched,
    for N consecutive Cleaner sweeps.

    Semantics (documented in docs/OBSERVABILITY.md): a *sweep* is one
    :meth:`MemoryMeter.leak_sweep` generation — the Cleaner advances it on
    every LRU sweep, and diagnostics (tests) may advance it explicitly. Per
    key the detector tracks a **growth streak** (consecutive sweeps where
    registered bytes strictly increased) and an **idle streak**
    (consecutive sweeps with no DKV put/get of the key). A key is flagged
    once either streak reaches ``LEAK_SWEEPS``, provided its bytes are at or
    above ``LEAK_MIN_BYTES`` (jobs and tiny models never page anyone)."""

    def __init__(self, sweeps: int = LEAK_SWEEPS,
                 min_bytes: int = LEAK_MIN_BYTES):
        self.sweeps = max(int(sweeps), 1)
        self.min_bytes = int(min_bytes)
        self.generation = 0
        # key -> {"kind", "bytes", "grow", "idle"}
        self._state: dict[str, dict] = {}

    def observe(self, keyed: dict[str, tuple[str, int]],
                accessed: set[str]) -> None:
        self.generation += 1
        gone = set(self._state) - set(keyed)
        for k in gone:
            del self._state[k]
        for key, (kind, nbytes) in keyed.items():
            st = self._state.get(key)
            if st is None:
                self._state[key] = {"kind": kind, "bytes": nbytes,
                                    "grow": 0, "idle": 0}
                continue
            st["grow"] = st["grow"] + 1 if nbytes > st["bytes"] else 0
            st["idle"] = 0 if key in accessed else st["idle"] + 1
            st["bytes"] = nbytes
            st["kind"] = kind

    def report(self) -> list[dict]:
        """Flagged keys, largest first."""
        out = []
        for key, st in self._state.items():
            if st["bytes"] < self.min_bytes:
                continue
            reasons = []
            if st["grow"] >= self.sweeps:
                reasons.append("growing")
            if st["idle"] >= self.sweeps:
                reasons.append("idle")
            if reasons:
                out.append({"key": key, "kind": st["kind"],
                            "bytes": st["bytes"],
                            "growth_sweeps": st["grow"],
                            "idle_sweeps": st["idle"],
                            "reasons": reasons})
        out.sort(key=lambda r: -r["bytes"])
        return out

    def reset(self) -> None:
        self.generation = 0
        self._state.clear()


# ---------------------------------------------------------------------------
# The meter.


class MemoryMeter:
    """Thread-safe byte accountant for the three levels above. One global
    instance (:data:`MEMORY`); the DKV registers keys on put/remove, the
    Cleaner advances leak sweeps, and the REST layer serves summaries."""

    def __init__(self):
        self._lock = lockwitness.lock("utils.memory.MemoryMeter._lock")
        # key -> (kind, bytes, host_bytes)
        self._keyed: dict[str, tuple[str, int, int]] = {}
        self._by_kind: dict[str, int] = {}
        self._host_total = 0                           # RSS-resident K/V bytes
        self._exported_kinds: set[str] = set()         # gauges ever written
        self._accessed: set[str] = set()               # since last sweep
        self._host_peak = 0
        self._device_peak = 0
        self.detector = LeakDetector()

    # -- per-key registration (DKV put/remove/clear) -------------------------

    def register(self, key: str, value) -> None:
        kind, nbytes = value_kind_bytes(value)
        host = value_host_bytes(value)
        with self._lock:
            self._set_locked(key, kind, nbytes, host)
            self._accessed.add(key)
            self._export_locked()

    def unregister(self, key: str) -> None:
        with self._lock:
            self._drop_locked(key)
            self._accessed.discard(key)
            self._export_locked()

    def clear(self) -> None:
        with self._lock:
            self._keyed.clear()
            self._by_kind.clear()
            self._host_total = 0
            self._accessed.clear()
            self.detector.reset()
            self._export_locked()

    def note_access(self, key: str) -> None:
        """A DKV get touched the key — resets its idle streak at the next
        sweep. A set-add under the lock: cheap enough for every get."""
        with self._lock:
            self._accessed.add(key)

    def _set_locked(self, key: str, kind: str, nbytes: int,
                    host: int) -> None:
        self._drop_locked(key)
        self._keyed[key] = (kind, nbytes, host)       # graftlint: ok(caller holds self._lock — _locked suffix contract)
        self._by_kind[kind] = self._by_kind.get(kind, 0) + nbytes   # graftlint: ok(caller holds self._lock — _locked suffix contract)
        self._host_total += host                      # graftlint: ok(caller holds self._lock — _locked suffix contract)

    def _drop_locked(self, key: str) -> None:
        old = self._keyed.pop(key, None)              # graftlint: ok(caller holds self._lock — _locked suffix contract)
        if old is not None:
            self._by_kind[old[0]] -= old[1]           # graftlint: ok(caller holds self._lock — _locked suffix contract)
            self._host_total -= old[2]                # graftlint: ok(caller holds self._lock — _locked suffix contract)

    def _export_locked(self) -> None:
        """Push per-kind totals into the gauges WHILE holding the meter
        lock, so a later snapshot can never be published before an earlier
        one (the telemetry registry's own lock is terminal in the
        store→meter→telemetry order). Kinds exported before but absent now
        are written as 0: after a DKV.clear() the gauge must not keep
        reporting the last resident bytes forever."""
        totals = dict(self._by_kind)
        stale = self._exported_kinds - set(totals)
        self._exported_kinds |= set(totals)           # graftlint: ok(caller holds self._lock — _locked suffix contract)
        for kind, total in totals.items():
            _tm.DKV_BYTES.labels(kind=kind).set(max(total, 0))
        for kind in stale:
            _tm.DKV_BYTES.labels(kind=kind).set(0)

    # -- authoritative refresh ----------------------------------------------

    def refresh(self) -> None:
        """Recompute every key's bytes from the live DKV objects. Puts and
        removes keep the registry current for the common paths; a refresh
        catches in-place mutation (a column added to a resident frame)
        before serving ``/3/Memory``. Runs under the STORE lock so a
        concurrent remove cannot be resurrected by an older snapshot
        (store→meter lock order, same as put/remove registration)."""
        from h2o3_tpu.utils.registry import DKV
        with DKV._lock:   # raw store, consistent with Cleaner.resident_frames
            fresh = {key: (*value_kind_bytes(value),
                           value_host_bytes(value))
                     for key, value in DKV._store.items()}
            with self._lock:
                self._keyed = fresh
                self._by_kind = {}
                self._host_total = 0
                for kind, nbytes, host in fresh.values():
                    self._by_kind[kind] = self._by_kind.get(kind, 0) + nbytes
                    self._host_total += host
                self._export_locked()

    # -- process/device sampling + watermarks --------------------------------

    def sample(self, rss: int | None = None,
               dev: int | None = None) -> tuple[int, int]:
        """(host_rss_bytes, device_bytes_in_use) — updates the monotonic
        high-water marks. The full-fidelity sample: uses the live-array
        fallback when the backend has no stats, so call it at build/section
        granularity, not per dispatch. Pass precomputed values when the
        caller already sampled (``summary`` reads both for its payload)."""
        if rss is None:
            rss = host_stats()["rss_bytes"]
        if dev is None:
            dev = device_stats()["bytes_in_use"]
        # peaks updated AND published under the lock: exporting from an
        # unlocked read could publish an older peak after a newer one,
        # making the "monotonic" gauges visibly decrease
        with self._lock:
            if rss > self._host_peak:
                self._host_peak = rss
            if dev > self._device_peak:
                self._device_peak = dev
            _tm.HOST_RSS_BYTES.set(rss)
            _tm.DEVICE_BYTES.set(dev)
            _tm.HOST_RSS_PEAK_BYTES.set(self._host_peak)
            _tm.DEVICE_PEAK_BYTES.set(self._device_peak)
        return rss, dev

    @property
    def watermarks(self) -> dict:
        with self._lock:
            return {"host_rss_peak_bytes": self._host_peak,
                    "device_peak_bytes": self._device_peak}

    # -- leak sweeps ---------------------------------------------------------

    def leak_sweep(self) -> None:
        """Advance one leak-detector generation over the REGISTERED keyed
        bytes (the Cleaner calls this on every budgeted sweep — i.e. on
        every frame put under an HBM budget — so it must stay O(keys):
        put/remove already keep the registered view current, and growth
        from in-place mutation is caught when the key is re-put or when a
        ``/3/Memory`` read refreshes). Tests call it directly."""
        with self._lock:
            keyed = {k: (kind, nbytes)
                     for k, (kind, nbytes, _host) in self._keyed.items()}
            accessed = set(self._accessed)
            self._accessed.clear()
            self.detector.observe(keyed, accessed)

    def idle_streaks(self) -> dict[str, int]:
        """Per-key consecutive no-access sweep counts from the leak
        detector — the Cleaner's spill-victim signal (a key idle for many
        sweeps is colder than anything the LRU clock alone can prove)."""
        with self._lock:
            return {k: st["idle"] for k, st in self.detector._state.items()}

    def leak_report(self) -> dict:
        with self._lock:
            return {"sweeps": self.detector.generation,
                    "flag_after_sweeps": self.detector.sweeps,
                    "min_bytes": self.detector.min_bytes,
                    "flagged": self.detector.report()}

    # -- summaries -----------------------------------------------------------

    def dkv_totals(self) -> tuple[int, dict[str, int], int]:
        """(total_bytes, by_kind, key_count) from the registered view."""
        with self._lock:
            by_kind = dict(self._by_kind)
            n = len(self._keyed)
        return sum(by_kind.values()), by_kind, n

    def dkv_host_bytes(self) -> int:
        """Host-RSS-resident K/V bytes (see :func:`value_host_bytes`) —
        what CloudV3's heap arithmetic may legitimately subtract from
        process RSS. A running total maintained at register/unregister:
        ``/3/Cloud`` is polled, so it must not walk the object graph."""
        with self._lock:
            return max(self._host_total, 0)

    def key_bytes(self, key: str) -> int:
        """Registered bytes of one DKV key (0 when unknown) — the tenancy
        byte ledger prices a key by the same measure /3/Memory reports."""
        with self._lock:
            rec = self._keyed.get(key)
            return rec[1] if rec is not None else 0

    def top_keys(self, n: int = 10) -> list[dict]:
        with self._lock:
            rows = [{"key": k, "kind": kind, "bytes": b}
                    for k, (kind, b, _host) in self._keyed.items()]
        rows.sort(key=lambda r: -r["bytes"])
        return rows[:n]

    def summary(self, top_n: int = 10, refresh: bool = True) -> dict:
        """The ``/3/Memory`` payload: host + device stats, keyed totals,
        top-N keys, watermarks, leak report, and the Cleaner's spill view
        (budget, spill/fault-in counters, what sits in the ice_root)."""
        if refresh:
            self.refresh()
        host = host_stats()
        dev = device_stats()
        # watermarks track every summary read too (reusing the samples
        # above — no second /proc read or live-array walk)
        self.sample(rss=host["rss_bytes"], dev=dev["bytes_in_use"])
        total, by_kind, nkeys = self.dkv_totals()
        from h2o3_tpu.utils.cleaner import CLEANER
        return {"host": host, "device": dev,
                "dkv": {"total_bytes": total, "by_kind": by_kind,
                        "keys": nkeys},
                "top_keys": self.top_keys(top_n),
                "watermarks": self.watermarks,
                "leaks": self.leak_report(),
                "spill": CLEANER.stats()}


#: the process-wide meter (reference: the MemoryManager singleton)
MEMORY = MemoryMeter()
