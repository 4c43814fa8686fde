"""On-demand device profiler — bounded ``jax.profiler.trace`` captures.

The span tracer (``utils/tracing.py``) answers *which request/iteration was
slow*; this module answers *what the device and the XLA runtime were doing
while it was slow*: ``POST /3/Profiler/capture`` wraps
``jax.profiler.trace`` around a bounded window and keeps the resulting
Perfetto-loadable artifact (the ``*.trace.json.gz`` Chrome-trace file the
profiler writes) for listing and download.

Every span is a ``jax.profiler.TraceAnnotation`` of the same name
(``tracing.annotation``) in any profiler session, this one included: host
spans, device ops, and XLA runtime events line up in one Perfetto view.

One capture at a time: the profiler runtime is process-global state, so a
second concurrent ``capture()`` raises :class:`CaptureBusy` (the REST layer
maps it to a structured 409). Artifacts live under ``H2O3TPU_PROFILE_DIR``
(default: a per-process dir under the system tempdir) and the registry
keeps the last :data:`MAX_CAPTURES` — older artifact directories are
deleted.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import threading
import time
import uuid

#: captures are bounded: 10 ms .. 30 s
MIN_CAPTURE_MS = 10
MAX_CAPTURE_MS = 30_000

MAX_CAPTURES = 8


class CaptureBusy(RuntimeError):
    """A capture is already open — the profiler runtime is process-global,
    so concurrent captures would interleave into one corrupt artifact."""


def _base_dir() -> str:
    d = os.environ.get("H2O3TPU_PROFILE_DIR", "").strip()
    if not d:
        d = os.path.join(tempfile.gettempdir(),
                         f"h2o3_tpu_profiles_{os.getpid()}")
    os.makedirs(d, exist_ok=True)
    return d


class DeviceProfiler:
    """Single-flight ``jax.profiler.trace`` capture manager."""

    def __init__(self):
        self._busy = threading.Lock()
        self._reg_lock = threading.Lock()
        self._captures: list[dict] = []

    def capture(self, duration_ms: int = 500, exercise: bool = True) -> dict:
        """Open a profiler trace for ``duration_ms`` (clamped to
        [10 ms, 30 s]) and register the artifact. ``exercise`` runs one
        tiny traced dispatch under a
        ``profiler:exercise`` span so an otherwise-idle server still yields
        a non-empty, annotation-carrying capture. Raises
        :class:`CaptureBusy` when a capture is already open."""
        duration_ms = max(MIN_CAPTURE_MS, min(int(duration_ms),
                                              MAX_CAPTURE_MS))
        if not self._busy.acquire(blocking=False):
            raise CaptureBusy(
                "a profiler capture is already in progress (the profiler "
                "runtime is process-global; retry when it completes)")
        try:
            import jax
            cap_id = f"cap_{uuid.uuid4().hex[:12]}"
            out_dir = os.path.join(_base_dir(), cap_id)
            os.makedirs(out_dir, exist_ok=True)
            t0 = time.time()
            jax.profiler.start_trace(out_dir)
            try:
                deadline = time.perf_counter() + duration_ms / 1e3
                if exercise:
                    self._exercise()
                while time.perf_counter() < deadline:
                    time.sleep(min(0.01, max(
                        deadline - time.perf_counter(), 0.0)))
            finally:
                jax.profiler.stop_trace()
            rec = self._register(cap_id, out_dir, duration_ms, t0)
            return rec
        finally:
            self._busy.release()

    @staticmethod
    def _exercise() -> None:
        """One tiny traced dispatch under a span, so the capture provably
        carries span-derived annotations even on an idle server."""
        import jax
        import jax.numpy as jnp
        from h2o3_tpu.utils import tracing as _tr
        with _tr.TRACER.span("profiler:exercise", kind="profile", root=True,
                             ephemeral=True):
            x = jnp.ones((128, 128), jnp.float32)
            jax.block_until_ready(jax.jit(jnp.matmul)(x, x))  # graftlint: ok(profiler exercise — the capture needs a synced dispatch inside the window)

    def _register(self, cap_id: str, out_dir: str, duration_ms: int,
                  t0: float) -> dict:
        trace_files = sorted(glob.glob(os.path.join(
            out_dir, "plugins", "profile", "*", "*.trace.json.gz")))
        artifact = trace_files[-1] if trace_files else None
        rec = {"capture_id": cap_id, "duration_ms": duration_ms,
               "started_at_ms": int(t0 * 1000),
               "artifact": os.path.basename(artifact) if artifact else None,
               "bytes": os.path.getsize(artifact) if artifact else 0,
               "path": artifact}
        with self._reg_lock:
            self._captures.append(rec)
            while len(self._captures) > MAX_CAPTURES:
                old = self._captures.pop(0)
                shutil.rmtree(os.path.join(_base_dir(), old["capture_id"]),
                              ignore_errors=True)
        return {k: v for k, v in rec.items() if k != "path"}

    def list_captures(self) -> list[dict]:
        with self._reg_lock:
            return [{k: v for k, v in rec.items() if k != "path"}
                    for rec in self._captures]

    def artifact_bytes(self, capture_id: str) -> tuple[bytes, str]:
        """(gzip bytes, filename) of a capture's Perfetto trace artifact.
        Raises ``KeyError`` for unknown/evicted ids or artifact-less
        captures."""
        with self._reg_lock:
            rec = next((r for r in self._captures
                        if r["capture_id"] == capture_id), None)
        if rec is None or not rec.get("path"):
            raise KeyError(f"no profiler capture {capture_id!r} "
                           "(the registry keeps the last "
                           f"{MAX_CAPTURES})")
        with open(rec["path"], "rb") as f:
            return f.read(), rec["artifact"]

    def clear(self) -> None:
        """Tests only: drop the registry and its artifact dirs."""
        with self._reg_lock:
            for rec in self._captures:
                shutil.rmtree(os.path.join(_base_dir(), rec["capture_id"]),
                              ignore_errors=True)
            self._captures.clear()


PROFILER = DeviceProfiler()
