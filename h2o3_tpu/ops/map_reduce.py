"""map_reduce — the MRTask contract on a TPU mesh.

Reference: ``water/MRTask.java:83-118,257-305`` — user code supplies
``map(Chunk[])`` producing per-chunk partial state and ``reduce(MRTask)``
merging two partials; the runtime fans out over nodes in a binary tree, runs
map on every local chunk via recursive fork/join, and reduces partials up the
tree over RPC.

TPU-native expression: the contract — a commutative-associative monoid over
row shards — maps 1:1 onto ``shard_map`` + ``lax.psum``:

- fan-out over nodes + per-chunk fork/join  →  SPMD: each device runs ``map_fn``
  on its shard (XLA vectorizes the "loop over rows" instead of forking tasks);
- tree reduction over RPC                   →  ``lax.psum`` over the ``rows``
  mesh axis (XLA lowers to an ICI all-reduce, which IS a ring/tree reduction
  in hardware).

Two styles are supported, and most algorithm code uses the second:

1. Explicit: ``map_reduce(map_fn, cols...)`` — per-shard partials psum-reduced.
   Use when the partial is a fixed-shape statistic (histogram, Gram, counts).
2. Implicit: write plain ``jnp`` reductions over the sharded column inside
   ``jax.jit`` — the SPMD partitioner inserts the same collectives. (This is
   why most of the framework contains no explicit communication code at all.)
"""

from __future__ import annotations

import itertools
import os
import time
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from h2o3_tpu.parallel.mesh import ROWS, get_mesh


# Compiled-program cache: jit executables are tied to the wrapper instance, so
# re-wrapping per call would recompile every invocation (deadly in iterative
# algorithms like tree building). Keyed by (fn, mesh, arg ranks, donate) with
# LRU eviction (``move_to_end`` on hit): hot entries survive fresh-lambda
# churn, fresh-lambda callers get no hits but can't grow the dict unboundedly
# (evicted entries simply recompile on reuse). Pass a module-level function or
# a stable partial to benefit from caching. jax.jit's own cache handles
# shape/dtype specialization underneath.
from collections import OrderedDict

_COMPILED_MAX = 256
_compiled: OrderedDict = OrderedDict()


def _cache_key(tag, fn, rest):
    return (tag, fn, rest)


def _cache_get(key):
    value = _compiled.get(key)
    if value is not None:
        _compiled.move_to_end(key)  # LRU: hot entries survive fresh-lambda churn
    return value


def _cache_put(key, value):
    _compiled[key] = value
    while len(_compiled) > _COMPILED_MAX:
        _compiled.popitem(last=False)


# Telemetry sampling for the dispatch path. JAX dispatch is ASYNC: blocking on
# the result to measure an accurate duration (and to stamp per-partition
# readiness) serializes back-to-back collectives — exactly the host-as-clock
# pattern this module exists to avoid. So accurate duration/straggler probes
# are SAMPLED: every Nth dispatch (H2O3TPU_DISPATCH_SAMPLE, default 16; the
# first dispatch always samples so short sessions still measure something)
# pays one sync for the `h2o3_mapreduce_dispatch_seconds` observation and —
# when a trace is active — the straggler attrs. Per-partition sub-spans are
# additionally gated behind H2O3TPU_TRACE_PARTITIONS=1 (full fidelity: every
# traced dispatch syncs and stamps shard readiness). Unsampled dispatches
# record only enqueue-side counters and return un-synced outputs, so the
# device pipelines K-step megasteps without the host in the loop.
_SAMPLE_EVERY = max(int(os.environ.get("H2O3TPU_DISPATCH_SAMPLE", "16") or 16), 1)
_dispatch_seq = itertools.count()


# ---------------------------------------------------------------------------
# Dispatch retry — the UDP-drop tolerance of the reference (water/H2O.java
# -random_udp_drop exercises an RPC retry path) mapped onto this runtime's
# network events: device dispatches. A transient failure (an injected
# FaultInjected drop, a transient XLA RuntimeError) is retried with
# exponential backoff + jitter under a budget; only an exhausted budget
# surfaces, as a structured DispatchFailed carrying the attempt history
# (docs/RELIABILITY.md).

class DispatchFailed(RuntimeError):
    """A dispatch kept failing after its retry budget was exhausted.

    ``fn`` names the call site; ``history`` is the per-attempt record
    (error + backoff) that Job surfaces to pollers."""

    def __init__(self, fn: str, history: "list[dict]"):
        self.fn = fn
        self.history = history
        last = history[-1]["error"] if history else "unknown"
        super().__init__(f"dispatch {fn!r} failed after {len(history)} "
                         f"attempt(s); last error: {last}")


#: elastic-membership ejection hook (parallel/elastic.py): a worker thread
#: running under ``ejection_scope(cb)`` turns an exhausted retry budget into
#: a MEMBERSHIP event instead of a build failure — ``retrying`` invokes the
#: hook with the call-site name and attempt history right before raising
#: DispatchFailed, so the elastic group records the ejection cause while the
#: exception unwinds only the worker's round (the build goes on without it).
import contextlib as _contextlib
import contextvars as _contextvars

_EJECT_HOOK: "_contextvars.ContextVar" = _contextvars.ContextVar(
    "h2o3_eject_hook", default=None)


@_contextlib.contextmanager
def ejection_scope(hook: "Callable[[str, list], None]"):
    """Route retry exhaustion in this context to ``hook(fn, history)``
    (called before :class:`DispatchFailed` is raised). Elastic worker
    threads bind this so a dead dispatch ejects the WORKER, not the job."""
    token = _EJECT_HOOK.set(hook)
    try:
        yield
    finally:
        _EJECT_HOOK.reset(token)


def retry_budget() -> int:
    """Retry attempts after the first try (``H2O3TPU_DISPATCH_RETRIES``,
    default 3; 0 disables the retry machinery — failures pass through
    unchanged)."""
    try:
        return max(int(os.environ.get("H2O3TPU_DISPATCH_RETRIES", "") or 3), 0)
    except ValueError:
        return 3


def _backoff_ms(attempt: int) -> float:
    """Exponential backoff with jitter: base * 2^attempt * U(0.5, 1.5)
    (``H2O3TPU_DISPATCH_BACKOFF_MS``, default 25)."""
    import random
    try:
        base = float(os.environ.get("H2O3TPU_DISPATCH_BACKOFF_MS", "") or 25.0)
    except ValueError:
        base = 25.0
    return base * (2 ** attempt) * (0.5 + random.random())


#: error-status tags that mark a RuntimeError DETERMINISTIC, not transient:
#: re-dispatching an OOM or an invalid program burns device time on a
#: failure that cannot change (XlaRuntimeError subclasses RuntimeError and
#: carries the gRPC-style status name in its message). INTERNAL is what a
#: compiler refusal (Mosaic, XLA) surfaces as — compiling the same program
#: again under back-off cannot succeed
_NON_TRANSIENT = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT",
                  "FAILED_PRECONDITION", "UNIMPLEMENTED", "INTERNAL")


def retrying(what: str, thunk: Callable, *, span=None,
             retry_runtime_errors: bool = True):
    """Run ``thunk`` under the dispatch retry budget.

    Fault injection (``FAULTS.maybe_fault(what)``) fires before every
    attempt, so chaos drops exercise this exact path. ``FaultInjected`` is
    always retryable (it is raised before the dispatch); ``RuntimeError``
    from the dispatch itself is retried only when ``retry_runtime_errors``
    (donated buffers are consumed by a real dispatch attempt, so donating
    call sites must not re-run it). Each retry increments
    ``h2o3_dispatch_retries_total{fn,outcome="retried"}`` and notes itself
    on the active Job; exhaustion increments ``outcome="exhausted"`` and
    raises :class:`DispatchFailed` with the attempt history."""
    from h2o3_tpu.utils import telemetry as _tm
    from h2o3_tpu.utils import timeline as _tl
    budget = retry_budget()
    history: list[dict] = []
    attempt = 0
    while True:
        try:
            if _tl.FAULTS is not None:
                _tl.FAULTS.maybe_fault(what)
            out = thunk()
        except (_tl.FaultInjected, RuntimeError) as e:
            if isinstance(e, DispatchFailed):
                raise          # a nested dispatch already exhausted its budget
            if budget == 0:
                raise          # retries disabled: pure pass-through, no
                               # metrics — the machinery never ran
            if not isinstance(e, _tl.FaultInjected) and (
                    not retry_runtime_errors
                    or any(tag in str(e) for tag in _NON_TRANSIENT)):
                raise          # deterministic failure: surface immediately
            history.append({"attempt": attempt,
                            "error": f"{type(e).__name__}: {e}"})
            if attempt >= budget:
                _tm.DISPATCH_RETRIES.labels(fn=what,
                                            outcome="exhausted").inc()
                if span is not None:
                    span.set_attrs(retries=attempt)
                hook = _EJECT_HOOK.get()
                if hook is not None:
                    # elastic worker context: the exhausted budget is an
                    # ejection cause, recorded before the exception unwinds
                    # this worker's round (best-effort — a hook error must
                    # not mask the DispatchFailed it annotates)
                    try:
                        hook(what, history)
                    except Exception as he:   # noqa: BLE001
                        _tl.TIMELINE.record("elastic",
                                            f"eject_hook_error:{he}")
                raise DispatchFailed(what, history) from e
            delay = _backoff_ms(attempt)
            history[-1]["backoff_ms"] = round(delay, 1)
            _tm.DISPATCH_RETRIES.labels(fn=what, outcome="retried").inc()
            from h2o3_tpu.models.job import note_dispatch_retry
            note_dispatch_retry()
            time.sleep(delay / 1000.0)
            attempt += 1
            continue
        if attempt:
            # absorbed faults still read in trace trees: the span carries
            # how many retries the dispatch cost and a "retried" status
            # (overriding the error mark the injected drop left). Builder
            # call sites pass no span of their own — mark the ACTIVE span
            # (their timed_event chunk/megastep span) instead.
            if span is not None:
                span.set_attrs(retries=attempt)
                span.set_status("retried")
            else:
                from h2o3_tpu.utils import tracing as _trc
                # force: the injected drop already marked this span "error";
                # the absorbed outcome overrides it
                _trc.TRACER.mark_active(status="retried", force=True,
                                        retries=attempt)
        return out


def map_reduce(map_fn: Callable, *cols: jax.Array, donate: bool = False):
    """Run ``map_fn`` on each device's row shard; psum-reduce the results.

    ``map_fn(*shards) -> pytree of arrays`` must produce partials whose
    elementwise sum is the correct global result (the MRTask ``reduce``
    contract specialized to addition, which covers every reference use:
    histograms, Gram matrices, gradient sums, counts).
    """
    mesh = get_mesh()
    ndims = tuple(c.ndim for c in cols)
    name = getattr(map_fn, "__name__", "map_reduce")
    key = _cache_key("mr", map_fn, (mesh, ndims, donate))
    fn = _cache_get(key)
    if fn is None:
        in_specs = tuple(P(ROWS, *([None] * (nd - 1))) for nd in ndims)

        def shard_body(*shards):
            return jax.tree.map(lambda p: lax.psum(p, ROWS), map_fn(*shards))

        # accounted AOT compile (utils/costs.py): every collective's
        # signature / compile time / cost_analysis FLOPs land in /3/Compute.
        # sample=False — this module's OWN sampled probe below measures the
        # synced duration and feeds COSTS.observe, so the wrapper must not
        # add a second sync of its own
        from h2o3_tpu.utils.costs import accounted_jit
        fn = accounted_jit(
            f"map_reduce:{name}",
            _shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                       out_specs=P()),
            donate_argnums=tuple(range(len(cols))) if donate else (),
            sample=False)
        _cache_put(key, fn)
    from h2o3_tpu.utils import telemetry as _tm
    from h2o3_tpu.utils import timeline as _tl
    from h2o3_tpu.utils import tracing as _tr
    # child span per dispatch (no-op outside an active trace); faults
    # injected below mark THIS span, so fault runs read in trace trees
    # sampled telemetry sync (see the note at _SAMPLE_EVERY): full partition
    # fidelity under H2O3TPU_TRACE_PARTITIONS=1, else every Nth dispatch
    full = _tr.trace_partitions_enabled()
    sampled = full or (next(_dispatch_seq) % _SAMPLE_EVERY == 0)
    dur_box = [0]
    with _tr.TRACER.span(f"map_reduce:{name}", kind="dispatch",
                         attrs={"fn": name,
                                "partitions": mesh.size,
                                "sampled": sampled}) as span:
        def _attempt():
            # device-byte attribution per TRACED SAMPLED dispatch — only
            # through the runtime's memory_stats counters (~µs): the
            # live-array fallback walks every resident buffer and has no
            # place on this hot path, so backends without stats (CPU) skip
            # it (fast probe returns None)
            mem0 = None
            if span is not None and sampled:
                from h2o3_tpu.utils.memory import fast_device_bytes
                mem0 = fast_device_bytes()
            t0 = time.time_ns()
            # NO unconditional sync: dispatch is async, so back-to-back
            # collectives pipeline on device and the host stops being the
            # clock. Only a SAMPLED dispatch blocks, because an enqueue-time
            # measurement would never see a slow collective — the sync IS
            # the probe.
            out = fn(*cols)
            if sampled:
                # measure BEFORE the full sync (per-shard readiness IS the
                # probe) but EMIT spans only after the attempt succeeds — a
                # failed-then-retried attempt must not leave bogus partition
                # spans in the trace tree
                meas = (_measure_partitions(out, mesh, t0)
                        if span is not None else None)
                out = jax.block_until_ready(out)  # graftlint: ok(sampled telemetry probe — the sync is the measurement)
                if meas is not None:
                    _emit_partition_spans(span, meas, t0)
                dur_box[0] = time.time_ns() - t0
                _tm.MR_DISPATCH_SECONDS.labels(fn=name).observe(
                    dur_box[0] / 1e9)
                # the synced duration is exactly what the compute
                # observatory needs: achieved FLOP/s of this collective
                # against the cost of the signature that actually ran
                # (utils/costs.py; fn is the AccountedJit built above)
                from h2o3_tpu.utils.costs import COSTS
                cflops, cbytes = fn.last_cost()
                COSTS.observe(f"map_reduce:{name}", dur_box[0] / 1e9,
                              flops=cflops, nbytes=cbytes)
                if mem0 is not None:
                    mem1 = fast_device_bytes()
                    if mem1 is not None:
                        # max of the two in-use samples, NOT the runtime's
                        # peak_bytes_in_use counter — that one is
                        # process-lifetime monotonic, so after any big build
                        # every later dispatch would report the global
                        # high-water mark instead of its own footprint (same
                        # semantic as the model-span attr)
                        span.set_attrs(
                            peak_device_bytes=max(mem0[0], mem1[0]),
                            device_bytes_delta=mem1[0] - mem0[0])
            # unmeasured dispatches keep dur_box at 0: the timeline keeps one
            # record per dispatch either way, but an async enqueue time must
            # not pollute the duration series — dur_ns=0 is the ring's
            # established "untimed event" marker; accurate durations live in
            # the SAMPLED observations
            return out

        # transient failures (injected drops, transient runtime errors) are
        # retried with backoff instead of killing the Job; donated buffers
        # are consumed by a real dispatch attempt, so donate=True only
        # retries pre-dispatch FaultInjected
        out = retrying("map_reduce", _attempt, span=span,
                       retry_runtime_errors=not donate)
    _tl.TIMELINE.record("collective", name, dur_box[0])
    # dispatch count + partition (shard) count always; the duration
    # histogram's min/max spread is the straggler signal (under SPMD all
    # shards run one program, so a straggler shows as dispatch max >> min)
    _tm.MR_DISPATCHES.labels(fn=name).inc()
    _tm.MR_PARTITIONS.inc(mesh.size)
    return out


def _measure_partitions(out, mesh, t0: int):
    """Per-partition readiness measurement under a traced SAMPLED dispatch:
    block on each device's output shard in device order and stamp when it
    became ready. Runs only on sampled dispatches / under
    ``H2O3TPU_TRACE_PARTITIONS=1`` — the sequential shard blocking is a
    real serialization, so it must never ride on every dispatch a traced
    request touches. Returns ``(ends, devices)`` or None; SPAN EMISSION is
    separate (:func:`_emit_partition_spans`) so a failed-then-retried
    attempt's measurements are simply discarded. Best-effort: a trace must
    never break a dispatch."""
    try:
        leaves = jax.tree.leaves(out)
        shards0 = getattr(leaves[0], "addressable_shards", None) \
            if leaves else None
        if not shards0:
            return None
        ends = []
        for i in range(len(shards0)):
            for leaf in leaves:
                sh = getattr(leaf, "addressable_shards", ())
                if i < len(sh):
                    # graftlint: ok(sampled straggler probe — per-shard readiness IS the measurement)
                    jax.block_until_ready(sh[i].data)
            ends.append(time.time_ns())
        return ends, [str(s.device) for s in shards0]
    except Exception:   # noqa: BLE001 — tracing is best-effort by contract
        return None


def _emit_partition_spans(span, meas, t0: int) -> None:
    """Turn a successful attempt's readiness measurement into partition
    child spans + straggler attribution attrs (max/argmax of the
    INCREMENTAL waits — see :func:`_shard_waits`)."""
    try:
        from h2o3_tpu.utils import tracing as _tr
        ends, devices = meas
        durs = [e - t0 for e in ends]
        waits = _shard_waits(ends, t0)
        argmax = waits.index(max(waits))
        for i, end in enumerate(ends):
            _tr.TRACER.add_span(f"partition:{i}", "partition", span,
                                start_ns=t0, end_ns=end,
                                attrs={"device": devices[i],
                                       "wait_ns": waits[i]},
                                tid=f"partition-{i}")
        span.set_attrs(part_dur_min_ns=min(durs), part_dur_max_ns=max(durs),
                       straggler=argmax, straggler_device=devices[argmax])
    except Exception:   # noqa: BLE001 — tracing is best-effort by contract
        pass


def _shard_waits(ends: "list[int]", t0: int) -> "list[int]":
    """Per-shard incremental wait from sequential readiness stamps: shards
    are blocked on in device order, so the CUMULATIVE times are monotone
    and their argmax would always name the last shard; the true straggler is
    where the readiness time JUMPS — a shard already finished while an
    earlier one was blocking shows ~zero incremental wait."""
    return [max(e - (ends[i - 1] if i else t0), 0)
            for i, e in enumerate(ends)]


def map_cols(fn: Callable, *cols: jax.Array) -> jax.Array:
    """Elementwise/column transform preserving row sharding.

    Reference analog: MRTask with ``NewChunk`` outputs (``outputFrame``) — a map
    with no reduce. Under jit on sharded inputs this is embarrassingly parallel;
    provided as a named entry point for symmetry and for fusing multi-column
    expressions in one compiled program.
    """
    key = _cache_key("mc", fn, ())
    jfn = _cache_get(key)
    if jfn is None:
        from h2o3_tpu.utils.costs import accounted_jit
        jfn = accounted_jit(
            f"map_cols:{getattr(fn, '__name__', 'map_cols')}", fn,
            sample=False)
        _cache_put(key, jfn)
    return jfn(*cols)


@partial(jax.jit, static_argnames=("num_segments",))
def segment_sum_cols(values: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    """Global segment-sum over sharded rows (building block for group-by and
    histogram accumulation). values: [rows] or [rows, k]; ids: [rows] int32
    with negative ids dropped."""
    ok = segment_ids >= 0
    ids = jnp.where(ok, segment_ids, 0)
    vals = jnp.where((ok if values.ndim == 1 else ok[:, None]), values, 0)
    return jax.ops.segment_sum(vals, ids, num_segments=num_segments)
