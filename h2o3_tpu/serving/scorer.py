"""ScorerCache — one compiled executable per (model, signature, bucket).

Reference template (PAPERS.md, the TensorFlow-serving design): compile a
model's inference program once per input signature and keep the warm
executable; arbitrary request sizes land in padded power-of-two batch
buckets so the steady state never recompiles. The scorer body is the
model's existing :meth:`Model._score_raw` — the same jitted batch program
training-side scoring uses — traced over a frame REBUILT from raw request
columns (:meth:`ServingSchema.build_frame`), so the serving path cannot
drift from ``model.predict``.

Signatures are ``(model identity, n_num, n_cat, dtype, bucket)``. A hit
returns the warm executable (counted — tests assert the
second same-shape request compiles nothing); a miss traces + compiles
eagerly via ``jit(...).lower(...).compile()`` so compile cost is paid at
miss time, never mid-batch. A model whose ``_score_raw`` does not trace or
compile fails its request with that error: nothing is served op by op.
"""

from __future__ import annotations

import os
import time

import jax
import numpy as np

from h2o3_tpu.serving.schema import ServingSchema
from h2o3_tpu.utils import lockwitness
from h2o3_tpu.utils import telemetry as _tm
from h2o3_tpu.utils.costs import COSTS, cost_of

#: requests larger than the max bucket are scored in max-bucket slices
MAX_BUCKET = int(os.environ.get("H2O3TPU_SCORE_MAX_BUCKET", "4096"))

#: smallest bucket — tiny interactive requests share one executable
MIN_BUCKET = 8


def bucket_for(n: int) -> int:
    """Smallest power-of-two bucket holding ``n`` rows (clamped to
    [MIN_BUCKET, MAX_BUCKET])."""
    b = MIN_BUCKET
    while b < n and b < MAX_BUCKET:
        b <<= 1
    return b


class CompiledScorer:
    """One signature's executable: ``score(num, cat)`` over padded host
    arrays returns host predictions ([bucket] or [bucket, K])."""

    __slots__ = ("bucket", "_fn", "site", "_ncalls", "_flops", "_bytes")

    def __init__(self, model, schema: ServingSchema, bucket: int):
        self.bucket = bucket
        self._ncalls = 0
        self._flops = self._bytes = None

        def raw_fn(num, cat):
            frame = schema.build_frame(num, cat, bucket)
            return model._score_raw(frame)

        num_spec = jax.ShapeDtypeStruct((bucket, len(schema.num_cols)),
                                        np.float32)
        cat_spec = jax.ShapeDtypeStruct((bucket, len(schema.cat_cols)),
                                        np.int32)
        # compile under the cost-observatory site scope: serving compile
        # time / FLOPs / recompile events show in /3/Compute next to the
        # training loops, and compile-cache hits credit the scoring tier.
        # H2O3TPU_COSTS_OFF=1 keeps the full-bypass contract: the scorer
        # still compiles, but nothing is recorded (utils/costs.py).
        from h2o3_tpu.utils.costs import enabled as _costs_on
        site = self.site = f"score:{getattr(model, 'algo', 'model')}"
        with COSTS.scope(site):
            t0 = time.perf_counter()
            self._fn = jax.jit(raw_fn).lower(num_spec, cat_spec).compile()
            dt = time.perf_counter() - t0
        flops, nbytes = self._flops, self._bytes = cost_of(self._fn)
        if _costs_on():
            COSTS.record_compile(
                site,
                {"args": [{"shape": list(num_spec.shape),
                           "dtype": "float32"},
                          {"shape": list(cat_spec.shape),
                           "dtype": "int32"}],
                 "statics": {"model": str(getattr(model, "key", None)),
                             "bucket": str(bucket)}},
                dt, flops, nbytes, loop="scoring")

    def score(self, num: np.ndarray, cat: np.ndarray) -> np.ndarray:
        # the device_get below is already a sync, so timing a sampled call
        # costs nothing extra — achieved FLOP/s of the scoring loop rides
        # into /3/Compute next to the training loops
        from h2o3_tpu.utils import costs as _costs
        n, self._ncalls = self._ncalls, self._ncalls + 1
        sampled = _costs.enabled() and n % _costs.sample_every() == 0
        t0 = time.perf_counter() if sampled else 0.0
        out = np.asarray(jax.device_get(self._fn(num, cat)))
        if sampled:
            # this executable's OWN cost, not the site's latest — several
            # buckets/models share the score:<algo> site
            COSTS.observe(self.site, time.perf_counter() - t0,
                          flops=self._flops, nbytes=self._bytes)
        return out


class ScorerCache:
    """Thread-safe signature → :class:`CompiledScorer` cache with LRU-able
    per-model grouping (evicting a model drops all its signatures)."""

    def __init__(self):
        self._lock = lockwitness.lock("serving.scorer.ScorerCache._lock")
        # (model_token, n_num, n_cat, dtype, bucket) -> CompiledScorer
        self._entries: dict[tuple, CompiledScorer] = {}
        self.hits = 0
        self.misses = 0
        self._pinned_bucket: int | None = None

    # -- bucket pinning (ops-plane recompile-storm remediation) --------------

    def pin_bucket(self, bucket: int) -> int:
        """Pin a floor bucket: requests whose natural bucket is SMALLER
        score in the pinned one instead, collapsing a storm of churning
        small signatures onto one warm executable (padding waste bounded
        by the pin). Returns the clamped pin actually installed."""
        b = MIN_BUCKET
        while b < bucket and b < MAX_BUCKET:
            b <<= 1
        with self._lock:
            self._pinned_bucket = b
        return b

    def unpin_bucket(self) -> None:
        with self._lock:
            self._pinned_bucket = None

    def pinned_bucket(self) -> "int | None":
        with self._lock:
            return self._pinned_bucket

    def bucket_for(self, n: int) -> int:
        """Bucket selection honoring the pin — the batcher's sole seam
        (module-level :func:`bucket_for` stays the pure natural law)."""
        natural = bucket_for(n)
        with self._lock:
            pin = self._pinned_bucket
        return pin if pin is not None and pin > natural else natural

    def compiled_buckets(self) -> "list[int]":
        """Distinct buckets with a compiled signature — what the ops-plane
        recompile-storm action may pin to."""
        with self._lock:
            return sorted({sig[5] for sig in self._entries})

    @staticmethod
    def _signature(model, schema: ServingSchema, bucket: int) -> tuple:
        # id(model) versions the cache: a reloaded model under the same DKV
        # key is a new object and must recompile against its new arrays
        return (getattr(model, "key", None), id(model),
                len(schema.num_cols), len(schema.cat_cols), "f32i32", bucket)

    def get(self, model, schema: ServingSchema, bucket: int) -> CompiledScorer:
        sig = self._signature(model, schema, bucket)
        with self._lock:
            entry = self._entries.get(sig)
            if entry is not None:
                self.hits += 1
                _tm.SCORER_CACHE.labels(event="hit").inc()
                return entry
        # compile OUTSIDE the cache lock: a cold signature must not stall
        # warm-signature scorers for the seconds a trace+compile takes
        entry = CompiledScorer(model, schema, bucket)
        with self._lock:
            won = self._entries.setdefault(sig, entry)
            self.misses += 1
            _tm.SCORER_CACHE.labels(event="miss").inc()
        return won

    def drop_model(self, model) -> int:
        """Evict every signature of ``model``; returns how many dropped."""
        token = (getattr(model, "key", None), id(model))
        with self._lock:
            victims = [s for s in self._entries if s[:2] == token]
            for s in victims:
                del self._entries[s]
            if victims:
                _tm.SCORER_CACHE.labels(event="evict").inc(len(victims))
            return len(victims)

    def stats(self) -> dict:
        with self._lock:
            return {"signatures": len(self._entries),
                    "hits": self.hits, "misses": self.misses,
                    "pinned_bucket": self._pinned_bucket}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = 0
