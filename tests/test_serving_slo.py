"""SLO-adaptive serving (ISSUE 13): latency-budget batching, priority
shedding, and the slice-leased scoring replica pool.

Acceptance pins:

- with no SLO configured, serving output is bit-identical to the PR 6
  fixed-window path (``mode == "fixed"``, window == base, predictions
  equal ``Model.predict``);
- replica slice leases come from :class:`MeshScheduler` and release
  cleanly on evict/shutdown — no leaked slices;
- shedding is accounted (``h2o3_score_shed_total{reason,priority}`` +
  the ``GET /3/Score`` ``shed`` block), low priority first;
- the batcher window is resolved at CONSTRUCTION, not module import
  (the ``WINDOW_S`` ENV001 regression).
"""

import threading
import time

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.serving import SCORING, ServiceUnavailable, Shed, SLOController
from h2o3_tpu.serving.slo import LatencyRing, clamp_priority
from h2o3_tpu.utils.registry import DKV


@pytest.fixture(autouse=True)
def _reset_scoring():
    SCORING.reset()
    SCORING.budget_bytes = None
    yield
    SCORING.reset()
    SCORING.budget_bytes = None


@pytest.fixture
def frame(rng):
    n = 400
    X = rng.normal(size=(n, 3)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(3)}
    cols["y"] = np.where(X[:, 0] - X[:, 1] > 0, "yes", "no")
    fr = Frame.from_arrays(cols, key="slo_frame")
    DKV.put("slo_frame", fr)
    return fr


@pytest.fixture
def gbm(frame):
    from h2o3_tpu.models.gbm import GBM
    return GBM(ntrees=4, max_depth=3, seed=7,
               model_id="slo_gbm").train(y="y", training_frame=frame)


def _rows(frame, n, start=0):
    names = [c for c in frame.names if c != "y"]
    pdf = frame[names].to_pandas().iloc[start:start + n]
    return [{k: float(v) for k, v in rec.items()}
            for rec in pdf.to_dict(orient="records")]


class TestController:
    def test_ring_percentiles(self):
        ring = LatencyRing(size=64)
        assert ring.percentile(99) is None          # cold ring: no signal
        for v in range(1, 101):
            ring.record(v / 1000.0)
        assert ring.percentile(50) == pytest.approx(0.064, abs=0.015)
        assert ring.percentile(99) >= 0.099

    def test_ring_rejects_nan_negative_and_counts_drops(self):
        """ISSUE 15 satellite: a NaN in the ring makes sorted() a partial
        order — every percentile read downstream would steer the SLO
        controller off garbage. Invalid latencies drop and account in
        h2o3_telemetry_rejected_total{where=latency_ring}."""
        from h2o3_tpu.utils.telemetry import METRICS
        rejected = METRICS.counter("h2o3_telemetry_rejected", "",
                                   ("where",)).labels(where="latency_ring")
        before = rejected.value
        ring = LatencyRing(size=64)
        for v in range(1, 101):
            ring.record(v / 1000.0)
        p99_clean = ring.percentile(99)
        ring.record(float("nan"))
        ring.record(-1.0)
        ring.record(float("inf"))
        assert rejected.value == before + 3
        assert ring.count == 100                    # drops never landed
        assert ring.percentile(99) == p99_clean     # signal unpoisoned

    def test_no_target_is_fixed_window_and_never_sheds(self):
        c = SLOController(base_window_s=0.002, slo_ms=None)
        assert not c.active
        for _ in range(20):
            c.record_latency(10.0)                  # terrible latencies
            c.record_dispatch(10.0, 4096)
        assert c.window_s(queued_rows=10 ** 6) == 0.002
        c.admit(0, queued_rows=10 ** 6, n_rows=64)  # must not raise
        assert c.snapshot()["mode"] == "fixed"

    def test_violating_p99_narrows_hard(self):
        c = SLOController(base_window_s=0.004, slo_ms=10.0)
        for _ in range(16):
            c.record_latency(0.02)                  # p99 = 20ms > 10ms SLO
        w0 = c.window_s(0)
        assert w0 < 0.004
        assert c.window_s(0) < w0                   # keeps narrowing
        assert c.narrowed >= 2

    def test_queue_growth_widens_capped_at_quarter_slo(self):
        c = SLOController(base_window_s=0.001, slo_ms=100.0)
        for _ in range(16):
            c.record_latency(0.006)                 # healthy (p99 6% of SLO)
        c.record_dispatch(0.001, rows=8)            # last dispatch: 8 rows
        w = 0.0
        for _ in range(64):
            w = c.window_s(queued_rows=4096)        # queue grew past 8
        assert w > 0.001
        assert w <= 100.0 / 1e3 / 4.0 + 1e-12       # SLO/4 cap
        assert c.widened > 0

    def test_headroom_narrows_gently_with_floor(self):
        c = SLOController(base_window_s=0.004, slo_ms=1000.0)
        for _ in range(16):
            c.record_latency(0.001)                 # massive headroom
        c.record_dispatch(0.001, rows=4096)         # queue never "grows"
        for _ in range(200):
            c.window_s(queued_rows=0)
        assert c.current_window_s() == pytest.approx(0.004 / 16.0)

    def test_admit_sheds_low_priority_first(self):
        c = SLOController(base_window_s=0.001, slo_ms=10.0, max_bucket=64)
        c.record_dispatch(0.030, rows=64)           # 30ms per dispatch EMA
        # ~2 dispatches queued ahead -> est ~60ms+ vs 10ms budget
        with pytest.raises(Shed) as ei:
            c.admit(0, queued_rows=64, n_rows=16)
        assert ei.value.reason == "overload"
        assert ei.value.retry_after_ms >= 100
        with pytest.raises(Shed):
            c.admit(3, queued_rows=64, n_rows=16)   # 4x budget still < est
        c.admit(9, queued_rows=64, n_rows=16)       # 10x budget: admitted
        assert c.shed_count == 2

    def test_per_model_override_beats_env(self, monkeypatch):
        monkeypatch.setenv("H2O3TPU_SCORE_SLO_MS", "50")
        c = SLOController(base_window_s=0.001)
        assert c.slo_ms == 50.0
        c.set_target(200.0)
        assert c.slo_ms == 200.0
        c.set_target(None)                          # None leaves it alone
        assert c.slo_ms == 200.0

    def test_clamp_priority(self):
        assert clamp_priority(None) == 5
        assert clamp_priority(-3) == 0
        assert clamp_priority(42) == 9
        assert clamp_priority("7") == 7
        assert clamp_priority("nope") == 5


class TestWindowConstruction:
    def test_window_resolved_at_construction_not_import(self, frame, gbm,
                                                        monkeypatch):
        """The WINDOW_S regression (ISSUE 13 satellite): a late env change
        must be honored by the next batcher, not silently ignored because
        the module captured the env at import."""
        from h2o3_tpu.serving.batcher import ModelBatcher
        monkeypatch.setenv("H2O3TPU_SCORE_WINDOW_MS", "7.5")
        entry = SCORING._admit(gbm.key)     # admitted under the new env
        try:
            assert entry.batcher._window == pytest.approx(7.5e-3)
            assert entry.slo.base_window_s == pytest.approx(7.5e-3)
            monkeypatch.setenv("H2O3TPU_SCORE_WINDOW_MS", "0.25")
            b2 = ModelBatcher(entry)
            try:
                assert b2._window == pytest.approx(0.25e-3)
            finally:
                b2.stop()
        finally:
            SCORING.reset()

    def test_no_slo_output_bit_identical_to_fixed_window_path(self, frame,
                                                              gbm):
        """ISSUE 13 acceptance: no SLO configured -> the PR 6 path,
        bit-identical predictions and a fixed window."""
        rows = _rows(frame, 17)
        out = SCORING.score(gbm.key, rows)["predictions"]
        entry = SCORING._resident[gbm.key]
        snap = entry.slo.snapshot()
        assert snap["mode"] == "fixed" and snap["target_ms"] is None
        assert entry.slo.current_window_s() == entry.slo.base_window_s
        names = [c for c in frame.names if c != "y"]
        pred = gbm.predict(Frame(names, [frame.vec(c) for c in names]))
        want = np.asarray(pred.vec("pyes").to_numpy())[:17]
        assert np.array_equal(np.asarray(out["pyes"], np.float32), want)
        assert "shed" not in {s["reason"] for s in SCORING.stats()["shed"]}


class TestShedding:
    def test_overloaded_low_priority_sheds_503_high_serves(self, frame, gbm):
        from h2o3_tpu.utils import telemetry as _tm
        rows = _rows(frame, 4)
        SCORING.score(gbm.key, rows, slo_ms=10.0)     # admit + set target
        entry = SCORING._resident[gbm.key]
        # fake a saturated tier: ~50ms per dispatch against a 10ms SLO —
        # beyond priority 1's 20ms budget, inside priority 9's 100ms one
        # (set the EMA directly: the warm-up dispatch above seeded it with
        # its compile wall, and one record_dispatch only moves it by 0.3)
        with entry.slo._lock:
            entry.slo._ema_dispatch_s = 0.05
        shed0 = _tm.SCORE_SHED.labels(reason="overload", priority="1").value
        with pytest.raises(ServiceUnavailable) as ei:
            SCORING.score(gbm.key, rows, priority=1)
        assert ei.value.retry_after_ms >= 100
        assert _tm.SCORE_SHED.labels(reason="overload",
                                     priority="1").value == shed0 + 1
        st = SCORING.stats()
        assert {"reason": "overload", "priority": 1, "count": 1} in st["shed"]
        assert st["shed_total"] >= 1
        # the same load admits priority 9 (10x budget tolerance)
        out = SCORING.score(gbm.key, rows, priority=9)
        assert len(out["predictions"]["predict"]) == 4
        assert out["priority"] == 9

    def test_timeout_shed_is_accounted(self, frame, gbm, monkeypatch):
        import h2o3_tpu.serving.batcher as bm
        from h2o3_tpu.utils import telemetry as _tm
        monkeypatch.setattr(bm, "SCORE_TIMEOUT_S", 0.05)
        entry = SCORING._admit(gbm.key)
        entry.batcher._window = 5.0              # hold the batch open
        t0 = _tm.SCORE_SHED.labels(reason="timeout", priority="5").value
        try:
            with pytest.raises(ServiceUnavailable):
                SCORING.score(gbm.key, _rows(frame, 2))
        finally:
            entry.batcher._window = entry.slo.base_window_s
        assert _tm.SCORE_SHED.labels(reason="timeout",
                                     priority="5").value == t0 + 1

    def test_withdrawer_losing_to_eviction_gets_evicted_not_timeout(
            self, frame, gbm, monkeypatch):
        """ISSUE 13 satellite, the deterministic interleave: the caller
        TIMES OUT first (enters the withdraw path) but the eviction has
        already drained the queue — ``remove`` misses, and the caller
        must surface the retryable :class:`Evicted` (-> 503 upstream),
        not a timeout blamed on the device, and never hang."""
        from h2o3_tpu.serving.batcher import Evicted, ModelBatcher
        import h2o3_tpu.serving.batcher as bm
        monkeypatch.setattr(bm, "SCORE_TIMEOUT_S", 0.05)
        entry = SCORING._admit(gbm.key)
        b = entry.batcher
        b._window = 30.0                         # batch never dispatches
        errs: list = []

        def caller():
            try:
                b.submit(*entry.schema.adapt_rows(_rows(frame, 2)), 2)
            except Exception as e:   # noqa: BLE001 — asserted below
                errs.append(e)

        t = threading.Thread(target=caller)
        t.start()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:       # wait for the enqueue
            with b._cond:
                if b._queue:
                    break
            time.sleep(0.005)
        # stop()'s exact body, but ordered UNDER the condvar — acquired
        # BEFORE the caller's timeout fires and held across it, so the
        # withdrawer blocks at the lock and deterministically loses: by
        # the time it gets in, the queue is drained AND its pending failed
        with b._cond:
            time.sleep(0.1)                      # caller times out, parks
            b._stopped = True                    # on acquiring this lock
            victims = list(b._queue)
            b._queue.clear()
            for p in victims:
                ModelBatcher._fail(p, Evicted("evicted mid-queue"))
            b._cond.notify_all()
        t.join(timeout=10.0)
        assert not t.is_alive(), "withdraw+eviction must never hang"
        assert len(victims) == 1, "the pending must not be dropped"
        assert len(errs) == 1
        assert isinstance(errs[0], Evicted), errs[0]
        # the service layer maps Evicted to re-admit -> a fresh batcher
        # serves (or a persistent loss 503s); either way the tier lives
        # (normal ceiling restored: the fresh batcher cold-compiles)
        monkeypatch.setattr(bm, "SCORE_TIMEOUT_S", 30.0)
        out = SCORING.score(gbm.key, _rows(frame, 2))
        assert len(out["predictions"]["predict"]) == 2

    def test_withdraw_racing_real_eviction_stays_retryable(self, frame, gbm,
                                                           monkeypatch):
        """The same interleave with the REAL ``stop()`` racing the
        timeout: whichever side wins, the caller gets a clean result or a
        retryable 503 — never a hang, never a server error."""
        import h2o3_tpu.serving.batcher as bm
        monkeypatch.setattr(bm, "SCORE_TIMEOUT_S", 0.1)
        entry = SCORING._admit(gbm.key)
        entry.batcher._window = 30.0             # batch never dispatches
        errs: list = []

        def caller():
            try:
                SCORING.score(gbm.key, _rows(frame, 2))
            except Exception as e:   # noqa: BLE001 — asserted below
                errs.append(e)

        t = threading.Thread(target=caller)
        t.start()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:       # wait for the enqueue
            with entry.batcher._cond:
                if entry.batcher._queue:
                    break
            time.sleep(0.005)
        entry.batcher.stop()                     # eviction races the wait
        t.join(timeout=15.0)
        assert not t.is_alive(), "withdraw+eviction must never hang"
        # Evicted -> transparent re-admit (success) or a retryable 503;
        # anything else (500s, TimeoutError leaking raw) is a regression
        assert errs == [] or isinstance(errs[0], ServiceUnavailable), errs
        with entry.batcher._cond:
            assert entry.batcher._queue == [], "dropped _Pending left behind"
        monkeypatch.setattr(bm, "SCORE_TIMEOUT_S", 30.0)
        out = SCORING.score(gbm.key, _rows(frame, 2))
        assert len(out["predictions"]["predict"]) == 2


class TestReplicaPool:
    def test_leases_come_from_scheduler_and_release(self, frame, gbm):
        """ISSUE 13 acceptance: replica slice leases come from
        MeshScheduler and release cleanly on evict/shutdown."""
        from h2o3_tpu.orchestration.scheduler import MeshScheduler
        import jax
        sched = MeshScheduler(slices=2)
        if sched.n < 2:
            pytest.skip("needs a multi-device (virtual) mesh")
        assert sched.free_count() == 2
        SCORING.configure_replicas(2, scheduler=sched)
        try:
            assert sched.free_count() == 0        # both slices leased
            pool = SCORING.pool
            reps = pool.replicas
            assert len(reps) == 2
            devsets = [set(r.devices) for r in reps]
            assert devsets[0].isdisjoint(devsets[1]), \
                "replicas must hold DISJOINT slices"
            assert set().union(*devsets) == \
                {d.id for d in jax.devices()}
            out = SCORING.score(gbm.key, _rows(frame, 4))
            assert out["replica"] in {r.label for r in reps}
            # evicting the model drops per-replica seats but NOT leases
            assert SCORING.evict(gbm.key) is True
            assert sched.free_count() == 0
            for r in reps:
                assert r.cache.stats()["signatures"] == 0
        finally:
            SCORING.reset()                        # shuts the pool down
        assert sched.free_count() == 2, "leases leaked past shutdown"

    def test_replica_path_matches_predict(self, frame, gbm):
        from h2o3_tpu.orchestration.scheduler import MeshScheduler
        SCORING.configure_replicas(2, scheduler=MeshScheduler(slices=2))
        try:
            rows = _rows(frame, 9)
            out = SCORING.score(gbm.key, rows)["predictions"]
            names = [c for c in frame.names if c != "y"]
            pred = gbm.predict(Frame(names, [frame.vec(c) for c in names]))
            want = np.asarray(pred.vec("pyes").to_numpy())[:9]
            assert np.array_equal(np.asarray(out["pyes"], np.float32), want)
        finally:
            SCORING.reset()

    def test_least_loaded_routing(self, frame, gbm):
        from h2o3_tpu.serving.replicas import ReplicaPool
        pool = ReplicaPool(2, scheduler=None)
        try:
            r0, r1 = pool.replicas
            assert pool.route() is r0              # tie: lowest rid
            with r0._lock:                         # fake load on r0
                pass
            r0.record_dispatch(0.0, 0, 0.0)        # accounting only
            # real load: queued rows
            entry = SCORING._admit(gbm.key)
            b = r0.batcher_for(entry)
            b._window = 5.0
            done = threading.Event()

            def enqueue():
                try:
                    b.submit(np.zeros((4, 3), np.float32),
                             np.full((4, 0), -1, np.int32), 4)
                except Exception:   # noqa: BLE001 — stop() fails it at exit
                    pass
                finally:
                    done.set()

            t = threading.Thread(target=enqueue, daemon=True)
            t.start()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and r0.load() == 0:
                time.sleep(0.005)
            assert r0.load() > 0
            assert pool.route() is r1              # r0 is loaded now
            b.stop()
            done.wait(timeout=5.0)
        finally:
            pool.shutdown()
            SCORING.reset()

    def test_precompile_warms_fresh_replica(self, frame, gbm):
        """Speculative bucket pre-compile at admission: after the warm
        thread joins, the replica's first request is a pure cache hit."""
        from h2o3_tpu.serving.replicas import ScoringReplica
        rep = ScoringReplica(99, scheduler=None)
        try:
            entry = SCORING._admit(gbm.key)
            rep.precompile(entry, buckets=(8, 16)).join(timeout=120)
            st = rep.cache.stats()
            assert st["signatures"] == 2
            misses0 = st["misses"]
            b = rep.batcher_for(entry)
            p = b.submit(*entry.schema.adapt_rows(_rows(frame, 4)), 4)
            assert p.result is not None
            st = rep.cache.stats()
            assert st["misses"] == misses0, \
                "first request on a pre-compiled replica must not compile"
            assert st["hits"] >= 1
        finally:
            rep.stop()
            SCORING.reset()

    def test_scale_up_on_queue_wait_and_down_when_idle(self, frame, gbm):
        from h2o3_tpu.serving.replicas import ReplicaPool
        pool = ReplicaPool(1, scheduler=None, max_replicas=3)
        try:
            assert len(pool.replicas) == 1
            assert pool.maybe_scale(None) is None          # no SLO: no scaling
            for _ in range(8):
                pool.observe_wait(0.5)                     # 500ms >> 25% of SLO
            pool._last_scale = 0.0                         # bypass cooldown
            assert pool.maybe_scale(100.0) == "up"
            assert len(pool.replicas) == 2
            assert pool.scale_ups == 1
            for _ in range(16):
                pool.observe_wait(0.0)                     # idle
            pool._last_scale = 0.0
            assert pool.maybe_scale(100.0) == "down"
            assert len(pool.replicas) == 1
            assert pool.scale_downs == 1
        finally:
            pool.shutdown()

    def test_scale_up_respects_mfu_ceiling(self, monkeypatch):
        from h2o3_tpu.serving import replicas as rmod
        pool = rmod.ReplicaPool(1, scheduler=None, max_replicas=3)
        try:
            monkeypatch.setattr(rmod.ReplicaPool, "mfu_headroom",
                                lambda self: False)
            for _ in range(8):
                pool.observe_wait(0.5)
            pool._last_scale = 0.0
            assert pool.maybe_scale(100.0) is None, \
                "no MFU headroom -> adding replicas cannot help"
            assert len(pool.replicas) == 1
        finally:
            pool.shutdown()

    def test_pool_capped_at_scheduler_slices(self):
        from h2o3_tpu.orchestration.scheduler import MeshScheduler
        from h2o3_tpu.serving.replicas import ReplicaPool
        sched = MeshScheduler(slices=2)
        if sched.n < 2:
            pytest.skip("needs a multi-device (virtual) mesh")
        pool = ReplicaPool(5, scheduler=sched)     # ask for more than slices
        try:
            assert len(pool.replicas) == 2         # an extra would park
            assert pool.max_replicas == 2
        finally:
            pool.shutdown()
        assert sched.free_count() == sched.n

    def test_evicted_entry_seat_is_not_resurrected(self, frame, gbm):
        """A score() racing an eviction between admit and routing must
        hit Evicted (-> transparent re-admit), never silently re-create
        a seat for the dropped model in the replica's cache."""
        from h2o3_tpu.serving.batcher import Evicted
        SCORING.configure_replicas(1)
        try:
            SCORING.score(gbm.key, _rows(frame, 2))
            entry = SCORING._resident[gbm.key]
            rep = SCORING.pool.replicas[0]
            assert SCORING.evict(gbm.key) is True
            assert entry.stopped
            assert rep.cache.stats()["signatures"] == 0
            with pytest.raises(Evicted):
                rep.batcher_for(entry)             # the stale-entry path
            assert rep.cache.stats()["signatures"] == 0
            # the service path re-admits a FRESH entry and serves
            out = SCORING.score(gbm.key, _rows(frame, 2))
            assert len(out["predictions"]["predict"]) == 2
        finally:
            SCORING.reset()

    def test_teardown_repoints_residents_at_local_seat(self, frame, gbm):
        """configure_replicas(0) must re-point already-resident models at
        a fresh local batcher — an entry left holding the shut-down pool
        would 500 on every subsequent request."""
        SCORING.configure_replicas(1)
        try:
            out = SCORING.score(gbm.key, _rows(frame, 3))
            assert out.get("replica") is not None
            SCORING.configure_replicas(0)          # tear the pool down
            assert SCORING.pool is None
            entry = SCORING._resident[gbm.key]
            assert entry.pool is None and entry.batcher is not None
            out = SCORING.score(gbm.key, _rows(frame, 3))
            assert len(out["predictions"]["predict"]) == 3
            assert "replica" not in out
        finally:
            SCORING.reset()

    def test_scaled_up_replica_defers_routing_while_warming(self, frame,
                                                            gbm):
        """A fresh replica must not win least-loaded routing (load 0)
        while its speculative pre-compiles are still running — its first
        requests would pay cold compiles inside someone's budget."""
        from h2o3_tpu.serving.replicas import ReplicaPool
        pool = ReplicaPool(2, scheduler=None)
        try:
            r0, r1 = pool.replicas
            with r1._lock:
                r1._warming = 1                    # pre-compiles in flight
            assert pool.route() is r0, "warming replica must not serve"
            with r1._lock:
                r1._warming = 0
            assert pool.route() in (r0, r1)        # warm again: eligible
            with r0._lock, r1._lock:
                r0._warming = r1._warming = 1      # ALL warming: serve anyway
            assert pool.route() is r0
        finally:
            pool.shutdown()

    def test_env_knob_arms_pool_after_reset(self, frame, gbm, monkeypatch):
        monkeypatch.setenv("H2O3TPU_SCORE_REPLICAS", "2")
        SCORING.reset()                            # re-arms the env check
        try:
            out = SCORING.score(gbm.key, _rows(frame, 3))
            assert out.get("replica") is not None
            assert SCORING.pool is not None
            assert len(SCORING.pool.replicas) >= 1
        finally:
            monkeypatch.delenv("H2O3TPU_SCORE_REPLICAS")
            SCORING.reset()
            assert SCORING.pool is None


class TestRestSurface:
    @pytest.fixture
    def server(self):
        from h2o3_tpu.api import H2OServer
        s = H2OServer(port=0).start()
        yield s
        s.stop()

    @pytest.fixture
    def client(self, server):
        from h2o3_tpu.api import H2OClient
        return H2OClient(server.url)

    def test_priority_and_slo_params_roundtrip(self, frame, gbm, client):
        out = client.score(gbm.key, _rows(frame, 3), priority=7, slo_ms=500)
        assert out["priority"] == 7
        st = client.serving()
        row = next(r for r in st["resident"] if r["model"] == gbm.key)
        assert row["slo"]["target_ms"] == 500.0
        assert row["slo"]["mode"] == "adaptive"
        assert st["shed"] == [] and st["shed_total"] == 0

    def test_shed_is_503_with_retry_after_and_accounted(self, frame, gbm,
                                                        client):
        client.score(gbm.key, _rows(frame, 2), slo_ms=10)
        entry = SCORING._resident[gbm.key]
        entry.slo.record_dispatch(5.0, rows=4096)   # saturate the estimator
        with pytest.raises(RuntimeError, match="503"):
            client.score(gbm.key, _rows(frame, 2), priority=0)
        st = client.serving()
        assert st["shed_total"] >= 1
        assert any(s["reason"] == "overload" and s["priority"] == 0
                   for s in st["shed"])
        text = client.metrics_text()
        assert "h2o3_score_shed_total" in text

    def test_build_beside_serving_completes_and_compiles_nothing(
            self, frame, gbm, client):
        """A GBM build in the same process while ``/3/Score`` is served:
        the build completes, every reply equals the warm one, and the warm
        window compiles no scorer."""
        from h2o3_tpu.models.gbm import GBM
        from h2o3_tpu.utils.telemetry import SCORER_CACHE
        rows = _rows(frame, 16)
        warm = client.score(gbm.key, rows)["predictions"]
        misses0 = SCORER_CACHE.labels(event="miss").value
        err: list = []

        def train():
            try:
                GBM(ntrees=6, max_depth=4, seed=9,
                    model_id="slo_beside").train(y="y", training_frame=frame)
            except Exception as e:   # noqa: BLE001 — asserted below
                err.append(e)

        trainer = threading.Thread(target=train, daemon=True)
        trainer.start()
        served = 0
        while served < 10 or (trainer.is_alive() and served < 500):
            assert client.score(gbm.key, rows)["predictions"] == warm
            served += 1
        trainer.join(timeout=120)
        assert not trainer.is_alive() and not err, err
        assert DKV.get("slo_beside") is not None
        assert SCORER_CACHE.labels(event="miss").value == misses0
        assert client.serving()["shed_total"] == 0

    def test_serving_view_carries_replicas(self, frame, gbm, client):
        from h2o3_tpu.orchestration.scheduler import MeshScheduler
        SCORING.configure_replicas(2, scheduler=MeshScheduler(slices=2))
        try:
            client.score(gbm.key, _rows(frame, 2))
            st = client.serving()
            assert st["replicas"]["count"] == len(SCORING.pool.replicas)
            rep = st["replicas"]["replicas"][0]
            assert {"replica", "slice", "devices", "busy_seconds",
                    "queue_wait_seconds", "models"} <= set(rep)
        finally:
            SCORING.reset()

    def test_bad_priority_is_400(self, frame, gbm, client):
        with pytest.raises(RuntimeError, match="400"):
            client.request("POST", f"/3/Score/{gbm.key}",
                           {"rows": [{"x0": 1.0}], "priority": "high"})
