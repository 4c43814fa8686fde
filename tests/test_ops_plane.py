"""Self-driving ops tests — remediation engine, action catalog audit,
chaos-driven heals, and multi-tenant admission quotas.

The heal tests drive the REAL pipeline end to end: monkeypatched health
probes (the same seams tests/test_health.py uses) trip a rule, the
incident rising edge fires the engine, the engine records exactly one
bounded action against a stub live target, and the next clean sweep
resolves the incident. Stubs stand in for the live targets (scoring
tier, Cleaner, elastic groups) via the actions module's probe seams.
"""

import threading
import time
import urllib.error
import urllib.request

import pytest

from h2o3_tpu.ops_plane import actions as oa
from h2o3_tpu.ops_plane import remediate as orm
from h2o3_tpu.ops_plane import tenancy as ot
from h2o3_tpu.ops_plane.actions import ActionLog
from h2o3_tpu.ops_plane.remediate import RemediationEngine
from h2o3_tpu.ops_plane.tenancy import (QuotaExceeded, QuotaManager,
                                        sanitize_tenant, tenant_scope)
from h2o3_tpu.utils import health as hm
from h2o3_tpu.utils.health import HealthEvaluator
from h2o3_tpu.utils.incidents import IncidentLog


# -- stub live targets --------------------------------------------------------

class _StubPool:
    def __init__(self, n):
        self.replicas = [object()] * n


class _StubScoring:
    """Looks like ScoringService to act_serving_relief/act_pin_bucket."""

    def __init__(self, widens=True, replicas=1, cache=None):
        self._widens = widens
        self.pool = _StubPool(replicas)
        self.cache = cache
        self.widen_calls = 0
        self.restore_calls = 0
        self.replica_history = []

    def widen_admission(self):
        self.widen_calls += 1
        return [{"model": "glm_1", "target_ms": 75.0}] if self._widens else []

    def restore_admission(self):
        self.restore_calls += 1
        return [{"model": "glm_1", "target_ms": 50.0}]

    def configure_replicas(self, n):
        self.replica_history.append(n)
        self.pool = _StubPool(n)


class _StubCache:
    def __init__(self, buckets=(64, 256)):
        self._buckets = sorted(buckets)
        self._pin = None

    def pinned_bucket(self):
        return self._pin

    def compiled_buckets(self):
        return list(self._buckets)

    def pin_bucket(self, bucket):
        self._pin = bucket
        return bucket

    def unpin_bucket(self):
        self._pin = None


class _StubCleaner:
    def __init__(self, budget):
        self.budget = budget
        self.spilled = []

    def last_touched(self, key):
        return 0.0

    def force_spill(self, keys, limit=2):
        done = list(keys)[:limit]
        self.spilled.extend(done)
        return done


class _StubGroup:
    group_id = "grp_test"

    def __init__(self, rows):
        self._rows = rows
        self.reassigned = []
        self.joins = []

    def rows(self):
        return self._rows

    def preempt_reassign(self, wid, reason="ops_preempt"):
        self.reassigned.append(wid)
        return [0, 2]

    def request_join(self, wid):
        self.joins.append(wid)


def _engine(monkeypatch, mode="act", cooldown="0"):
    monkeypatch.setenv("H2O3TPU_REMEDIATE", mode)
    monkeypatch.setenv("H2O3TPU_OPS_COOLDOWN_SECS", cooldown)
    return RemediationEngine(actions=ActionLog())


# -- the action catalog -------------------------------------------------------

def test_observe_mode_records_without_executing(monkeypatch):
    svc = _StubScoring()
    monkeypatch.setattr(oa, "_scoring", lambda: svc)
    log = ActionLog()
    rec = log.record("serving_relief", "serving_shed_rate", "inc_1",
                     "observe")
    assert rec["outcome"] == "observed"
    assert rec["rollback_token"] is None
    assert svc.widen_calls == 0 and svc.replica_history == []
    assert log.recorded_total() == 1       # the decision IS in the trail


def test_unknown_action_is_a_failed_record():
    log = ActionLog()
    rec = log.record("reboot_the_moon", "some_rule", None, "act")
    assert rec["outcome"] == "failed"
    assert "unknown action" in rec["params"]["error"]
    assert log.recorded_total() == 1


def test_serving_relief_widens_admission_first(monkeypatch):
    svc = _StubScoring(widens=True)
    monkeypatch.setattr(oa, "_scoring", lambda: svc)
    log = ActionLog()
    rec = log.record("serving_relief", "serving_shed_rate", "inc_1", "act")
    assert rec["outcome"] == "applied"
    assert rec["params"]["widened"][0]["model"] == "glm_1"
    assert rec["rollback_token"] == rec["id"]
    assert svc.replica_history == []       # widening sufficed
    assert log.rollback(rec["id"]) is True
    assert svc.restore_calls == 1
    assert log.rollback(rec["id"]) is False   # token is single-use
    # the rollback itself is audited
    assert [r["action"] for r in log.list()][0] == "rollback"


def test_serving_relief_adds_one_replica_when_nothing_to_widen(monkeypatch):
    svc = _StubScoring(widens=False, replicas=1)
    monkeypatch.setattr(oa, "_scoring", lambda: svc)
    monkeypatch.setenv("H2O3TPU_OPS_MAX_REPLICAS", "2")
    log = ActionLog()
    rec = log.record("serving_relief", "serving_p99_slo", "inc_2", "act")
    assert rec["outcome"] == "applied" and rec["params"]["replicas"] == 2
    assert svc.replica_history == [2]
    # bounded: at the cap the action SKIPS instead of scaling forever
    rec2 = log.record("serving_relief", "serving_p99_slo", "inc_3", "act")
    assert rec2["outcome"] == "skipped"
    assert rec2["params"]["replica_cap"] == 2
    # rollback removes the replica it added
    assert log.rollback(rec["id"]) is True
    assert svc.replica_history == [2, 1]


def test_raise_cleaner_budget_bounded_at_cap(monkeypatch):
    cleaner = _StubCleaner(budget=1000)
    monkeypatch.setattr(oa, "_cleaner", lambda: cleaner)
    monkeypatch.setenv("H2O3TPU_OPS_CLEANER_CAP_FACTOR", "2.0")
    oa._CLEANER_BASE.pop(id(cleaner), None)
    log = ActionLog()
    rec = log.record("raise_cleaner_budget", "memory_spill_thrash", "i", "act")
    assert rec["outcome"] == "applied" and cleaner.budget == 1500
    rec = log.record("raise_cleaner_budget", "memory_spill_thrash", "i", "act")
    assert rec["outcome"] == "applied" and cleaner.budget == 2000  # cap 2x
    # at the ceiling with no cold tenant: skipped, never unbounded
    rec = log.record("raise_cleaner_budget", "memory_spill_thrash", "i", "act")
    assert rec["outcome"] == "skipped" and cleaner.budget == 2000
    # rollback restores the prior budget
    applied = [r for r in log.list() if r["outcome"] == "applied"]
    assert log.rollback(applied[0]["id"]) is True   # newest applied: 1500->2000
    assert cleaner.budget == 1500


def test_raise_cleaner_budget_evicts_coldest_tenant_at_ceiling(monkeypatch):
    cleaner = _StubCleaner(budget=1000)
    monkeypatch.setattr(oa, "_cleaner", lambda: cleaner)
    monkeypatch.setenv("H2O3TPU_OPS_CLEANER_CAP_FACTOR", "1.0")  # at ceiling

    class _StubQuotas:
        def coldest_tenant(self):
            return "hoarder"

        def keys_of(self, tenant):
            return ["k3", "k1", "k2"]

    monkeypatch.setattr(oa, "_quotas", lambda: _StubQuotas())
    oa._CLEANER_BASE.pop(id(cleaner), None)
    log = ActionLog()
    rec = log.record("raise_cleaner_budget", "memory_spill_thrash", "i", "act")
    assert rec["outcome"] == "applied"
    assert rec["params"]["evicted_tenant"] == "hoarder"
    assert len(rec["params"]["spilled_keys"]) == 2      # bounded to 2 keys
    assert cleaner.budget == 1000                       # budget untouched


def test_reassign_shards_picks_single_worst_worker(monkeypatch):
    g = _StubGroup([
        {"worker": 0, "state": "ACTIVE", "last_heartbeat_ago_ms": 10.0},
        {"worker": 1, "state": "SUSPECT", "last_heartbeat_ago_ms": 9000.0},
        {"worker": 2, "state": "EJECTED", "last_heartbeat_ago_ms": 99999.0},
    ])
    monkeypatch.setattr(oa, "_live_groups", lambda: [g])
    log = ActionLog()
    rec = log.record("reassign_shards", "elastic_heartbeat_gap", "i", "act")
    assert rec["outcome"] == "applied"
    assert rec["params"]["worker"] == 1            # worst LIVE, not EJECTED
    assert rec["params"]["moved_shards"] == [0, 2]
    assert g.reassigned == [1]                     # exactly one worker
    assert log.rollback(rec["id"]) is True
    assert g.joins == [1]


def test_pin_bucket_pins_largest_compiled_and_unpins(monkeypatch):
    cache = _StubCache(buckets=(64, 256))
    monkeypatch.setattr(oa, "_scorer_cache", lambda: cache)
    log = ActionLog()
    rec = log.record("pin_bucket", "compute_recompile_storm", "i", "act")
    assert rec["outcome"] == "applied"
    assert rec["params"]["pinned_bucket"] == 256
    assert cache.pinned_bucket() == 256
    # idempotence bound: an already-pinned cache is a skip, not a re-pin
    rec2 = log.record("pin_bucket", "compute_recompile_storm", "i", "act")
    assert rec2["outcome"] == "skipped"
    assert log.rollback(rec["id"]) is True
    assert cache.pinned_bucket() is None


def test_failed_action_is_audited_not_raised(monkeypatch):
    def boom():
        raise RuntimeError("live target sick")
    monkeypatch.setattr(oa, "_cleaner", boom)
    log = ActionLog()
    rec = log.record("raise_cleaner_budget", "memory_spill_thrash", "i", "act")
    assert rec["outcome"] == "failed"
    assert "RuntimeError" in rec["params"]["error"]
    assert log.recorded_total() == 1


def test_action_log_capacity_bounds_the_trail():
    log = ActionLog(capacity=5)
    for i in range(9):
        log.record("nope", "r", f"i{i}", "observe")
    assert log.recorded_total() == 5
    assert log.list()[0]["incident_id"] == "i8"    # newest first


# -- the engine: kill switch, cooldown, rising edges --------------------------

def test_kill_switch_off_records_nothing(monkeypatch):
    eng = _engine(monkeypatch, mode="off")
    assert eng.on_incident({"id": "i", "rule": "serving_shed_rate"},
                           None) is None
    assert eng.actions.recorded_total() == 0


def test_default_and_unknown_modes_read_observe(monkeypatch):
    monkeypatch.delenv("H2O3TPU_REMEDIATE", raising=False)
    assert orm.remediate_mode() == "observe"
    monkeypatch.setenv("H2O3TPU_REMEDIATE", "yolo")
    assert orm.remediate_mode() == "observe"       # typos fail safe
    monkeypatch.setenv("H2O3TPU_REMEDIATE", " ACT ")
    assert orm.remediate_mode() == "act"


def test_unmapped_rule_pages_a_human(monkeypatch):
    eng = _engine(monkeypatch, mode="act")
    assert eng.on_incident({"id": "i", "rule": "memory_leak_growth"},
                           None) is None
    assert eng.actions.recorded_total() == 0


def test_cooldown_rate_limits_per_rule(monkeypatch):
    eng = _engine(monkeypatch, mode="observe", cooldown="3600")
    assert eng.on_incident({"id": "i1", "rule": "serving_shed_rate"},
                           None) is not None
    # same rule inside the cooldown: suppressed, NOT appended
    assert eng.on_incident({"id": "i2", "rule": "serving_shed_rate"},
                           None) is None
    # a different rule has its own cooldown clock
    assert eng.on_incident({"id": "i3", "rule": "memory_spill_thrash"},
                           None) is not None
    assert eng.actions.recorded_total() == 2


def test_rising_edge_fires_once_per_episode(monkeypatch):
    eng = _engine(monkeypatch, mode="observe")
    log = IncidentLog(capacity=8)
    eng.install(log)
    try:
        log.open("serving_shed_rate", "serving", "degraded", "m", 0.4, 0.05)
        log.open("serving_shed_rate", "serving", "degraded", "m", 0.5, 0.05)
        assert eng.actions.recorded_total() == 1   # repeat folded, no refire
        log.resolve("serving_shed_rate")
        log.open("serving_shed_rate", "serving", "degraded", "m", 0.6, 0.05)
        assert eng.actions.recorded_total() == 2   # new episode, new edge
    finally:
        eng.uninstall()


def test_act_mode_stamps_action_id_into_incident(monkeypatch):
    svc = _StubScoring()
    monkeypatch.setattr(oa, "_scoring", lambda: svc)
    eng = _engine(monkeypatch, mode="act")
    log = IncidentLog(capacity=8)
    eng.install(log)
    try:
        log.open("serving_shed_rate", "serving", "degraded", "m", 0.4, 0.05)
        [inc] = log.list()
        rec = eng.actions.list()[0]
        assert inc["action_id"] == rec["id"]
        assert rec["incident_id"] == inc["id"]
        full = log.get(inc["id"])
        assert full["context"]["remediation_action"] == rec["id"]
    finally:
        eng.uninstall()


def test_policy_view_names_mode_map_and_bounds(monkeypatch):
    monkeypatch.setenv("H2O3TPU_REMEDIATE", "observe")
    view = RemediationEngine(actions=ActionLog()).policy_view()
    assert view["mode"] == "observe"
    assert view["policy"]["memory_spill_thrash"] == "raise_cleaner_budget"
    assert view["bounds"]["reassign_workers_per_action"] == 1
    assert view["bounds"]["spill_keys_per_action"] == 2


# -- chaos-driven heals (the acceptance demo, one per failure class) ----------

def _healing_rig(monkeypatch, mode="act"):
    """A private evaluator + engine pair wired rising-edge to each other."""
    ev = HealthEvaluator(interval_s=9.0, incidents=IncidentLog(capacity=16))
    eng = _engine(monkeypatch, mode=mode)
    eng.install(ev.incidents)
    return ev, eng


def test_spill_thrash_heals_with_one_budget_raise(monkeypatch):
    cleaner = _StubCleaner(budget=1 << 20)
    monkeypatch.setattr(oa, "_cleaner", lambda: cleaner)
    oa._CLEANER_BASE.pop(id(cleaner), None)
    stats = {"spill_count": 0, "restore_count": 0}
    monkeypatch.setattr(hm, "_cleaner_stats", lambda: dict(stats))
    ev, eng = _healing_rig(monkeypatch)
    try:
        ev.evaluate()                              # window baseline
        stats.update(spill_count=6, restore_count=6)   # ping-pong chaos
        ev.evaluate()                              # trips -> edge -> action
        assert cleaner.budget == int((1 << 20) * 1.5)
        applied = [r for r in eng.actions.list() if r["outcome"] == "applied"]
        assert [r["action"] for r in applied] == ["raise_cleaner_budget"]
        # counters quiet next sweep (working set fits) -> incident resolves
        ev.evaluate()
        [inc] = ev.incidents.list(state="resolved")
        assert inc["rule"] == "memory_spill_thrash"
        assert inc["resolved_at"] is not None
        assert inc["action_id"] == applied[0]["id"]
        assert ev.incidents.list(state="open") == []
    finally:
        eng.uninstall()


def test_serving_overload_heals_with_one_admission_widen(monkeypatch):
    svc = _StubScoring(widens=True)
    monkeypatch.setattr(oa, "_scoring", lambda: svc)
    stats = {"shed_total": 0,
             "resident": [{"model": "glm_1",
                           "slo": {"target_ms": 50.0, "p99_ms": 20.0}}]}
    total = [100.0]
    monkeypatch.setattr(hm, "_serving_stats", lambda: dict(stats))
    monkeypatch.setattr(hm, "_score_requests_total", lambda: total[0])
    ev, eng = _healing_rig(monkeypatch)
    try:
        ev.evaluate()                              # baseline
        stats["shed_total"], total[0] = 40, 200.0  # 40/100 shed this window
        ev.evaluate()
        assert svc.widen_calls == 1                # exactly one action
        applied = [r for r in eng.actions.list() if r["outcome"] == "applied"]
        assert [r["action"] for r in applied] == ["serving_relief"]
        assert applied[0]["rule"] == "serving_shed_rate"
        ev.evaluate()                              # traffic drained: quiet
        [inc] = ev.incidents.list(state="resolved")
        assert inc["rule"] == "serving_shed_rate"
        assert inc["action_id"] == applied[0]["id"]
    finally:
        eng.uninstall()


def test_stalled_worker_heals_with_one_preemptive_reassign(monkeypatch):
    rows = [{"worker": 0, "state": "ACTIVE", "last_heartbeat_ago_ms": 10.0},
            {"worker": 1, "state": "ACTIVE",
             "last_heartbeat_ago_ms": 120_000.0}]
    g = _StubGroup(rows)
    monkeypatch.setattr(oa, "_live_groups", lambda: [g])
    monkeypatch.setattr(hm, "_elastic_rows", lambda: list(rows))
    monkeypatch.setenv("H2O3TPU_HEALTH_HEARTBEAT_GAP_SECS", "30")
    ev, eng = _healing_rig(monkeypatch)
    try:
        ev.evaluate()                              # gap rule: no window
        assert g.reassigned == [1]                 # one bounded reassignment
        applied = [r for r in eng.actions.list() if r["outcome"] == "applied"]
        assert [r["action"] for r in applied] == ["reassign_shards"]
        rows[1] = {"worker": 1, "state": "EJECTED",
                   "last_heartbeat_ago_ms": 120_000.0}
        ev.evaluate()                              # silence now accounted
        [inc] = ev.incidents.list(state="resolved")
        assert inc["rule"] == "elastic_heartbeat_gap"
        assert inc["action_id"] == applied[0]["id"]
    finally:
        eng.uninstall()


def test_clean_sweeps_take_no_action(rng, monkeypatch):
    """The negative of the three heals: sweeps over clean registries with a
    warm build between them open no incident and record no action — an
    engine that remediates normal operation pages ops with changes nobody
    asked for."""
    import numpy as np

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.glm import GLM
    X = rng.normal(size=(400, 4)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(4)}
    cols["y"] = np.where(X[:, 0] - X[:, 1] > 0, "Y", "N")
    fr = Frame.from_arrays(cols)

    def build():
        GLM(family="binomial", lambda_=1e-4, max_iterations=8).train(
            y="y", training_frame=fr)

    build()                 # compiles land outside the watched window
    ev, eng = _healing_rig(monkeypatch)
    try:
        ev.evaluate()                              # window baseline
        build()
        ev.evaluate()
        assert ev.incidents.opened_total() == 0
        assert eng.actions.recorded_total() == 0
    finally:
        eng.uninstall()


def test_observe_mode_heals_nothing_but_logs_the_decision(monkeypatch):
    cleaner = _StubCleaner(budget=1 << 20)
    monkeypatch.setattr(oa, "_cleaner", lambda: cleaner)
    stats = {"spill_count": 0, "restore_count": 0}
    monkeypatch.setattr(hm, "_cleaner_stats", lambda: dict(stats))
    ev, eng = _healing_rig(monkeypatch, mode="observe")
    try:
        ev.evaluate()
        stats.update(spill_count=6, restore_count=6)
        ev.evaluate()
        assert cleaner.budget == 1 << 20           # UNTOUCHED
        recs = eng.actions.list()
        assert [r["outcome"] for r in recs] == ["observed"]
        [inc] = ev.incidents.list(state="open")
        assert inc["action_id"] is None            # nothing to stamp
    finally:
        eng.uninstall()


# -- incident API satellites --------------------------------------------------

def test_incident_state_filter_and_resolution_stamps():
    log = IncidentLog(capacity=8)
    log.open("rule_a", "serving", "degraded", "m", 1, 0)
    log.open("rule_b", "memory", "degraded", "m", 1, 0)
    log.resolve("rule_a")
    opens = log.list(state="open")
    resolved = log.list(state="resolved")
    assert [r["rule"] for r in opens] == ["rule_b"]
    assert [r["rule"] for r in resolved] == ["rule_a"]
    assert resolved[0]["resolved_at"] is not None
    assert opens[0]["resolved_at"] is None
    assert {r["rule"] for r in log.list()} == {"rule_a", "rule_b"}
    with pytest.raises(ValueError):
        log.list(state="everything")


def test_listener_faults_are_isolated():
    log = IncidentLog(capacity=8)
    calls = []

    def bad_listener(record, src):
        raise RuntimeError("listener bug")

    def good_listener(record, src):
        calls.append(record["rule"])

    log.add_listener(bad_listener)
    log.add_listener(good_listener)
    log.open("rule_x", "serving", "degraded", "m", 1, 0)
    assert calls == ["rule_x"]          # the bad one didn't block the good
    [inc] = log.list()                  # ...or the open itself
    assert inc["rule"] == "rule_x"
    log.remove_listener(bad_listener)
    log.remove_listener(good_listener)


# -- multi-tenant admission ---------------------------------------------------

def test_sanitize_tenant_contract():
    assert sanitize_tenant(None) == "default"
    assert sanitize_tenant("") == "default"
    assert sanitize_tenant("team-a.prod_1") == "team-a.prod_1"
    with pytest.raises(ValueError):
        sanitize_tenant("bad tenant!")
    with pytest.raises(ValueError):
        sanitize_tenant("x" * 65)


def test_tenant_scope_binds_context():
    assert ot.current_tenant() == "default"
    with tenant_scope("team-a"):
        assert ot.current_tenant() == "team-a"
        with tenant_scope(None):
            assert ot.current_tenant() == "default"
        assert ot.current_tenant() == "team-a"
    assert ot.current_tenant() == "default"


def test_qps_quota_sheds_with_retry_after():
    qm = QuotaManager()
    qm.set_quota("team-a", qps=2)
    assert qm.admit("team-a") == "team-a"
    qm.admit("team-a")
    with pytest.raises(QuotaExceeded) as ei:
        qm.admit("team-a")
    assert ei.value.dimension == "qps"
    assert ei.value.retry_after_s > 0
    assert "429" not in str(ei.value)   # the REST layer owns the status
    # the shed is visible in usage, never silent
    assert qm.usage("team-a")["shed"] == {"qps": 1}
    # an unquota'd tenant is admitted freely
    for _ in range(5):
        qm.admit("team-b")


def test_device_seconds_quota_windows_out(monkeypatch):
    monkeypatch.setenv("H2O3TPU_TENANT_WINDOW_SECS", "1")
    qm = QuotaManager()
    qm.set_quota("team-a", device_seconds=0.5)
    qm.charge_device_seconds("team-a", 0.6)
    with pytest.raises(QuotaExceeded) as ei:
        qm.admit("team-a")
    assert ei.value.dimension == "device_seconds"
    u = qm.usage("team-a")
    assert u["device_seconds_window"] == 0.6
    assert u["device_seconds_total"] == 0.6
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:   # the charge ages out of the window
        try:
            qm.admit("team-a")
            break
        except QuotaExceeded:
            time.sleep(0.05)
    else:
        pytest.fail("device-seconds charge never aged out of the window")
    assert qm.usage("team-a")["device_seconds_total"] == 0.6  # lifetime stays


def test_bytes_quota_prices_owned_keys(monkeypatch):
    qm = QuotaManager()
    qm.set_quota("team-a", bytes=1000)
    with tenant_scope("team-a"):
        qm.tag_key("frame_a")
    monkeypatch.setattr(QuotaManager, "_bytes_locked",
                        lambda self, tenant: 2048 if tenant == "team-a"
                        else 0)
    with pytest.raises(QuotaExceeded) as ei:
        qm.admit("team-a")
    assert ei.value.dimension == "bytes" and ei.value.observed == 2048
    assert qm.owner_of("frame_a") == "team-a"
    qm.untag_key("frame_a")
    assert qm.owner_of("frame_a") is None


def test_coldest_tenant_never_the_default(monkeypatch):
    qm = QuotaManager()
    qm.set_quota("default", bytes=10)
    qm.set_quota("hoarder", bytes=10)
    monkeypatch.setattr(QuotaManager, "_bytes_locked",
                        lambda self, tenant: 4096)
    assert qm.coldest_tenant() == "hoarder"
    qm.remove_quota("hoarder")
    assert qm.coldest_tenant() is None   # only default left: nobody


def test_usage_all_covers_every_known_tenant():
    qm = QuotaManager()
    qm.set_quota("team-a", qps=100)
    qm.charge_device_seconds("team-b", 0.1)
    tenants = {u["tenant"] for u in qm.usage_all()}
    assert {"default", "team-a", "team-b"} <= tenants


# -- REST surface -------------------------------------------------------------

@pytest.fixture
def server():
    from h2o3_tpu.api.server import H2OServer
    s = H2OServer(port=0).start()
    yield s
    s.stop()
    ot.QUOTAS.reset()
    from h2o3_tpu.ops_plane import ACTIONS, ENGINE
    ACTIONS.reset()
    ENGINE.reset()


@pytest.fixture
def client(server):
    from h2o3_tpu.api.client import H2OClient
    return H2OClient(server.url)


def test_ops_endpoint_serves_policy_actions_tenants(client):
    out = client.ops()
    assert out["__meta"]["schema_type"] == "OpsV3"
    assert out["remediation"]["mode"] in ("off", "observe", "act")
    assert "policy" in out["remediation"]
    assert isinstance(out["actions"], list)
    assert any(u["tenant"] == "default" for u in out["tenants"])


def test_quota_crud_via_rest(client):
    q = client.set_quota("team-a", qps=10, bytes=1 << 20)
    assert q == {"tenant": "team-a", "qps": 10.0,
                 "device_seconds": None, "bytes": 1 << 20}
    out = client.ops()
    assert any(r["tenant"] == "team-a" and r["qps"] == 10.0
               for r in out["quotas"])
    assert client.remove_quota("team-a") is True
    assert client.remove_quota("team-a") is False
    with pytest.raises(RuntimeError, match="400"):
        client.set_quota("bad tenant!", qps=1)


def test_two_tenant_overload_sheds_only_the_over_quota_tenant(server):
    """ISSUE acceptance: tenant A blows its budget and gets 429 +
    Retry-After; tenant B's requests keep landing untouched."""
    from h2o3_tpu.api.client import H2OClient
    a = H2OClient(server.url, tenant="team-a")
    b = H2OClient(server.url, tenant="team-b")
    a.set_quota("team-a", qps=2)

    def post_file(cli):
        req = urllib.request.Request(
            server.url + "/3/PostFile", data=b"x,y\n1,2\n",
            headers={"X-H2O3-Tenant": cli.tenant}, method="POST")
        return urllib.request.urlopen(req, timeout=30)

    assert post_file(a).status == 200
    assert post_file(a).status == 200
    with pytest.raises(urllib.error.HTTPError) as ei:
        post_file(a)
    assert ei.value.code == 429
    assert int(ei.value.headers["Retry-After"]) >= 1
    # tenant B rides through the same instant, same route
    for _ in range(3):
        assert post_file(b).status == 200
    # the shed is ledgered, not silent
    usage = {u["tenant"]: u for u in a.ops()["tenants"]}
    assert usage["team-a"]["shed"].get("qps", 0) >= 1
    assert usage["team-b"]["shed"] == {}


def test_tenant_query_param_and_bad_tenant_400(server):
    req = urllib.request.Request(
        server.url + "/3/PostFile?tenant=team-q", data=b"x\n1\n",
        method="POST")
    assert urllib.request.urlopen(req, timeout=30).status == 200
    usage = {u["tenant"] for u in ot.QUOTAS.usage_all()}
    assert "team-q" in usage
    bad = urllib.request.Request(
        server.url + "/3/Ping", headers={"X-H2O3-Tenant": "no spaces"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(bad, timeout=30)
    assert ei.value.code == 400


def test_get_routes_are_never_quota_metered(server, client):
    client.set_quota("team-a", qps=0)     # zero budget: every POST sheds
    t = __import__("h2o3_tpu.api.client", fromlist=["H2OClient"]) \
        .H2OClient(server.url, tenant="team-a")
    assert t.ops()["__meta"]["schema_type"] == "OpsV3"   # GET still lands
    assert t.request("GET", "/3/Cloud")["cloud_healthy"] in (True, False)


def test_incidents_rest_state_filter_and_action_stamp(server, client,
                                                      monkeypatch):
    from h2o3_tpu.utils.incidents import INCIDENTS
    svc = _StubScoring()
    monkeypatch.setattr(oa, "_scoring", lambda: svc)
    monkeypatch.setenv("H2O3TPU_REMEDIATE", "act")
    monkeypatch.setenv("H2O3TPU_OPS_COOLDOWN_SECS", "0")
    try:
        INCIDENTS.open("serving_shed_rate", "serving", "degraded",
                       "m", 0.4, 0.05)
        opens = client.incidents(state="open")
        rule_rows = [r for r in opens if r["rule"] == "serving_shed_rate"]
        assert rule_rows and rule_rows[0]["action_id"] is not None
        # the stamped action is fetchable from the ops log
        acts = {r["id"] for r in client.ops()["actions"]}
        assert rule_rows[0]["action_id"] in acts
        INCIDENTS.resolve("serving_shed_rate")
        resolved = client.incidents(state="resolved")
        row = [r for r in resolved if r["rule"] == "serving_shed_rate"][0]
        assert row["resolved_at"] is not None
        with pytest.raises(RuntimeError, match="400"):
            client.incidents(state="everything")
    finally:
        INCIDENTS.reset()


def test_rollback_via_rest(server, client, monkeypatch):
    svc = _StubScoring()
    monkeypatch.setattr(oa, "_scoring", lambda: svc)
    from h2o3_tpu.ops_plane import ACTIONS
    rec = ACTIONS.record("serving_relief", "serving_shed_rate", None, "act")
    assert rec["outcome"] == "applied"
    assert client.rollback_action(rec["id"]) is True
    assert svc.restore_calls == 1
    assert client.rollback_action(rec["id"]) is False


# -- scoring charges device-seconds to the bound tenant -----------------------

def test_scoring_charges_device_seconds(rng):
    import numpy as np

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.serving import service as svc_mod
    X = rng.normal(size=(200, 3)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(3)}
    cols["y"] = np.where(X[:, 0] > 0, "yes", "no")
    fr = Frame.from_arrays(cols, key="ops_glm_train")
    glm = GLM(family="binomial", lambda_=1e-4,
              model_id="ops_glm").train(y="y", training_frame=fr)
    rows = [{f"x{i}": float(X[r, i]) for i in range(3)} for r in range(8)]
    svc_mod.SCORING.reset()
    before = ot.QUOTAS.usage("team-score")["device_seconds_total"]
    try:
        with tenant_scope("team-score"):
            out = svc_mod.SCORING.score(glm.key, rows)
        assert len(out["predictions"]["predict"]) == 8
        after = ot.QUOTAS.usage("team-score")["device_seconds_total"]
        assert after > before      # the batch share landed on the tenant
    finally:
        svc_mod.SCORING.reset()
