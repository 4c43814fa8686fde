"""Distributed request tracing tests: span trees, contextvar propagation
through jobs and build pools, W3C traceparent round trips, straggler
attribution, Perfetto export, trace-store bounds, and the TimeLine epoch /
fault-injection satellites (reference: water/TimeLine + TimelineHandler)."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.api import H2OServer
from h2o3_tpu.api.client import H2OClient
from h2o3_tpu.utils import tracing
from h2o3_tpu.utils.tracing import (TRACER, Tracer, critical_path,
                                    format_traceparent, parse_traceparent,
                                    span_tree, to_chrome_trace)

# -- traceparent parsing -----------------------------------------------------


def test_traceparent_round_trip():
    ctx = tracing.SpanContext("ab" * 16, "cd" * 8)
    hdr = format_traceparent(ctx)
    assert hdr == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = parse_traceparent(hdr)
    assert back.trace_id == ctx.trace_id and back.span_id == ctx.span_id


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-span-01",
    f"00-{'0' * 32}-{'cd' * 8}-01",        # all-zero trace id
    f"00-{'ab' * 16}-{'0' * 16}-01",       # all-zero span id
    f"ff-{'ab' * 16}-{'cd' * 8}-01",       # forbidden version
])
def test_traceparent_rejects_invalid(bad):
    assert parse_traceparent(bad) is None


# -- tracer core -------------------------------------------------------------


def test_span_tree_and_critical_path():
    tr = Tracer(capacity=8)
    with tr.span("root", kind="server", root=True) as root:
        tid = root.trace_id
        with tr.span("fast", kind="work"):
            pass
        with tr.span("slow", kind="work"):
            with tr.span("inner", kind="work"):
                pass
    trace = tr.get_trace(tid)
    assert trace["nspans"] == 4 and trace["status"] == "ok"
    roots = span_tree(trace)
    assert len(roots) == 1 and roots[0]["name"] == "root"
    assert {c["name"] for c in roots[0]["children"]} == {"fast", "slow"}
    cp = [e["name"] for e in critical_path(trace)]
    assert cp[0] == "root" and cp[-1] == "inner"


def test_child_spans_silent_without_active_trace():
    tr = Tracer(capacity=4)
    with tr.span("orphan", kind="work") as s:   # no root, no active trace
        assert s is None
    assert tr.list_traces() == []


def test_trace_off_env_disables_roots(monkeypatch):
    monkeypatch.setenv("H2O3TPU_TRACE_OFF", "1")
    tr = Tracer(capacity=4)
    with tr.span("root", kind="server", root=True) as s:
        assert s is None
    assert tr.list_traces() == []


def test_trace_store_ring_eviction():
    tr = Tracer(capacity=4)
    ids = []
    for i in range(7):
        with tr.span(f"t{i}", root=True) as s:
            ids.append(s.trace_id)
    done = tr.list_traces()
    assert len(done) == 4                       # ring bound
    assert [t["name"] for t in done] == ["t6", "t5", "t4", "t3"]  # newest 1st
    with pytest.raises(KeyError):
        tr.get_trace(ids[0])                    # oldest evicted


def test_retention_bridges_root_end_to_worker_start():
    """A Job-style hand-off: the root span ends before the worker begins —
    the captured context must keep the trace open until the worker span
    ends, then finalize it as ONE connected trace."""
    tr = Tracer(capacity=4)
    with tr.span("request", kind="server", root=True) as root:
        tid = root.trace_id
        token = tracing._CURRENT.set(root.context)
        ctx = tr.capture()
        tracing._CURRENT.reset(token)
    assert ctx is not None
    assert tr.get_trace(tid).get("in_progress")   # retained: still open
    assert all(t["trace_id"] != tid for t in tr.list_traces())
    with tr.adopt(ctx, "job:late", kind="job") as jspan:
        assert jspan.parent_id == root.span_id
    trace = tr.get_trace(tid)
    assert {s["name"] for s in trace["spans"]} == {"request", "job:late"}


def test_get_trace_serves_newest_record_for_shared_trace_id():
    """Same-traceparent callers produce several completed records under
    one trace_id; lookups must serve the newest (the substantive one)."""
    tr = Tracer(capacity=8)
    ctx = tracing.SpanContext("ab" * 16, "cd" * 8)
    with tr.span("first", root=True, parent=ctx):
        pass
    with tr.span("second", root=True, parent=ctx):
        with tr.span("work"):
            pass
    got = tr.get_trace("ab" * 16)
    assert {s["name"] for s in got["spans"]} == {"second", "work"}


def test_open_trace_eviction_spares_retained_traces():
    """The open-trace cap must prefer victims nobody retains: evicting a
    Job-retained trace would let the late adopt() recreate the entry and
    emit a duplicate record."""
    tr = Tracer(capacity=16, max_open=2)
    with tr.span("held", root=True) as held:
        held_tid = held.trace_id
        token = tracing._CURRENT.set(held.context)
        ctx = tr.capture()                       # pending retention
        tracing._CURRENT.reset(token)
    # two more open traces push past max_open=2; the retained one survives
    spans = [tr.begin(f"open{i}", root=True) for i in range(3)]
    with tr.adopt(ctx, "job:late", kind="job"):
        pass
    trace = tr.get_trace(held_tid)               # ONE record, connected
    assert {s["name"] for s in trace["spans"]} == {"held", "job:late"}
    assert not trace.get("in_progress")
    for s in spans:
        tr.end(s)


def test_exception_marks_span_error():
    tr = Tracer(capacity=4)
    with pytest.raises(RuntimeError):
        with tr.span("boom", root=True) as s:
            tid = s.trace_id
            raise RuntimeError("nope")
    trace = tr.get_trace(tid)
    assert trace["status"] == "error"
    assert trace["spans"][0]["attrs"]["exception"].startswith("RuntimeError")


# -- chrome trace export -----------------------------------------------------


def test_chrome_export_schema_and_nesting():
    tr = Tracer(capacity=4)
    with tr.span("root", root=True) as root:
        tid = root.trace_id
        with tr.span("a"):
            with tr.span("b"):
                pass
        with tr.span("c"):
            pass
    chrome = to_chrome_trace(tr.get_trace(tid))
    assert chrome["displayTimeUnit"] == "ms"
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in chrome["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 4
    assert any(m["name"] == "process_name" for m in metas)
    for e in xs:
        assert {"ph", "ts", "dur", "pid", "tid", "name", "cat",
                "args"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] > 0
    # nesting consistency: within one (pid, tid) lane, complete events
    # sorted by ts must properly nest (no partial overlap)
    by_lane: dict = {}
    for e in xs:
        by_lane.setdefault((e["pid"], e["tid"]), []).append(e)
    for lane in by_lane.values():
        lane.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in lane:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
                stack.pop()
            if stack:
                assert e["ts"] + e["dur"] <= \
                    stack[-1]["ts"] + stack[-1]["dur"] + 1e-6
            stack.append(e)


# -- map_reduce partition spans + straggler attribution ----------------------


def test_dispatch_records_partition_spans_and_straggler_attrs(rng, monkeypatch):
    """Full-fidelity partition tracing rides behind H2O3TPU_TRACE_PARTITIONS=1
    (ISSUE 7): with it set, every traced dispatch syncs and stamps shard
    readiness sub-spans + straggler attrs."""
    import jax.numpy as jnp

    from h2o3_tpu.ops.map_reduce import map_reduce

    monkeypatch.setenv("H2O3TPU_TRACE_PARTITIONS", "1")
    x = jnp.asarray(rng.normal(size=64).astype(np.float32))

    def total(shard):
        return shard.sum()

    with TRACER.span("mr_root", root=True) as root:
        tid = root.trace_id
        map_reduce(total, x)
    trace = TRACER.get_trace(tid)
    dispatch = [s for s in trace["spans"] if s["kind"] == "dispatch"]
    parts = [s for s in trace["spans"] if s["kind"] == "partition"]
    assert len(dispatch) == 1 and parts
    d = dispatch[0]
    assert d["name"] == "map_reduce:total"
    assert d["parent_id"] == root.span_id
    for key in ("part_dur_min_ns", "part_dur_max_ns", "straggler",
                "straggler_device"):
        assert key in d["attrs"]
    assert all(p["parent_id"] == d["span_id"] for p in parts)
    assert len(parts) == d["attrs"]["partitions"]
    assert d["attrs"]["sampled"] is True


def test_unsampled_dispatch_skips_partition_spans(rng, monkeypatch):
    """Without H2O3TPU_TRACE_PARTITIONS, an UNSAMPLED traced dispatch must
    not serialize on per-shard readiness: the dispatch span records (the
    tree stays connected) but no partition sub-spans, no straggler attrs,
    and no blocking sync ride along."""
    import sys

    import jax.numpy as jnp

    from h2o3_tpu.ops.map_reduce import map_reduce

    mr = sys.modules["h2o3_tpu.ops.map_reduce"]
    monkeypatch.delenv("H2O3TPU_TRACE_PARTITIONS", raising=False)
    monkeypatch.setattr(mr, "_SAMPLE_EVERY", 10 ** 9)
    next(mr._dispatch_seq)            # burn seq 0 — never the sampled slot
    x = jnp.asarray(rng.normal(size=64).astype(np.float32))

    with TRACER.span("mr_async_root", root=True) as root:
        tid = root.trace_id
        map_reduce(lambda s: s.sum(), x)
    trace = TRACER.get_trace(tid)
    dispatch = [s for s in trace["spans"] if s["kind"] == "dispatch"]
    parts = [s for s in trace["spans"] if s["kind"] == "partition"]
    assert len(dispatch) == 1 and parts == []
    d = dispatch[0]
    assert d["attrs"]["sampled"] is False
    assert "straggler" not in d["attrs"]


def test_straggler_attribution_names_the_slow_shard_not_the_last():
    """Readiness times from sequential blocking are cumulative (monotone),
    so argmax of the raw durations would ALWAYS name the last shard; the
    attribution must key on the incremental wait — where readiness jumps."""
    from h2o3_tpu.ops.map_reduce import _shard_waits

    t0 = 1_000
    # shard 2 straggles: readiness jumps 1_000 → 9_000 there; shards 3-7
    # were already done and add ~nothing
    ends = [1_500, 2_000, 9_000, 9_010, 9_020, 9_030, 9_040, 9_050]
    waits = _shard_waits(ends, t0)
    assert waits.index(max(waits)) == 2
    assert waits[0] == 500 and waits[2] == 7_000 and waits[-1] == 10


def test_effective_nobs_reflects_skip_rows(rng):
    """The per-build map_reduce rollup must count the weights the fit
    actually used: GLM Skip zeroes NA-row weights, so those rows must not
    appear in effective_nobs."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.glm import GLM

    n = 100
    x = rng.normal(size=n).astype(np.float32)
    x[:20] = np.nan                            # 20 rows unusable under Skip
    y = 3 * np.nan_to_num(x) + rng.normal(size=n).astype(np.float32) * 0.1
    fr = Frame.from_arrays({"x": x, "y": y})
    m = GLM(lambda_=0.0, missing_values_handling="Skip").train(
        y="y", training_frame=fr)
    assert m.output["effective_nobs"] == n - 20
    m2 = GLM(lambda_=0.0).train(y="y", training_frame=fr)  # MeanImputation
    assert m2.output["effective_nobs"] == n


def test_fault_injection_marks_span_status(rng, monkeypatch):
    """Satellite: injected drops/delays must surface on the active span —
    fault-injection runs are visible in trace trees."""
    import jax.numpy as jnp

    # retries off: the drop must surface as FaultInjected and leave the
    # span in error state (the retried/absorbed path is covered in
    # tests/test_chaos.py)
    monkeypatch.setenv("H2O3TPU_DISPATCH_RETRIES", "0")

    from h2o3_tpu.ops.map_reduce import map_reduce
    from h2o3_tpu.utils.timeline import FaultInjected, inject_faults

    x = jnp.asarray(rng.normal(size=32).astype(np.float32))

    with TRACER.span("delay_root", root=True) as root:
        tid = root.trace_id
        with inject_faults(delay_ms=3, delay_rate=1.0):
            map_reduce(lambda s: s.sum(), x)
    trace = TRACER.get_trace(tid)
    delayed = [s for s in trace["spans"] if s["status"] == "delayed"]
    assert delayed and delayed[0]["kind"] == "dispatch"
    assert delayed[0]["attrs"]["delay_ns"] > 0
    assert trace["status"] == "delayed"

    with TRACER.span("drop_root", root=True) as root:
        tid = root.trace_id
        with inject_faults(drop_rate=1.0):
            with pytest.raises(FaultInjected):
                map_reduce(lambda s: s.sum(), x)
    trace = TRACER.get_trace(tid)
    errs = [s for s in trace["spans"] if s["status"] == "error"]
    assert errs and any("drop:map_reduce" == s["attrs"].get("fault")
                        for s in errs)
    assert trace["status"] == "error"


# -- TimeLine epoch + fault duration satellites ------------------------------


def test_timeline_clear_epoch_drops_stale_events():
    from h2o3_tpu.utils.timeline import TimeLine

    tl = TimeLine(size=8)
    for i in range(5):
        tl.record("test", f"old{i}")
    tl.clear()
    assert tl.snapshot() == []               # nothing stale served
    tl.record("test", "new0")
    tl.record("test", "new1")
    whats = [e["what"] for e in tl.snapshot()]
    assert whats == ["new0", "new1"]         # old-epoch slots invisible


def test_timeline_clear_is_race_safe_under_hammer():
    from h2o3_tpu.utils.timeline import TimeLine

    tl = TimeLine(size=32)
    stop = threading.Event()
    bad: list = []

    def reader():
        while not stop.is_set():
            for e in tl.snapshot():
                if not e["what"].startswith("ep"):
                    bad.append(e)

    th = threading.Thread(target=reader)
    th.start()
    for epoch in range(50):
        for i in range(40):                  # wraps the ring each epoch
            tl.record("test", f"ep{epoch}_{i}")
        tl.clear()
    stop.set()
    th.join()
    assert not bad


def test_delay_fault_records_true_duration(rng):
    import jax.numpy as jnp

    from h2o3_tpu.ops.map_reduce import map_reduce
    from h2o3_tpu.utils.timeline import TIMELINE, inject_faults

    TIMELINE.clear()
    x = jnp.asarray(rng.normal(size=32).astype(np.float32))
    with inject_faults(delay_ms=5, delay_rate=1.0) as inj:
        map_reduce(lambda s: s.sum(), x)
    assert inj.delayed == 1
    faults = [e for e in TIMELINE.snapshot() if e["kind"] == "fault"]
    assert faults and faults[0]["what"] == "delay:map_reduce"
    assert faults[0]["dur_ns"] >= 5_000_000   # the TRUE stall, not 0


# -- REST surface ------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    s = H2OServer(port=0).start()
    yield s
    s.stop()


def _get(server, path, headers=None):
    req = urllib.request.Request(server.url + path, headers=headers or {})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read()), dict(r.headers)


def _never_stored(trace_id, timeout=2.0):
    """The reply is written INSIDE the request's root span (the span times
    it), so a client holds the reply while the handler thread is still
    ending the span, and until then the trace is in flight. Poll until the
    tracer has let go of it; True if it then is in no store. (A trace that
    IS stored is awaited with ``_wait_trace``.)"""
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            TRACER.get_trace(trace_id)
        except KeyError:
            return True
        time.sleep(0.01)
    return False


def test_response_carries_traceparent_and_trace_completes(server):
    _, headers = _get(server, "/3/Capabilities")
    tp = parse_traceparent(headers.get("traceparent"))
    assert tp is not None
    trace = _wait_trace(tp.trace_id, timeout=2.0)
    assert trace["name"] == "GET /3/Capabilities"   # renamed to the pattern
    [root] = [s for s in trace["spans"] if s["parent_id"] is None]
    assert root.get("attrs", {}).get("http_status") == 200


def test_polling_routes_are_ephemeral(server):
    """High-frequency GETs (job polls, /metrics scrapes) must not churn
    the completed-trace ring — they propagate a traceparent but their
    finished traces are discarded."""
    _, headers = _get(server, "/3/Ping")
    tp = parse_traceparent(headers["traceparent"])
    assert tp is not None                      # propagation still works
    assert _never_stored(tp.trace_id)          # ...but nothing was stored
    assert all(t["trace_id"] != tp.trace_id for t in TRACER.list_traces())


def test_incoming_traceparent_joins_callers_trace(server):
    caller = f"00-{'ab' * 16}-{'cd' * 8}-01"
    _, headers = _get(server, "/3/Ping", headers={"traceparent": caller})
    tp = parse_traceparent(headers["traceparent"])
    assert tp.trace_id == "ab" * 16           # joined, not re-minted
    assert tp.span_id != "cd" * 8             # our root span, fresh id
    trace = _wait_trace("ab" * 16, timeout=2.0)
    [root] = [s for s in trace["spans"] if s["kind"] == "server"]
    assert root["parent_id"] == "cd" * 8      # caller's span is our parent


def test_concurrent_requests_get_distinct_trace_ids(server):
    """Contextvar isolation under the server's thread-per-request model:
    parallel requests must never share a trace."""
    results: list = []
    lock = threading.Lock()

    def hit():
        _, headers = _get(server, "/3/Ping")
        with lock:
            results.append(parse_traceparent(headers["traceparent"]).trace_id)

    threads = [threading.Thread(target=hit) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 16 and len(set(results)) == 16


def test_health_polling_routes_are_ephemeral(server):
    """ISSUE 15 satellite: the ops-plane polling endpoints (/3/Health,
    /3/Incidents) are scraped like /metrics and /3/Jobs — a health
    scraper must not churn the completed-trace ring. Propagation still
    works: each reply carries a traceparent, and sending one records the
    call in the caller's trace as usual."""
    for path in ("/3/Health", "/3/Incidents"):
        _, headers = _get(server, path)
        tp = parse_traceparent(headers["traceparent"])
        assert tp is not None                  # propagation still works
        assert _never_stored(tp.trace_id)      # ...but nothing was stored
        assert all(t["trace_id"] != tp.trace_id
                   for t in TRACER.list_traces())
    # an explicit caller traceparent opts the call INTO recording
    caller = f"00-{'5e' * 16}-{'7a' * 8}-01"
    _, headers = _get(server, "/3/Health", headers={"traceparent": caller})
    assert parse_traceparent(headers["traceparent"]).trace_id == "5e" * 16
    trace = _wait_trace("5e" * 16, timeout=2.0)
    assert any(s["name"] == "GET /3/Health" for s in trace["spans"])


def test_unmatched_routes_are_ephemeral(server):
    """A scanner hitting unknown paths must not churn the trace ring."""
    import urllib.error
    req = urllib.request.Request(server.url + "/no/such/route")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    tp = parse_traceparent(ei.value.headers.get("traceparent"))
    assert tp is not None
    import time
    time.sleep(0.05)
    assert all(t["trace_id"] != tp.trace_id for t in TRACER.list_traces())


def test_traces_endpoints_and_client_accessors(server):
    client = H2OClient(server.url)
    client.request("GET", "/3/Capabilities")
    tid = client.last_trace_id
    assert tid
    summaries = client.traces()
    assert any(t["trace_id"] == tid for t in summaries)
    assert all("spans" not in t for t in summaries)   # list stays light
    full = client.trace(tid)
    assert full["trace_id"] == tid and full["critical_path"]
    assert full["tree"][0]["name"] == "GET /3/Capabilities"
    export = client.trace_export(tid)
    assert "traceEvents" in export
    with pytest.raises(RuntimeError, match="404"):
        client.trace("f" * 32)


def test_rest_to_job_to_partition_trace_is_connected(server, tmp_path,
                                                     monkeypatch):
    """Tentpole: one connected span tree spanning REST → Job (worker
    thread) → model fit → map_reduce dispatch → partition spans (partition
    sub-spans need H2O3TPU_TRACE_PARTITIONS=1 since the async-dispatch
    refactor — sampled-only by default)."""
    monkeypatch.setenv("H2O3TPU_TRACE_PARTITIONS", "1")
    client = H2OClient(server.url)
    rng = np.random.default_rng(7)
    x = rng.normal(size=200)
    csv = tmp_path / "t.csv"
    csv.write_text("x,y\n" + "\n".join(
        f"{v:.4f},{3 * v + rng.normal() * .1:.4f}" for v in x))
    frame_key = client.import_file(str(csv))
    out = client.request("POST", "/3/ModelBuilders/glm",
                         {"training_frame": frame_key, "response_column": "y"})
    tid = client.last_trace_id
    assert out["job"]["trace_id"] == tid      # pollers correlate via JobV3
    client._poll(out["job"]["key"]["name"])
    trace = _wait_trace(tid)
    kinds = {s["kind"] for s in trace["spans"]}
    assert {"server", "job", "model", "iteration", "dispatch",
            "partition"} <= kinds
    ids = {s["span_id"] for s in trace["spans"]}
    roots = [s for s in trace["spans"] if s["parent_id"] is None]
    assert len(roots) == 1                    # ONE connected tree
    assert all(s["parent_id"] in ids for s in trace["spans"]
               if s["parent_id"] is not None)
    assert client.trace(tid)["critical_path"]


def _wait_trace(trace_id, timeout=10.0):
    """The job span closes slightly after the job flips DONE; poll the
    tracer until the trace finalizes."""
    import time
    deadline = time.time() + timeout
    while True:
        try:
            trace = TRACER.get_trace(trace_id)
            if not trace.get("in_progress"):
                return trace
        except KeyError:
            pass
        if time.time() > deadline:
            raise AssertionError(f"trace {trace_id} never completed")
        time.sleep(0.05)


@pytest.mark.slow
def test_automl_trace_acceptance(server, tmp_path, monkeypatch):
    """Acceptance: a completed REST AutoML run yields ONE connected span
    tree spanning REST → leaderboard jobs → per-model map_reduce partition
    spans, with a non-empty critical path and at least one straggler
    attribution attr; its Perfetto export is valid Chrome trace JSON."""
    monkeypatch.setenv("H2O3TPU_TRACE_PARTITIONS", "1")
    client = H2OClient(server.url)
    rng = np.random.default_rng(11)
    n = 150
    X = rng.normal(size=(n, 3))
    y = np.where(X[:, 0] + 0.5 * X[:, 1] > 0, "a", "b")
    csv = tmp_path / "aml.csv"
    csv.write_text("x0,x1,x2,y\n" + "\n".join(
        f"{r[0]:.4f},{r[1]:.4f},{r[2]:.4f},{lab}"
        for r, lab in zip(X, y)))
    frame_key = client.import_file(str(csv))
    out = client.request("POST", "/99/AutoMLBuilder",
                         {"training_frame": frame_key, "response_column": "y",
                          "max_models": 2, "nfolds": 0,
                          "project_name": "trace_accept"})
    tid = client.last_trace_id
    client._poll(out["job"]["key"]["name"], poll_secs=0.3)
    trace = _wait_trace(tid, timeout=30.0)

    kinds = {s["kind"] for s in trace["spans"]}
    assert {"server", "job", "orchestration", "build", "model",
            "dispatch", "partition"} <= kinds
    ids = {s["span_id"] for s in trace["spans"]}
    roots = [s for s in trace["spans"] if s["parent_id"] is None]
    assert len(roots) == 1, "AutoML trace must be ONE connected tree"
    assert all(s["parent_id"] in ids for s in trace["spans"]
               if s["parent_id"] is not None)
    full = client.trace(tid)
    assert full["critical_path"], "critical path must be non-empty"
    assert any("straggler" in s["attrs"] for s in trace["spans"]), \
        "at least one straggler-attribution attr"

    export = client.trace_export(tid)
    assert json.loads(json.dumps(export))     # valid JSON round trip
    xs = [e for e in export["traceEvents"] if e["ph"] == "X"]
    assert xs and all({"ph", "ts", "dur", "pid", "tid", "name"} <= set(e)
                      for e in xs)


# -- one clock: spans in ANY profiler session; the boost program by name ------


def _tiny_gbm_frame():
    from h2o3_tpu.frame.frame import Frame
    rng = np.random.default_rng(7)
    X = rng.normal(size=(2000, 4)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(4)}
    cols["y"] = (X[:, 0] + X[:, 1] * X[:, 2]).astype(np.float32)
    return Frame.from_arrays(cols)


def _tiny_gbm(frame):
    from h2o3_tpu.models.gbm import GBM
    return GBM(ntrees=3, max_depth=3, nbins=16, seed=1).train(
        y="y", training_frame=frame)


def test_span_hook_is_gone_and_spans_annotate_themselves():
    """The profiler hook global went: every span and every span-less
    ``timed_event`` opens its own ``TraceAnnotation`` (``/3/Profiler/
    capture`` still carries ``profiler:exercise``: tests/test_compute.py)."""
    from h2o3_tpu.utils import profiling, timeline
    assert not hasattr(tracing, "SPAN_HOOK")
    assert not hasattr(profiling, "_annotation_hook")
    for gone in ("start_profiler", "stop_profiler", "device_memory_profile"):
        assert not hasattr(timeline, gone)
    ann = tracing.annotation("test:annotation")
    assert ann is not None
    ann.__exit__(None, None, None)


def test_timed_event_without_profiler_or_trace_still_records():
    """No profiler session, no root trace (``builder.train()`` called
    directly): the ring and the histogram get the event, nothing raises,
    and no span is invented."""
    from h2o3_tpu.utils.timeline import TIMELINE, timed_event

    class Hist:
        seen = []

        def observe(self, seconds):
            self.seen.append(seconds)

    assert TRACER.current() is None
    before = len(TRACER.list_traces())
    with timed_event("phase", "test:no_session", observe=Hist()) as ev:
        assert ev._span is None and ev._ann is not None
    assert len(Hist.seen) == 1 and Hist.seen[0] >= 0.0
    mine = [e for e in TIMELINE.snapshot() if e["what"] == "test:no_session"]
    assert len(mine) == 1 and mine[0]["kind"] == "phase"
    assert len(TRACER.list_traces()) == before
    # under a root trace the span annotates, the wrapper does not: once
    # (the newest trace, not a longer list: the ring holds 128 traces and a
    # worker that has served that many requests keeps its length)
    with TRACER.span("test:root", kind="server", root=True):
        with timed_event("phase", "test:under_root") as ev:
            assert ev._span is not None and ev._ann is None
    newest = TRACER.list_traces()[0]
    assert newest["name"] == "test:root" and newest["nspans"] == 2


def test_builder_phases_land_in_a_profiler_session_the_test_opened(tmp_path):
    """Under ``jax.profiler.start_trace`` opened HERE (not by ``PROFILER``),
    with no root trace, a build leaves its phases on the host plane: each
    once a build, ``prepare.bin`` opening before ``chunk``. The build runs
    on a thread of its own so that this test has a time limit of its own."""
    import glob
    import os

    import jax
    frame = _tiny_gbm_frame()
    _tiny_gbm(frame)                        # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    failure = []

    def traced_builds():
        try:
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                _tiny_gbm(frame)
                _tiny_gbm(frame)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:   # noqa: BLE001 — handed to the test thread
            failure.append(e)

    worker = threading.Thread(target=traced_builds, daemon=True)
    worker.start()
    worker.join(timeout=120.0)
    assert not worker.is_alive(), "two toy builds under the profiler: 120 s"
    assert not failure, failure
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert found
    data = jax.profiler.ProfileData.from_file(found[-1])
    starts: dict[str, list[float]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("gbm:"):
                    starts.setdefault(e.name, []).append(e.start_ns)
    assert {k: len(v) for k, v in starts.items()} == {
        "gbm:train": 2, "gbm:fit": 2, "gbm:prepare.edges": 2,
        "gbm:prepare.bin": 2, "gbm:chunk": 2, "gbm:metrics": 2}
    for train, fit, edges, bins, chunk, metrics in zip(*(
            sorted(starts[k]) for k in (
                "gbm:train", "gbm:fit", "gbm:prepare.edges",
                "gbm:prepare.bin", "gbm:chunk", "gbm:metrics"))):
        assert train < fit < edges < bins < chunk < metrics


def test_boost_program_parts_are_named_in_the_compiled_module():
    """``jax.named_scope`` around the round's parts and the tree's levels:
    the compiled module's ``op_name``s carry them (the ``scatter`` path,
    which the CPU takes), so a profile reads a level's routing by name."""
    import re

    from h2o3_tpu.models import gbm
    _tiny_gbm(_tiny_gbm_frame())
    text = gbm._boost_scan_jit.executables()[-1].as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    paths = {tuple(n.split("/")) for n in op_names}

    def has(*scope):
        return any(scope == p[i:i + len(scope)]
                   for p in paths for i in range(len(p)))

    for d in range(3):
        for part in ("hist", "split", "route"):
            assert has(f"level{d}", part), (d, part)
    assert not has("level3")                # max_depth levels, then leaves
    for scope in ("leaves", "grad", "update"):
        assert has(scope), scope
    # the histogram build itself (segment_sum's scatter-add) sits under hist
    assert any("hist" in p and p[-1] == "scatter-add" for p in paths)
    # routing is compare-and-select reduced over nodes and features (PR 27)
    assert any("route" in p and p[-1] == "reduce_sum" for p in paths)
    assert not any("route" in p and p[-1] == "gather" for p in paths)
