"""Compute observatory tests (ISSUE 10): XLA cost accounting, recompile
attribution with signature diffs, utilization-or-null on unknown backends,
the ``/3/Compute`` + ``/3/Profiler`` REST surface, per-site compile-cache
attribution, and the overhead contract (no device sync on the unsampled
dispatch path; traced-vs-off GLM wall time inside the tracer's envelope).
"""

import gzip
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu import Frame
from h2o3_tpu.api import H2OServer
from h2o3_tpu.api.client import H2OClient
from h2o3_tpu.models import GBM, GLM
from h2o3_tpu.utils import costs as costs_mod
from h2o3_tpu.utils.costs import (COSTS, accounted_jit, backend_peak,
                                  signature_diff)

# -- signatures and diffs -----------------------------------------------------


def _sig(*shapes, statics=None):
    return {"args": [{"shape": list(s), "dtype": "float32"} for s in shapes],
            "statics": statics or {}}


def test_signature_diff_names_changed_dimension():
    d = signature_diff(_sig((2048, 12)), _sig((3008, 12)))
    assert d == ["arg0.shape[0]: 2048 -> 3008"]


def test_signature_diff_names_dtype_rank_statics_and_arity():
    old = _sig((8, 4), statics={"k": "5"})
    new = {"args": [{"shape": [8, 4, 1], "dtype": "bfloat16"}],
           "statics": {"k": "9"}}
    d = signature_diff(old, new)
    assert "arg0.rank: 2 -> 3" in d
    assert "arg0.dtype: float32 -> bfloat16" in d
    assert "static k: 5 -> 9" in d
    d2 = signature_diff(_sig((4,)), _sig((4,), (4,)))
    assert "arg count: 1 -> 2" in d2


def test_backend_peak_table_and_unknown_kinds():
    assert backend_peak("TPU v5 lite chip")["name"] == "TPU v5e"
    assert backend_peak("TPU v5e")["flops_per_sec"] == pytest.approx(197e12)
    assert backend_peak("TPU v4")["name"] == "TPU v4"
    # unknown kinds (this CPU container, future chips): None, never 0,
    # never an exception
    assert backend_peak("cpu") is None
    assert backend_peak("Radical New Accelerator 9000") is None
    assert backend_peak() is None          # default backend here is CPU


# -- CostMeter recording ------------------------------------------------------


def test_recompile_event_only_on_new_signature():
    COSTS.clear()
    COSTS.record_compile("t:site", _sig((8, 2)), 0.5, 100.0, 400.0)
    # same signature again (fresh-lambda churn): counted, NOT a recompile
    COSTS.record_compile("t:site", _sig((8, 2)), 0.2, 100.0, 400.0)
    [site] = [s for s in COSTS.snapshot()["sites"] if s["site"] == "t:site"]
    assert site["compiles"] == 2
    assert len(site["signatures"]) == 1
    assert site["recompile_events"] == []
    assert site["compile_seconds"] == pytest.approx(0.7)
    # a genuinely new signature IS a recompile event, with the diff
    COSTS.record_compile("t:site", _sig((16, 2)), 0.1, 150.0, 500.0)
    [site] = [s for s in COSTS.snapshot()["sites"] if s["site"] == "t:site"]
    [ev] = site["recompile_events"]
    assert ev["diff"] == ["arg0.shape[0]: 8 -> 16"]
    assert COSTS.recompile_count() == 1


def test_observe_on_unknown_backend_reports_null_utilization():
    COSTS.clear()
    COSTS.record_compile("t:loop", _sig((8,)), 0.1, 1e6, 2e6, loop="toy")
    COSTS.observe("t:loop", 0.01)
    loops = COSTS.snapshot()["loops"]
    st = loops["toy"]
    assert st["achieved_flops_per_sec"] == pytest.approx(1e8)
    assert st["achieved_bytes_per_sec"] == pytest.approx(2e8)
    assert st["arithmetic_intensity"] == pytest.approx(0.5)
    # CPU is off the peak table: utilization is null — not 0, no exception
    assert st["utilization"] is None
    assert st["roofline"] is None
    assert COSTS.snapshot()["peak"] is None


# -- the accounted jit wrapper ------------------------------------------------


def test_accounted_jit_records_cost_and_recompile_diff():
    COSTS.clear()

    @accounted_jit("t:matmul", loop="toy_loop")
    def mm(a, b):
        return a @ b

    x = jnp.ones((32, 32), jnp.float32)
    np.testing.assert_allclose(mm(x, x), np.full((32, 32), 32.0))
    mm(x, x)                               # same signature: cached
    [site] = [s for s in COSTS.snapshot()["sites"] if s["site"] == "t:matmul"]
    assert site["compiles"] == 1           # one executable, reused
    assert site["loop"] == "toy_loop"
    assert site["flops"] and site["flops"] > 0
    assert site["bytes"] and site["bytes"] > 0
    assert site["compile_seconds"] > 0
    y = jnp.ones((64, 32), jnp.float32)
    mm(y, x)                               # shape change: recompile event
    [site] = [s for s in COSTS.snapshot()["sites"] if s["site"] == "t:matmul"]
    [ev] = site["recompile_events"]
    assert "arg0.shape[0]: 32 -> 64" in ev["diff"]


def test_accounted_jit_static_change_named_in_diff():
    COSTS.clear()

    @accounted_jit("t:statics", static_argnames=("k",))
    def scale(x, k):
        return x * k

    x = jnp.ones(8, jnp.float32)
    scale(x, k=2)
    scale(x, k=3)
    [site] = [s for s in COSTS.snapshot()["sites"] if s["site"] == "t:statics"]
    [ev] = site["recompile_events"]
    assert any(d.startswith("static k:") for d in ev["diff"])


def test_accounted_jit_nested_in_trace_falls_through():
    COSTS.clear()
    inner = accounted_jit("t:inner", lambda x: x * 2.0)

    @jax.jit
    def outer(x):
        return inner(x) + 1.0              # leaves are tracers here

    np.testing.assert_allclose(outer(jnp.ones(4)), np.full(4, 3.0))
    # the OUTER program owns the compile: the wrapper recorded nothing
    assert all(s["site"] != "t:inner" for s in COSTS.snapshot()["sites"])


def test_costs_off_bypasses_recording(monkeypatch):
    COSTS.clear()
    monkeypatch.setenv("H2O3TPU_COSTS_OFF", "1")
    w = accounted_jit("t:off", lambda x: x + 1.0)
    np.testing.assert_allclose(w(jnp.ones(4)), np.full(4, 2.0))
    assert COSTS.snapshot()["sites"] == []


def test_sampled_probe_attributes_executed_signature(monkeypatch):
    """A site holding several live signatures (full GBM chunk + remainder
    chunk) must rate each sampled execution against the cost of the
    signature that RAN, not the site's most recent compile."""
    COSTS.clear()
    monkeypatch.setenv("H2O3TPU_COSTS_SAMPLE", "1")   # sample every call
    w = accounted_jit("t:multi", lambda a: a @ a)
    small = jnp.ones((8, 8), jnp.float32)
    big = jnp.ones((64, 64), jnp.float32)
    w(small)
    w(big)                                 # big is now the LATEST compile
    [site] = [s for s in COSTS.snapshot()["sites"] if s["site"] == "t:multi"]
    by_shape = {tuple(s["signature"]["args"][0]["shape"]): s["flops"]
                for s in site["signatures"]}
    assert by_shape[(8, 8)] < by_shape[(64, 64)]
    seen = []
    orig = COSTS.observe
    monkeypatch.setattr(
        COSTS, "observe",
        lambda site, secs, flops=None, nbytes=None: seen.append(flops))
    w(small)                               # sampled: must carry SMALL's cost
    assert seen == [by_shape[(8, 8)]]
    monkeypatch.setattr(COSTS, "observe", orig)


def test_unsampled_dispatch_path_never_syncs(monkeypatch):
    """Cost accounting must not add a device sync on the unsampled path:
    the only sync the wrapper owns is the sampled achieved-FLOPs probe, and
    with the sample period pushed out of reach, zero ``block_until_ready``
    calls may happen across repeated dispatches."""
    COSTS.clear()
    w = accounted_jit("t:nosync", lambda x: x * 3.0)
    x = jnp.ones(16, jnp.float32)
    w(x)                                   # call 0: compiles + sampled probe
    monkeypatch.setenv("H2O3TPU_COSTS_SAMPLE", "1000000")
    real = jax.block_until_ready
    calls = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: (calls.append(1), real(v))[1])
    for _ in range(10):
        w(x)
    assert calls == []


def test_observe_folds_flops_into_active_mesh_slice():
    """Under an active slice lease the sampled FLOPs credit the slice's
    row in /3/Cloud's mesh_slices (achieved_flops) — the observatory's
    'where did the arithmetic run' half of the PR 9 utilization view."""
    from h2o3_tpu.orchestration import scheduler
    COSTS.clear()
    scheduler.SLICE_STATS.reset()
    COSTS.record_compile("t:sliced", _sig((8,)), 0.1, 5e5, 1e6, loop="toy")
    token = scheduler._ACTIVE_SLICE.set("full")
    try:
        COSTS.observe("t:sliced", 0.01)
    finally:
        scheduler._ACTIVE_SLICE.reset(token)
    try:
        [row] = [r for r in scheduler.SLICE_STATS.snapshot()["slices"]
                 if r["slice"] == "full"]
        assert row["achieved_flops"] == pytest.approx(5e5)
    finally:
        scheduler.SLICE_STATS.reset()


# -- per-site compile-cache attribution ---------------------------------------


def test_compile_cache_events_credit_active_site():
    from h2o3_tpu.utils import compile_cache
    base = compile_cache.stats()
    with COSTS.scope("fit:test_algo"):
        compile_cache._on_event("/jax/compilation_cache/cache_misses")
        compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    st = compile_cache.stats()
    per = st["by_site"]["fit:test_algo"]
    base_per = (base["by_site"].get("fit:test_algo")
                or {"hits": 0, "misses": 0})
    assert per["misses"] - base_per["misses"] == 1
    assert per["hits"] - base_per["hits"] == 1
    unattr = st["by_site"]["(unattributed)"]["hits"] \
        - (base["by_site"].get("(unattributed)") or {"hits": 0})["hits"]
    assert unattr == 1


def test_model_fit_runs_under_site_scope(rng):
    """ModelBuilder.train wraps _fit in COSTS.scope(f"fit:{algo}") so cache
    events during a build credit the algo; verify the scope is live inside
    the fit by observing it from a map_reduce-adjacent hook."""
    seen = []

    class Probe(GLM):
        def _fit(self, job, frame, x, y, w):
            seen.append(COSTS.active_site())
            return super()._fit(job, frame, x, y, w)

    X = rng.normal(size=(256, 3))
    cols = {f"x{i}": X[:, i] for i in range(3)}
    cols["y"] = X @ np.ones(3)
    Probe(family="gaussian").train(y="y", training_frame=Frame.from_arrays(cols))
    assert seen == ["fit:glm"]


# -- REST surface: /3/Compute acceptance --------------------------------------


@pytest.fixture(scope="module")
def server():
    s = H2OServer(port=0).start()
    yield s
    s.stop()


def _get(server, path):
    with urllib.request.urlopen(server.url + path) as r:
        return json.loads(r.read())


def _train_frame(nrows, ncols=4, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(nrows, ncols))
    cols = {f"x{i}": X[:, i] for i in range(ncols)}
    cols["y"] = np.where(X[:, 0] + 0.1 * rng.normal(size=nrows) > 0,
                         "yes", "no")
    return Frame.from_arrays(cols)


def test_compute_endpoint_acceptance(server):
    """The ISSUE 10 acceptance flow: a fresh GBM + GLM and a warmed scoring
    signature each show >= 1 executable with nonzero cost_analysis FLOPs /
    bytes and compile seconds; a deliberately shape-changed second GLM
    build records EXACTLY ONE recompile event whose diff names the changed
    dimension; on this CPU-only run utilization is null — not 0, and not
    an exception."""
    COSTS.clear()
    fr = _train_frame(600)
    gbm = GBM(ntrees=3, max_depth=3, model_id="cmp_gbm").train(
        y="y", training_frame=fr)
    GLM(family="binomial", lambda_=1e-4, model_id="cmp_glm").train(
        y="y", training_frame=fr)
    client = H2OClient(server.url)
    payload = [{f"x{i}": 0.5 for i in range(4)}] * 4
    client.score(gbm.key, payload)         # compile the scoring signature
    client.score(gbm.key, payload)         # ... and hit it warm

    snap = client.compute()
    sites = {s["site"]: s for s in snap["sites"]}
    for needed in ("gbm:boost_scan", "glm:irls_megastep", "score:gbm"):
        assert needed in sites, sorted(sites)
        s = sites[needed]
        assert s["compiles"] >= 1
        assert s["flops"] and s["flops"] > 0, needed
        assert s["bytes"] and s["bytes"] > 0, needed
        assert s["compile_seconds"] > 0
    # CPU-only: no peak row, every published loop utilization is null
    assert snap["peak"] is None
    assert snap["device_kind"] == "cpu"
    assert snap["loops"], "sampled probes should have published loops"
    for st in snap["loops"].values():
        assert st["utilization"] is None
        assert st["achieved_flops_per_sec"] > 0

    # deliberately shape-changed second build: wider X changes the IRLS
    # signature's feature dimension. (The first build may legitimately
    # record a device-set recompile — beta starts single-device before the
    # loop shards it — so assert on SHAPE-diff events specifically.)
    irls = sites["glm:irls_megastep"]
    assert not any(".shape[" in d for e in irls["recompile_events"]
                   for d in e["diff"]), irls["recompile_events"]
    GLM(family="binomial", lambda_=1e-4, model_id="cmp_glm2").train(
        y="y", training_frame=_train_frame(600, ncols=6))
    snap2 = _get(server, "/3/Compute")
    [irls] = [s for s in snap2["sites"] if s["site"] == "glm:irls_megastep"]
    # exactly ONE recompile event names the changed dimension — and it
    # names the RIGHT one (the feature dim we widened, 4 -> 6)
    shape_evs = [e for e in irls["recompile_events"]
                 if any(".shape[" in d for d in e["diff"])]
    assert len(shape_evs) == 1, irls["recompile_events"]
    assert any(d.startswith("arg0.shape[1]: 4 -> 6")
               for d in shape_evs[0]["diff"]), shape_evs[0]["diff"]
    assert snap2["recompile_events"] >= 1


def test_compute_schema_meta(server):
    snap = _get(server, "/3/Compute")
    assert snap["__meta"]["schema_type"] == "ComputeV3"
    assert {"backend", "sites", "loops", "signature_count"} <= set(snap)


# -- REST surface: profiler capture lifecycle ---------------------------------


def _post(server, path):
    req = urllib.request.Request(server.url + path, data=b"", method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_profiler_capture_roundtrip(server):
    rec = _post(server, "/3/Profiler/capture?duration_ms=120")
    assert rec["capture_id"].startswith("cap_")
    assert rec["artifact"] and rec["bytes"] > 0
    caps = _get(server, "/3/Profiler/captures")["captures"]
    assert any(c["capture_id"] == rec["capture_id"] for c in caps)
    # the artifact is a Perfetto-loadable gzip Chrome trace whose events
    # carry span-derived annotations (TraceAnnotation long_name)
    url = f"{server.url}/3/Profiler/captures/{rec['capture_id']}/download"
    with urllib.request.urlopen(url) as r:
        assert r.headers["Content-Type"] == "application/gzip"
        body = r.read()
    doc = json.loads(gzip.decompress(body))
    events = doc["traceEvents"]
    assert events
    assert any(e.get("args", {}).get("long_name") == "profiler:exercise"
               for e in events), "span-derived annotation missing"


def test_profiler_concurrent_capture_409(server):
    from h2o3_tpu.utils.profiling import PROFILER, CaptureBusy
    assert PROFILER._busy.acquire(blocking=False)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server, "/3/Profiler/capture?duration_ms=50")
        assert ei.value.code == 409
        err = json.loads(ei.value.read())
        assert err["http_status"] == 409
        assert "in progress" in err["msg"]
        with pytest.raises(CaptureBusy):
            PROFILER.capture(duration_ms=50)
    finally:
        PROFILER._busy.release()


def test_profiler_unknown_capture_download_404(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server, "/3/Profiler/captures/cap_nope/download")
    assert ei.value.code in (400, 404)


# -- overhead envelope --------------------------------------------------------


@pytest.mark.slow
def test_costs_overhead_within_tracer_envelope(rng, monkeypatch):
    """Accounted GLM build vs ``H2O3TPU_COSTS_OFF=1``, min-of-3 each:
    the observatory is held to the same <2% always-on envelope as the
    tracer. Sub-second CPU builds put 2% under scheduler noise, so the
    assertion carries a small absolute floor; the pure ratio at real
    scale is a timing on the chip, not measured."""
    import time

    X = rng.normal(size=(60_000, 8)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = (X[:, 0] - 0.5 * X[:, 1]
                 + 0.1 * rng.normal(size=60_000)).astype(np.float32)
    fr = Frame.from_arrays(cols)

    def build():
        GLM(family="gaussian", lambda_=1e-4, max_iterations=12).train(
            y="y", training_frame=fr)

    def timed():
        t0 = time.perf_counter()
        build()
        return time.perf_counter() - t0

    build()                                # warm-up: compiles out of timing
    jax.effects_barrier()
    t_on = min(timed() for _ in range(3))
    monkeypatch.setenv("H2O3TPU_COSTS_OFF", "1")
    build()                                # warm the plain-jit path too
    jax.effects_barrier()
    t_off = min(timed() for _ in range(3))
    assert t_on <= t_off * 1.02 + 0.05, (t_on, t_off)
