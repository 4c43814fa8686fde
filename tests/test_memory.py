"""Memory observability tests: MemoryMeter per-key accounting, host/device
sampling + watermarks, leak-detector semantics, per-span attribution, the
`/3/Memory` endpoint (reconciliation against frame chunk nbytes), real
numbers in `/3/Cloud`, and the client accessors (docs/OBSERVABILITY.md
"Memory")."""

import json
import re
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.api import H2OServer
from h2o3_tpu.api.client import H2OClient
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.utils.memory import (MEMORY, LeakDetector, array_tree_bytes,
                                   device_stats, host_stats, value_kind_bytes)
from h2o3_tpu.utils.registry import DKV


def _frame(nrows=2000, ncols=3, seed=0):
    rng = np.random.default_rng(seed)
    return Frame.from_arrays(
        {f"x{i}": rng.normal(size=nrows).astype(np.float32)
         for i in range(ncols)})


# -- byte measurement --------------------------------------------------------


def test_vec_and_frame_nbytes():
    fr = _frame(nrows=1000, ncols=2)
    for v in fr.vecs:
        # padded device chunk: plen rows of float32
        assert v.nbytes == v.plen * 4
    assert fr.nbytes == sum(v.nbytes for v in fr.vecs)


def test_frame_nbytes_counts_host_payloads():
    fr = Frame.from_arrays({"s": np.array(["a", "bb", "ccc"] * 10,
                                          dtype=object)})
    assert fr.nbytes > 0                      # host object array, no device


def test_value_kind_bytes_dispatch():
    fr = _frame()
    kind, b = value_kind_bytes(fr)
    assert kind == "frame" and b == fr.nbytes
    from h2o3_tpu.frame.parse import RawFile
    kind, b = value_kind_bytes(RawFile(b"x" * 100, name="f.csv"))
    assert kind == "raw" and b == 100
    from h2o3_tpu.models.job import Job
    kind, b = value_kind_bytes(Job("j"))
    assert kind == "job" and b == 0


def test_array_tree_bytes_walks_models():
    from h2o3_tpu.frame.vec import Vec
    fr = _frame(nrows=500, ncols=4, seed=1)
    y = (np.asarray(fr.vec("x0").to_numpy()) > 0)
    fr.add("y", Vec.from_numpy(np.where(y, "a", "b")))
    from h2o3_tpu.models.glm import GLM
    m = GLM(family="binomial", max_iterations=3).train(y="y",
                                                       training_frame=fr)
    kind, b = value_kind_bytes(m)
    assert kind == "model" and b > 0
    assert m.output["artifact_bytes"] == pytest.approx(b, rel=0.2)


# -- registration at put/remove ----------------------------------------------


def test_dkv_registration_keeps_totals_current():
    fr = _frame()
    DKV.put("memtest_frame", fr)
    total, by_kind, n = MEMORY.dkv_totals()
    assert by_kind.get("frame", 0) >= fr.nbytes
    assert any(r["key"] == "memtest_frame" and r["bytes"] == fr.nbytes
               for r in MEMORY.top_keys(50))
    DKV.remove("memtest_frame")
    assert all(r["key"] != "memtest_frame" for r in MEMORY.top_keys(50))


def test_refresh_catches_inplace_mutation():
    fr = _frame(nrows=1000, ncols=1)
    DKV.put("mut_frame", fr)
    b0 = next(r["bytes"] for r in MEMORY.top_keys(50)
              if r["key"] == "mut_frame")
    from h2o3_tpu.frame.vec import Vec
    fr.add("extra", Vec.from_numpy(np.zeros(1000, np.float32)))
    MEMORY.refresh()
    b1 = next(r["bytes"] for r in MEMORY.top_keys(50)
              if r["key"] == "mut_frame")
    assert b1 > b0


# -- host/device sampling + watermarks ---------------------------------------


def test_host_stats_reads_proc():
    h = host_stats()
    assert h["rss_bytes"] > 0
    assert h["rss_peak_bytes"] >= h["rss_bytes"] // 2
    assert h["total_bytes"] > h["available_bytes"] > 0


def test_device_stats_fallback_accounts_live_arrays():
    fr = _frame(nrows=4000, ncols=2, seed=2)
    d = device_stats()
    assert d["source"] in ("memory_stats", "live_arrays")
    assert d["bytes_in_use"] >= fr.nbytes
    assert d["devices"]


def test_watermarks_are_monotonic():
    MEMORY.sample()
    w0 = MEMORY.watermarks
    _fr = _frame(nrows=50_000, ncols=2, seed=3)
    MEMORY.sample()
    w1 = MEMORY.watermarks
    assert w1["device_peak_bytes"] >= w0["device_peak_bytes"]
    assert w1["host_rss_peak_bytes"] >= w0["host_rss_peak_bytes"]
    del _fr


def test_sample_leaves_a_nonzero_host_watermark():
    """A zero host watermark after a sample means the accounting broke
    (``/proc`` unread, or the sample not folded into the peak)."""
    rss, _dev = MEMORY.sample()
    assert MEMORY.watermarks["host_rss_peak_bytes"] >= rss > 0


# -- leak detector ------------------------------------------------------------


def test_leak_detector_flags_idle_growth_and_recovery():
    det = LeakDetector(sweeps=3, min_bytes=100)
    keyed = {"big": ("frame", 1000), "small": ("frame", 10)}
    det.observe(dict(keyed), {"big", "small"})
    for _ in range(3):
        det.observe(dict(keyed), set())       # nobody touches anything
    flagged = {f["key"]: f for f in det.report()}
    assert "big" in flagged and flagged["big"]["reasons"] == ["idle"]
    assert "small" not in flagged             # under the byte floor
    # an access resets the idle streak
    det.observe(dict(keyed), {"big"})
    assert not det.report()


def test_leak_detector_flags_monotone_growth():
    det = LeakDetector(sweeps=2, min_bytes=100)
    det.observe({"grow": ("frame", 100)}, {"grow"})
    det.observe({"grow": ("frame", 200)}, {"grow"})
    det.observe({"grow": ("frame", 300)}, {"grow"})
    [f] = det.report()
    assert f["key"] == "grow" and "growing" in f["reasons"]
    # removal drops the state entirely
    det.observe({}, set())
    assert not det.report()


def test_meter_leak_sweep_end_to_end():
    fr = _frame(nrows=200_000, ncols=2, seed=4)     # > 1 MiB floor
    DKV.put("leaky_frame", fr)
    sweeps = MEMORY.detector.sweeps
    for _ in range(sweeps + 1):
        MEMORY.leak_sweep()
    rep = MEMORY.leak_report()
    assert any(f["key"] == "leaky_frame" and "idle" in f["reasons"]
               for f in rep["flagged"])
    # a DKV get between sweeps resets the idle streak
    DKV.get("leaky_frame")
    MEMORY.leak_sweep()
    assert not any(f["key"] == "leaky_frame"
                   for f in MEMORY.leak_report()["flagged"])


def test_growth_detection_through_refresh_and_sweeps():
    """The leak detector's signal end-to-end: a key growing in place across
    interleaved refresh+sweep generations accumulates a growth streak and
    flags as 'growing'."""
    from h2o3_tpu.frame.vec import Vec
    fr = _frame(nrows=300_000, ncols=1, seed=11)     # above the byte floor
    DKV.put("grower", fr)
    MEMORY.leak_sweep()
    for i in range(MEMORY.detector.sweeps):
        fr.add(f"c{i}", Vec.from_numpy(np.zeros(300_000, np.float32)))
        MEMORY.refresh()
        MEMORY.leak_sweep()
    growing = [f for f in MEMORY.leak_report()["flagged"]
               if "growing" in f["reasons"]]
    assert any(f["key"] == "grower" for f in growing)
    # one static sweep resets the growth streak (read growth flags BEFORE
    # any back-to-back idle sweeps)
    MEMORY.leak_sweep()
    assert not any("growing" in f["reasons"]
                   for f in MEMORY.leak_report()["flagged"])


# -- per-span attribution -----------------------------------------------------


def test_glm_build_trace_root_carries_peak_device_bytes():
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.utils import tracing as tr
    rng = np.random.default_rng(7)
    cols = {f"x{i}": rng.normal(size=800).astype(np.float32)
            for i in range(4)}
    cols["y"] = np.where(rng.random(800) > 0.5, "a", "b")
    fr = Frame.from_arrays(cols)
    with tr.TRACER.span("memtest:root", root=True) as root:
        GLM(family="binomial", max_iterations=4).train(y="y",
                                                       training_frame=fr)
    trace = tr.TRACER.get_trace(root.trace_id)
    root_span = next(s for s in trace["spans"] if s["name"] == "memtest:root")
    assert root_span["attrs"].get("peak_device_bytes", 0) > 0
    fit = next(s for s in trace["spans"] if s["name"] == "glm:fit")
    assert fit["attrs"]["peak_device_bytes"] > 0
    assert "device_bytes_delta" in fit["attrs"]
    assert fit["attrs"]["host_rss_bytes"] > 0
    # the root's rollup is the max over its builds' peaks
    assert root_span["attrs"]["peak_device_bytes"] >= \
        fit["attrs"]["peak_device_bytes"] * 0.99


# -- REST surface -------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    s = H2OServer(port=0).start()
    yield s
    s.stop()


def _get(server, path):
    with urllib.request.urlopen(server.url + path) as r:
        return json.loads(r.read())


def test_memory_endpoint_reconciles_with_frame_nbytes(server, tmp_path):
    """Acceptance: /3/Memory's top-N byte totals reconcile (±1%) against
    the sum of frame chunk nbytes for a parsed frame."""
    rng = np.random.default_rng(5)
    csv = tmp_path / "mem.csv"
    csv.write_text("a,b\n" + "\n".join(
        f"{v:.5f},{v * 2:.5f}" for v in rng.normal(size=3000)))
    client = H2OClient(server.url)
    key = client.import_file(str(csv))
    fr = DKV[key]
    expect = sum(v.nbytes for v in fr.vecs)
    mem = _get(server, "/3/Memory?top=50")
    assert mem["__meta"]["schema_type"] == "MemoryV3"
    row = next(r for r in mem["top_keys"] if r["key"] == key)
    assert row["kind"] == "frame"
    assert row["bytes"] == pytest.approx(expect, rel=0.01)
    assert mem["dkv"]["by_kind"]["frame"] >= expect
    assert mem["dkv"]["total_bytes"] >= expect
    assert mem["host"]["rss_bytes"] > 0
    assert mem["device"]["bytes_in_use"] >= expect
    assert mem["watermarks"]["host_rss_peak_bytes"] > 0
    assert set(mem["leaks"]) >= {"sweeps", "flagged", "min_bytes"}


def test_cloud_serves_real_memory_numbers(server):
    fr = _frame(nrows=5000, ncols=2, seed=6)
    DKV.put("cloud_mem_frame", fr)
    cloud = _get(server, "/3/Cloud")
    node = cloud["nodes"][0]
    assert node["max_mem"] > node["free_mem"] > 0
    assert node["mem_value_size"] >= fr.nbytes
    assert node["pojo_mem"] > 0               # RSS beyond DKV values
    assert node["num_keys"] >= 1
    assert node["pid"] > 0


def test_memory_gauges_in_openmetrics(server):
    fr = _frame(nrows=2000, ncols=2, seed=8)
    DKV.put("gauge_frame", fr)
    _get(server, "/3/Memory")                  # samples + refreshes gauges
    with urllib.request.urlopen(server.url + "/metrics") as r:
        text = r.read().decode()
    m = re.search(r'h2o3_dkv_bytes\{kind="frame"\} (\d+)', text)
    assert m and int(m.group(1)) >= fr.nbytes
    assert re.search(r"^h2o3_host_rss_bytes [1-9]", text, re.M)
    assert re.search(r"^h2o3_device_bytes_in_use [1-9]", text, re.M)
    assert re.search(r"^h2o3_host_rss_peak_bytes [1-9]", text, re.M)


def test_dkv_clear_zeroes_exported_gauges(server):
    """A DKV.clear must not leave h2o3_dkv_bytes gauges reporting the last
    resident bytes forever (dashboards alert on these)."""
    fr = _frame(nrows=2000, ncols=2, seed=10)
    DKV.put("clear_gauge_frame", fr)
    DKV.clear()
    with urllib.request.urlopen(server.url + "/metrics") as r:
        text = r.read().decode()
    m = re.search(r'h2o3_dkv_bytes\{kind="frame"\} (\d+)', text)
    assert m and int(m.group(1)) == 0


def test_memory_endpoint_rejects_bad_top(server):
    import urllib.error
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server, "/3/Memory?top=abc")
    assert ei.value.code == 404               # client error, not a 500


def test_client_memory_jstack_profiler_accessors(server):
    client = H2OClient(server.url)
    mem = client.memory(top=3)
    assert len(mem["top_keys"]) <= 3
    assert any(t["name"] == "MainThread" for t in client.jstack())
    prof = client.profiler(depth=2)
    assert prof["stacktraces"] and prof["counts"]


def test_model_key_reports_artifact_bytes(server):
    rng = np.random.default_rng(9)
    cols = {f"x{i}": rng.normal(size=400).astype(np.float32)
            for i in range(3)}
    cols["y"] = np.where(rng.random(400) > 0.5, "a", "b")
    fr = Frame.from_arrays(cols)
    from h2o3_tpu.models.glm import GLM
    m = GLM(family="binomial", max_iterations=3).train(y="y",
                                                       training_frame=fr)
    mem = _get(server, "/3/Memory?top=100")
    row = next(r for r in mem["top_keys"] if r["key"] == m.key)
    assert row["kind"] == "model" and row["bytes"] > 0
