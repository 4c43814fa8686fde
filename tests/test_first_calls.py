"""What a first call pays, recorded where JAX reports it
(``utils/compile_cache.py``'s listeners): tracing, lowering and the backend's
compile-or-load in seconds by the program's phase, one row an executable
in ``COSTS.snapshot()["first_calls"]``; roll-ups counted where they run.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.glm import GLM
from h2o3_tpu.utils import compile_cache, costs
from h2o3_tpu.utils.costs import COSTS, accounted_jit
from h2o3_tpu.utils.telemetry import METRICS
from h2o3_tpu.utils.timeline import PHASE, TIMELINE, timed_event

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("trace_seconds", "lower_seconds", "backend_seconds")


def rows(fun_name):
    return [r for r in COSTS.snapshot()["first_calls"]
            if r["fun_name"] == fun_name]


def metric(name, **labels):
    return sum(r["value"] for r in METRICS.snapshot(include_buckets=False)
               if r["name"] == name
               and all(r["labels"].get(k) == v for k, v in labels.items()))


def fresh(name):
    """A jitted function no other test has compiled: its name is its row."""
    def fun(x):
        return jnp.tanh(x) * 3.0 + 1.0
    fun.__name__ = fun.__qualname__ = name
    return jax.jit(fun)


def test_a_first_call_is_one_row_under_its_phase_and_a_second_call_none():
    f, x = fresh("first_calls_probe_a"), jnp.ones(11)
    with timed_event("phase", "x:y"):
        f(x)
    [row] = rows("jit(first_calls_probe_a)")
    assert row["phase"] == "x:y" and row["requests"] == 1
    assert all(row[k] > 0 for k in STAGES), row
    before = metric("h2o3_executables_total")
    with timed_event("phase", "x:y"):
        f(x)
    assert rows("jit(first_calls_probe_a)") == [row]
    assert metric("h2o3_executables_total") == before


def test_outside_any_timed_event_the_phase_says_so():
    n = metric("h2o3_executables_total", phase=compile_cache.OUTSIDE)
    fresh("first_calls_probe_b")(jnp.ones(11))
    [row] = rows("jit(first_calls_probe_b)")
    assert row["phase"] == compile_cache.OUTSIDE == "(outside a build)"
    assert metric("h2o3_executables_total",
                  phase=compile_cache.OUTSIDE) == n + 1


def test_the_phase_is_the_innermost_open_event_and_is_put_back():
    assert PHASE.get() is None
    with timed_event("phase", "outer:p"):
        with timed_event("iteration", "inner:q"):
            assert PHASE.get() == "inner:q"
        assert PHASE.get() == "outer:p"
    assert PHASE.get() is None


def test_a_nested_jit_is_traced_once_so_the_stages_stay_under_the_wall():
    inner = fresh("first_calls_probe_inner")

    @jax.jit
    def first_calls_probe_outer(x):
        return inner(x) * 2.0 + jnp.concatenate([x, x]).sum()

    x = jnp.ones(13)
    jax.block_until_ready(x + 1.0)        # the eager operations' own programs
    before = {s: metric("h2o3_first_call_seconds_total", phase="nest:p",
                        stage=s) for s in ("trace", "lower", "backend")}
    with timed_event("phase", "nest:p"):
        t0 = time.time()
        first_calls_probe_outer(x)
        wall = time.time() - t0
    booked = {s: metric("h2o3_first_call_seconds_total", phase="nest:p",
                        stage=s) - before[s] for s in before}
    assert all(v > 0 for v in booked.values()), booked
    assert sum(booked.values()) <= wall
    # one executable: the inner function's trace went to the outer's row
    assert rows("jit(first_calls_probe_inner)") == []
    [row] = rows("jit(first_calls_probe_outer)")
    assert row["trace_seconds"] >= booked["trace"] - 1e-6


def test_a_span_inside_another_is_the_outer_ones_seconds():
    trace, lower, backend = compile_cache._STAGES
    base = {s: metric("h2o3_first_call_seconds_total", phase="nested:p",
                      stage=s) for s in ("trace", "lower", "backend")}
    token = PHASE.set("nested:p")
    try:
        # a trace of 2 s that holds one of 0.5 s; then a lowering of 1 s that
        # holds a trace of 0.25 s; then 3 s in the backend
        compile_cache._on_span_start(trace)
        compile_cache._on_span_start(trace)
        compile_cache._on_span(trace, 10.5, 11.0, fun_name="g")
        compile_cache._on_span(trace, 10.0, 12.0, fun_name="f")
        compile_cache._on_span_start(lower)
        compile_cache._on_span_start(trace)
        compile_cache._on_span(trace, 12.5, 12.75, fun_name="h")
        compile_cache._on_span(lower, 12.0, 13.0, fun_name="jit(f)")
        compile_cache._on_span_start(backend)
        compile_cache._on_span(backend, 13.0, 16.0, fun_name="jit(f)")
    finally:
        PHASE.reset(token)
    got = {s: metric("h2o3_first_call_seconds_total", phase="nested:p",
                     stage=s) - base[s] for s in base}
    assert got == pytest.approx({"trace": 2.0, "lower": 1.0, "backend": 3.0})
    [row] = [r for r in rows("jit(f)") if r["phase"] == "nested:p"]
    assert [row[k] for k in STAGES] == pytest.approx([2.0, 1.0, 3.0])
    assert row["cache_hits"] == 0


def test_a_trace_that_ends_in_no_request_goes_to_no_other_functions_row():
    trace, lower, backend = compile_cache._STAGES
    s0 = metric("h2o3_first_call_seconds_total", phase="orphan:p",
                stage="trace")
    token = PHASE.set("orphan:p")
    try:
        # `jax.eval_shape(f)`: a trace of 4 s and nothing after it; then an
        # unrelated function's own trace, lowering and request
        for event, start, end, name in (
                (trace, 0.0, 4.0, "f"), (trace, 4.0, 4.5, "g"),
                (lower, 4.5, 4.75, "jit(g)"), (backend, 4.75, 5.75, "jit(g)")):
            compile_cache._on_span_start(event)
            compile_cache._on_span(event, start, end, fun_name=name)
        # a request with no trace or lowering of its own since the last one
        compile_cache._on_span_start(backend)
        compile_cache._on_span(backend, 6.0, 6.5, fun_name="jit(k)")
    finally:
        PHASE.reset(token)
    # the seconds were spent, so the counter has them ...
    assert metric("h2o3_first_call_seconds_total", phase="orphan:p",
                  stage="trace") - s0 == pytest.approx(4.5)
    # ... and the rows hold only their own function's
    [g] = [r for r in rows("jit(g)") if r["phase"] == "orphan:p"]
    assert [g[k] for k in STAGES] == pytest.approx([0.5, 0.25, 1.0])
    [k] = [r for r in rows("jit(k)") if r["phase"] == "orphan:p"]
    assert [k[key] for key in STAGES] == pytest.approx([0.0, 0.0, 0.5])


def test_an_executable_asked_for_inside_a_trace_is_still_a_request():
    trace, _, backend = compile_cache._STAGES
    n0 = metric("h2o3_executables_total", phase=compile_cache.OUTSIDE)
    s0 = metric("h2o3_first_call_seconds_total", phase=compile_cache.OUTSIDE)
    compile_cache._on_span_start(trace)
    compile_cache._on_span_start(backend)
    compile_cache._on_span(backend, 20.5, 21.0,
                           fun_name="jit(first_calls_probe_eager)")
    compile_cache._on_span(trace, 20.0, 22.0, fun_name="f")
    assert metric("h2o3_executables_total",
                  phase=compile_cache.OUTSIDE) == n0 + 1
    # its half second lies inside the trace's two and is not counted again
    assert metric("h2o3_first_call_seconds_total",
                  phase=compile_cache.OUTSIDE) - s0 == pytest.approx(2.0)
    [row] = rows("jit(first_calls_probe_eager)")
    assert row["backend_seconds"] == pytest.approx(0.5)


_CACHE_PROBE = """
import json, jax, jax.numpy as jnp
from h2o3_tpu.utils import compile_cache
from h2o3_tpu.utils.costs import COSTS
from h2o3_tpu.utils.telemetry import METRICS
assert compile_cache.enable(default_on=True)

@jax.jit
def first_calls_probe_cached(x):
    return jnp.cos(x) * 2.0

def sources():
    return {r["labels"]["source"]: r["value"]
            for r in METRICS.snapshot(include_buckets=False)
            if r["name"] == "h2o3_executables_total"
            and r["labels"]["phase"] == "cache:p"}

from h2o3_tpu.utils.timeline import timed_event
x = jnp.ones(17)
out = []
for _ in range(2):
    with timed_event("phase", "cache:p"), COSTS.scope("fit:probe"):
        first_calls_probe_cached(x)
    out.append(sources())
    jax.clear_caches()
[row] = [r for r in COSTS.snapshot()["first_calls"]
         if r["fun_name"] == "jit(first_calls_probe_cached)"]
print(json.dumps({"sources": out, "row": row,
                  "by_site": compile_cache.stats()["by_site"]}))
"""


def test_the_source_is_the_compiler_and_then_the_cache(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "H2O3TPU_COMPILE_CACHE"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=180)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sources"] == [{"compiler": 1.0},
                              {"compiler": 1.0, "cache": 1.0}]
    assert out["row"]["requests"] == 2 and out["row"]["cache_hits"] == 1
    # hits and misses by CostMeter site, as before the listeners were folded
    assert out["by_site"]["fit:probe"] == {"hits": 1, "misses": 1}


def test_the_listeners_are_registered_once_a_process():
    from jax._src import monitoring
    ours = {"_event_listeners": compile_cache._on_event,
            "_scalar_listeners": compile_cache._on_span_start,
            "_event_time_span_listeners": compile_cache._on_span}
    compile_cache.listen()
    compile_cache.listen()
    for name, fn in ours.items():
        assert getattr(monitoring, name).count(fn) == 1, name


def test_enable_registers_nothing_more(tmp_path, monkeypatch):
    from jax._src import monitoring
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("H2O3TPU_COMPILE_CACHE", raising=False)
    before = [len(getattr(monitoring, n)) for n in (
        "_event_listeners", "_scalar_listeners",
        "_event_time_span_listeners", "_event_duration_secs_listeners")]
    was = dict(compile_cache._state, by_site=dict(
        compile_cache._state["by_site"]))
    try:
        assert compile_cache.enable(default_on=True)
        assert compile_cache.enable(default_on=True)
    finally:
        compile_cache._state.update(enabled=was["enabled"], dir=was["dir"])
    assert before == [len(getattr(monitoring, n)) for n in (
        "_event_listeners", "_scalar_listeners",
        "_event_time_span_listeners", "_event_duration_secs_listeners")]


def test_compile_seconds_hold_the_stages_the_table_splits():
    def first_calls_probe_site(x):
        return jnp.sin(x) * 2.0
    site = accounted_jit("t:first_calls_sum", first_calls_probe_site,
                         sample=False)
    site(jnp.ones(5))
    [rec] = [s for s in COSTS.snapshot()["sites"]
             if s["site"] == "t:first_calls_sum"]
    [sig] = rec["signatures"]
    # one timer around `lower().compile()`, as `entry.compile_s` reads it;
    # trace and lower seconds apart are the table's, and lie inside it
    assert rec["compile_seconds"] == sig["compile_seconds"] > 0
    assert "lower_seconds" not in sig
    [row] = rows("jit(first_calls_probe_site)")
    assert all(row[k] > 0 for k in STAGES), row
    assert sum(row[k] for k in STAGES) <= rec["compile_seconds"] + 1e-3


def test_a_compile_is_an_annotation_named_after_its_site(monkeypatch):
    seen = []
    real = costs.tracing.annotation
    monkeypatch.setattr(costs.tracing, "annotation",
                        lambda name: (seen.append(name), real(name))[1])
    site = accounted_jit("t:first_calls_ann", lambda x: x + 1.0, sample=False)
    site(jnp.ones(3))
    site(jnp.ones(3))
    assert seen == ["compile:t:first_calls_ann"]


def test_the_table_stays_at_its_bound_and_drops_the_cheapest_first():
    meter = costs.CostMeter()
    for i in range(costs.MAX_FIRST_CALL_ROWS + 40):
        meter.record_first_call("p", f"jit(f{i})", 0.0, 0.0, 1.0 + i, False)
    table = meter.snapshot()["first_calls"]
    assert costs.MAX_FIRST_CALL_ROWS == 256 and len(table) == 256
    assert table[0]["fun_name"] == "jit(f295)"
    assert {r["fun_name"] for r in table}.isdisjoint(
        f"jit(f{i})" for i in range(40))
    meter.record_first_call("p", "jit(f295)", 0.5, 0.25, 1.0, True)
    top = meter.snapshot()["first_calls"][0]
    assert (top["requests"], top["cache_hits"]) == (2, 1)
    assert (top["trace_seconds"], top["lower_seconds"]) == (0.5, 0.25)
    meter.clear()
    assert meter.snapshot()["first_calls"] == []


def _five_columns(rng, nrows=64):
    cols = {f"n{i}": rng.normal(size=nrows) for i in range(3)}
    cols["c0"] = np.where(rng.random(nrows) > 0.5, "a", "b")
    cols["c1"] = np.where(rng.random(nrows) > 0.3, "u", "v")
    return Frame.from_arrays(cols)


def test_rollups_are_counted_where_they_are_computed(rng):
    frame = _five_columns(rng)
    n0 = metric("h2o3_rollups_total")
    by0 = {k: metric("h2o3_rollups_total", kind=k) for k in ("numeric", "cat")}
    s0 = metric("h2o3_rollup_seconds_total")
    t0 = time.perf_counter()
    for v in frame.vecs:
        v.rollups()
    wall = time.perf_counter() - t0
    assert metric("h2o3_rollups_total") == n0 + 5
    assert metric("h2o3_rollups_total", kind="numeric") == by0["numeric"] + 3
    assert metric("h2o3_rollups_total", kind="cat") == by0["cat"] + 2
    assert 0 < metric("h2o3_rollup_seconds_total") - s0 <= wall
    s1 = metric("h2o3_rollup_seconds_total")
    for v in frame.vecs:
        v.rollups()
    assert metric("h2o3_rollups_total") == n0 + 5
    assert metric("h2o3_rollup_seconds_total") == s1


def test_a_rollups_first_call_is_booked_under_its_own_phase(rng):
    # 72 rows pad to a length no other test's frame has: a new program
    frame = _five_columns(rng, nrows=72 * 8 + 8)
    n0 = metric("h2o3_executables_total", phase="frame:rollups")
    with timed_event("phase", "some:build"):
        frame.vecs[0].rollups()
        assert PHASE.get() == "some:build"
    assert metric("h2o3_executables_total", phase="frame:rollups") > n0


def test_rollup_seconds_go_under_the_phase_that_asked(rng):
    frame = _five_columns(rng)

    def seconds():
        return {p: metric("h2o3_rollup_seconds_total", phase=p)
                for p in ("asking:build", compile_cache.OUTSIDE,
                          "frame:rollups")}
    s0 = seconds()
    with timed_event("phase", "asking:build"):
        frame.vecs[0].rollups()
    s1 = seconds()
    assert s1["asking:build"] > s0["asking:build"]
    assert s1[compile_cache.OUTSIDE] == s0[compile_cache.OUTSIDE]
    frame.vecs[1].rollups()          # the frame's making, no build open
    s2 = seconds()
    assert s2[compile_cache.OUTSIDE] > s1[compile_cache.OUTSIDE]
    assert s2["asking:build"] == s1["asking:build"]
    assert s2["frame:rollups"] == 0.0     # that is the first calls' label


def test_train_is_one_phase_a_build_and_samples_no_memory(rng, monkeypatch):
    from h2o3_tpu.utils.memory import MEMORY
    samples = []
    real = MEMORY.sample
    monkeypatch.setattr(MEMORY, "sample",
                        lambda *a, **k: (samples.append(PHASE.get()),
                                         real(*a, **k))[1])
    X = rng.normal(size=(256, 3))
    cols = {f"x{i}": X[:, i] for i in range(3)}
    cols["y"] = X @ np.ones(3)
    TIMELINE.clear()
    GLM(family="gaussian").train(y="y", training_frame=Frame.from_arrays(cols))
    events = [(e["kind"], e["what"]) for e in TIMELINE.snapshot()]
    assert events.count(("phase", "glm:train")) == 1
    assert events.index(("model", "glm:fit")) < events.index(
        ("phase", "glm:train"))            # recorded at exit: fit is inside
    # the two samples a build takes are `glm:fit`'s own (kind "model")
    assert len(samples) == 2
    # what the build compiled has a build's phase, never "(outside a build)"
    phases = {r["phase"] for r in COSTS.snapshot()["first_calls"]}
    assert phases & {"glm:train", "glm:fit", "glm:irls", "glm:megastep",
                     "glm:metrics", "glm:expand", "frame:rollups"}
