"""Chaos harness — fault tolerance end to end (ISSUE 8).

Reference: H2O-3 survives production because its substrate is defensive:
``-random_udp_drop`` (water/H2O.java:446) exercises an RPC retry path, jobs
carry deadlines, and ``hex/faulttolerance/Recovery.java`` snapshots long
jobs so a restart resumes instead of restarting. These tests drive the
TPU-native equivalents: dispatch retry/backoff absorbing injected drops
(results within 1e-6 of the fault-free run — exact, in fact, since retried
dispatches are functional re-runs), job deadlines terminating runaway
builds as CANCELLED with partial results, auto-checkpointed builds resuming
bit-identically, and process-fatal ``crash`` faults proving the resume
paths survive a real kill (subprocess tests, marked slow).
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.gbm import GBM
from h2o3_tpu.models.glm import GLM
from h2o3_tpu.models.job import JobCancelled
from h2o3_tpu.ops.map_reduce import DispatchFailed, map_reduce
from h2o3_tpu.utils.timeline import FaultInjector, inject_faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fast_backoff(monkeypatch):
    monkeypatch.setenv("H2O3TPU_DISPATCH_BACKOFF_MS", "1")


def _binfr(rng, n=500, key=None):
    X = rng.normal(size=(n, 5)).astype(np.float32)
    logit = X[:, 0] * 1.5 - X[:, 1] + 0.3 * X[:, 2]
    y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "yes", "no")
    cols = {f"x{i}": X[:, i] for i in range(5)}
    cols["y"] = y
    return Frame.from_arrays(cols, key=key)


def _raw(model, fr):
    import jax
    return np.asarray(jax.device_get(model._score_raw(fr)))


# -- dispatch retry/backoff ---------------------------------------------------

def test_retry_absorbs_drops_and_marks_span(rng):
    import jax.numpy as jnp

    from h2o3_tpu.utils.tracing import TRACER
    x = jnp.asarray(rng.normal(size=32).astype(np.float32))
    with TRACER.span("chaos_root", root=True) as root:
        tid = root.trace_id
        # seed chosen so at least one drop fires before a success
        with inject_faults(drop_rate=0.6, seed=3) as inj:
            out = map_reduce(lambda s: s.sum(), x)
    assert abs(float(out) - float(np.sum(np.asarray(x)))) < 1e-4
    assert inj.dropped >= 1          # faults were injected AND absorbed
    trace = TRACER.get_trace(tid)
    retried = [s for s in trace["spans"] if s["status"] == "retried"]
    assert retried and retried[0]["attrs"]["retries"] == inj.dropped


def test_retry_exhaustion_raises_structured_dispatch_failed(rng):
    import jax.numpy as jnp

    from h2o3_tpu.utils.telemetry import DISPATCH_RETRIES
    exhausted0 = DISPATCH_RETRIES.labels(fn="map_reduce",
                                         outcome="exhausted").value
    with inject_faults(drop_rate=1.0):
        with pytest.raises(DispatchFailed) as ei:
            map_reduce(lambda s: s.sum(),
                       jnp.ones(16, jnp.float32))
    e = ei.value
    assert e.fn == "map_reduce"
    assert len(e.history) == 4       # 1 attempt + default 3 retries
    assert all("FaultInjected" in h["error"] for h in e.history)
    assert all("backoff_ms" in h for h in e.history[:-1])
    assert DISPATCH_RETRIES.labels(fn="map_reduce",
                                   outcome="exhausted").value \
        == exhausted0 + 1


def test_retries_land_on_the_job_and_jobv3(rng):
    from h2o3_tpu.api import schemas
    fr = _binfr(rng)
    b = GBM(ntrees=4, max_depth=2, seed=1)
    with inject_faults(drop_rate=0.5, seed=11) as inj:
        b.train(y="y", training_frame=fr)
    assert inj.dropped >= 1
    assert b.job.retries == inj.dropped
    v3 = schemas.job_v3(b.job.key, b.job)
    assert v3["retries"] == inj.dropped
    assert v3["auto_recoverable"] is False
    assert v3["max_runtime_secs"] == 0.0


def test_exhausted_budget_records_retry_history_on_job(rng):
    fr = _binfr(rng)
    b = GBM(ntrees=4, max_depth=2, seed=1)
    with pytest.raises(DispatchFailed):
        with inject_faults(site_rates={"gbm_chunk": {"drop_rate": 1.0}}):
            b.train(y="y", training_frame=fr)
    assert b.job.status == "FAILED"
    assert b.job.retry_history and len(b.job.retry_history) == 4


# -- chaos gate: builds complete with parity under faults ---------------------

def test_gbm_completes_exactly_under_drop_injection(rng):
    fr = _binfr(rng)
    clean = GBM(ntrees=8, max_depth=3, seed=5,
                trees_per_dispatch=2).train(y="y", training_frame=fr)
    with inject_faults(drop_rate=0.3, seed=29) as inj:
        faulted = GBM(ntrees=8, max_depth=3, seed=5,
                      trees_per_dispatch=2).train(y="y", training_frame=fr)
    assert inj.dropped >= 1
    # retried dispatches are functional re-runs: parity is EXACT (the 1e-6
    # acceptance bound holds with margin zero)
    np.testing.assert_allclose(_raw(clean, fr), _raw(faulted, fr), atol=1e-6)


def test_glm_completes_exactly_under_drop_and_delay(rng):
    fr = _binfr(rng)
    clean = GLM(family="binomial", lambda_=1e-4,
                max_iterations=12).train(y="y", training_frame=fr)
    with inject_faults(drop_rate=0.3, delay_rate=0.3, delay_ms=2,
                       seed=31) as inj:
        faulted = GLM(family="binomial", lambda_=1e-4,
                      max_iterations=12).train(y="y", training_frame=fr)
    assert inj.dropped + inj.delayed >= 1
    np.testing.assert_allclose(_raw(clean, fr), _raw(faulted, fr), atol=1e-6)


def test_automl_completes_under_fault_injection(rng):
    from h2o3_tpu.orchestration import AutoML
    fr = _binfr(rng, n=300)
    # parallelism=2 (un-pinned): overlapped builds now lease DISJOINT mesh
    # slices from the MeshScheduler, so the two builds' collectives
    # rendezvous on separate device sets and can no longer wedge each
    # other (the hazard that used to force parallelism=1 here). Parity
    # stays exact: same-size slices run the same deterministic programs.
    clean = AutoML(max_models=2, nfolds=0, seed=7, parallelism=2)
    clean.train(y="y", training_frame=fr)
    with inject_faults(drop_rate=0.05, delay_rate=0.1, delay_ms=1, seed=13):
        chaotic = AutoML(max_models=2, nfolds=0, seed=7, parallelism=2)
        chaotic.train(y="y", training_frame=fr)
    assert len(chaotic.leaderboard) == len(clean.leaderboard)
    for mc, mf in zip(clean.leaderboard.models,
                      chaotic.leaderboard.models):
        a = float(mc.training_metrics.auc)
        b = float(mf.training_metrics.auc)
        assert abs(a - b) < 1e-6


# -- job deadlines ------------------------------------------------------------

def test_gbm_deadline_cancels_and_keeps_built_trees(rng):
    from h2o3_tpu.utils.telemetry import JOB_DEADLINE_EXCEEDED
    n0 = JOB_DEADLINE_EXCEEDED._default().value
    fr = _binfr(rng)
    # compile every program of this build first: the deadline is meant to
    # fall between chunks, and in a cold process (xdist hands this test to
    # any worker) 0.8 s pass before the first chunk is even dispatched
    GBM(ntrees=2, max_depth=3, seed=1, trees_per_dispatch=2).train(
        y="y", training_frame=fr)
    b = GBM(ntrees=500, max_depth=3, seed=1, trees_per_dispatch=2,
            max_runtime_secs=0.8)
    m = b.train(y="y", training_frame=fr)
    assert b.job.status == "CANCELLED"
    assert b.job.deadline_exceeded
    assert "max_runtime_secs" in b.job.progress_msg
    assert 0 < m.output["ntrees"] < 500       # partial trees KEPT
    assert m.training_metrics is not None     # finalized despite the cancel
    assert JOB_DEADLINE_EXCEEDED._default().value == n0 + 1


def test_glm_deadline_terminates_as_cancelled(rng):
    fr = _binfr(rng)
    b = GLM(family="binomial", lambda_=1e-4, max_iterations=5000,
            max_runtime_secs=1e-4)
    with pytest.raises(JobCancelled, match="max_runtime_secs"):
        b.train(y="y", training_frame=fr)
    assert b.job.status == "CANCELLED"
    assert b.job.deadline_exceeded


def test_drf_deadline_cancels_before_forest_launch(rng):
    """DRF grows its whole forest in ONE fused program: the deadline is
    checked at the dispatch boundary, so an expired budget cancels before
    the program launches (docs/RELIABILITY.md)."""
    from h2o3_tpu.models.gbm import DRF
    fr = _binfr(rng)
    b = DRF(ntrees=50, max_depth=3, seed=1, max_runtime_secs=1e-4)
    with pytest.raises(JobCancelled, match="max_runtime_secs"):
        b.train(y="y", training_frame=fr)
    assert b.job.status == "CANCELLED"
    assert b.job.deadline_exceeded


def test_dart_deadline_keeps_built_trees(rng):
    """DART rounds run as a host loop, so it keeps grown trees on deadline
    like the other tree builders (partial model, job CANCELLED)."""
    from h2o3_tpu.models.xgboost import XGBoost
    fr = _binfr(rng)
    b = XGBoost(booster="dart", ntrees=4000, max_depth=3, seed=1,
                rate_drop=0.2, max_runtime_secs=1.0)
    m = b.train(y="y", training_frame=fr)
    assert b.job.status == "CANCELLED"
    assert b.job.deadline_exceeded
    assert 0 < m.output["ntrees"] < 4000      # partial trees KEPT
    assert m.training_metrics is not None


def test_deadline_surfaces_in_job_v3(rng):
    from h2o3_tpu.api import schemas
    fr = _binfr(rng)
    b = GBM(ntrees=500, max_depth=3, seed=1, trees_per_dispatch=2,
            max_runtime_secs=0.8)
    b.train(y="y", training_frame=fr)
    v3 = schemas.job_v3(b.job.key, b.job)
    assert v3["status"] == "CANCELLED"
    assert v3["deadline_exceeded"] is True
    assert v3["max_runtime_secs"] == 0.8


# -- auto-checkpointed builds -------------------------------------------------

def test_gbm_auto_checkpoint_resumes_bit_identical(rng, tmp_path,
                                                   monkeypatch):
    monkeypatch.setenv("H2O3TPU_CHECKPOINT_EVERY", "4")
    fr = _binfr(rng)
    rdir = str(tmp_path / "rec")
    clean = GBM(ntrees=12, max_depth=3, seed=1,
                trees_per_dispatch=4).train(y="y", training_frame=fr)
    # interruption: the SECOND chunk's dispatch exhausts its retry budget
    # (drop_rate=1.0 armed after one success) — the build dies after the
    # first snapshot landed, like a crash between checkpoints
    with pytest.raises(DispatchFailed):
        with inject_faults(site_rates={"gbm_chunk": {"drop_rate": 1.0,
                                                     "after": 1}}):
            GBM(ntrees=12, max_depth=3, seed=1, trees_per_dispatch=4,
                auto_recovery_dir=rdir).train(y="y", training_frame=fr)
    assert os.path.exists(os.path.join(rdir, "model_snapshot.bin"))
    resumed = GBM(ntrees=12, max_depth=3, seed=1, trees_per_dispatch=4,
                  auto_recovery_dir=rdir).train(y="y", training_frame=fr)
    assert resumed.output["ntrees"] == 12
    # per-tree PRNG replay + sequential margin fold: BIT-identical trees
    for i, (tc, tr) in enumerate(zip(clean.output["trees"],
                                     resumed.output["trees"])):
        for ch in ("feat", "thresh_bin", "thresh_val", "na_left",
                   "is_split", "leaf"):
            assert np.array_equal(np.asarray(getattr(tc, ch)),
                                  np.asarray(getattr(tr, ch))), (i, ch)
    # success retires the snapshot: the next run trains fresh
    assert not os.path.exists(os.path.join(rdir, "model_snapshot.bin"))


def test_deadline_cancelled_build_leaves_resumable_snapshot(rng, tmp_path,
                                                            monkeypatch):
    monkeypatch.setenv("H2O3TPU_CHECKPOINT_EVERY", "2")
    fr = _binfr(rng)
    rdir = str(tmp_path / "rec")
    b = GBM(ntrees=500, max_depth=3, seed=1, trees_per_dispatch=2,
            max_runtime_secs=0.8, auto_recovery_dir=rdir)
    m = b.train(y="y", training_frame=fr)
    assert b.job.status == "CANCELLED"
    # CANCELLED keeps the snapshot (only DONE retires it) and the job
    # advertises recoverability
    assert os.path.exists(os.path.join(rdir, "model_snapshot.bin"))
    from h2o3_tpu.api import schemas
    v3 = schemas.job_v3(b.job.key, b.job)
    assert v3["auto_recoverable"] is True
    assert v3["auto_recovery_dir"] == rdir
    with open(os.path.join(rdir, "build_recovery.json")) as fh:
        state = json.load(fh)
    assert state["progress"] >= m.output["ntrees"] - 1
    assert state["target"] == 500


def test_snapshot_with_different_params_is_not_resumed(rng, tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv("H2O3TPU_CHECKPOINT_EVERY", "2")
    fr = _binfr(rng)
    rdir = str(tmp_path / "rec")
    with pytest.raises(DispatchFailed):
        with inject_faults(site_rates={"gbm_chunk": {"drop_rate": 1.0,
                                                     "after": 1}}):
            GBM(ntrees=8, max_depth=3, seed=1, trees_per_dispatch=2,
                auto_recovery_dir=rdir).train(y="y", training_frame=fr)
    # different depth: the stale snapshot must be IGNORED, not resumed
    # into a differently-shaped ensemble
    m = GBM(ntrees=4, max_depth=2, seed=1,
            auto_recovery_dir=rdir).train(y="y", training_frame=fr)
    ref = GBM(ntrees=4, max_depth=2, seed=1).train(y="y", training_frame=fr)
    np.testing.assert_allclose(_raw(m, fr), _raw(ref, fr), atol=0)


def test_auto_checkpoint_tolerates_callable_params(rng, tmp_path,
                                                   monkeypatch):
    """An unpicklable custom_metric_func (lambda) must not poison the
    snapshot: the artifact drops callables, and the fingerprint encodes
    them by NAME (str() would embed a process-specific address, silently
    breaking every cross-process resume)."""
    from h2o3_tpu.persist.recovery import _params_fingerprint
    # two distinct lambdas (distinct addresses, same qualname) fingerprint
    # identically — the address never reaches the fingerprint
    assert _params_fingerprint({"custom_metric_func": lambda a: a}) == \
        _params_fingerprint({"custom_metric_func": lambda a: a + 1})

    monkeypatch.setenv("H2O3TPU_CHECKPOINT_EVERY", "4")
    fr = _binfr(rng)
    rdir = str(tmp_path / "rec")

    def cmf(preds, yv, w):
        return float(np.sum(w))

    with pytest.raises(DispatchFailed):
        with inject_faults(site_rates={"gbm_chunk": {"drop_rate": 1.0,
                                                     "after": 1}}):
            GBM(ntrees=12, max_depth=3, seed=1, trees_per_dispatch=4,
                auto_recovery_dir=rdir,
                custom_metric_func=cmf).train(y="y", training_frame=fr)
    # the lambda didn't fail the snapshot write: chunk 1's checkpoint landed
    assert os.path.exists(os.path.join(rdir, "model_snapshot.bin"))
    # and it is RESUMABLE by a like-configured builder (fingerprint matches
    # even though the stored params dropped the callable)
    from h2o3_tpu.persist.recovery import BuildRecovery
    resumer = GBM(ntrees=12, max_depth=3, seed=1, trees_per_dispatch=4,
                  auto_recovery_dir=rdir, custom_metric_func=cmf)
    snap = BuildRecovery(rdir).load_snapshot(resumer.params)
    assert snap is not None and snap.output["ntrees"] == 4
    m = resumer.train(y="y", training_frame=fr)
    assert m.output["ntrees"] == 12
    assert getattr(m.training_metrics, "custom_metric_value", None) is not None
    ref = GBM(ntrees=12, max_depth=3, seed=1,
              trees_per_dispatch=4).train(y="y", training_frame=fr)
    np.testing.assert_allclose(_raw(m, fr), _raw(ref, fr), atol=0)


def test_rest_deadline_metadata_survives_no_partial_builders(rng):
    """The REST job must carry deadline evidence even when the builder
    keeps NO partial results (GLM raises JobCancelled): pollers need to
    distinguish a deadline kill from a user cancel."""
    import time as _t

    from h2o3_tpu.api import H2OClient, H2OServer
    from h2o3_tpu.utils.registry import DKV
    fr = _binfr(rng, key="chaos_rest_fr")
    DKV.put("chaos_rest_fr", fr)
    s = H2OServer(port=0).start()
    try:
        c = H2OClient(s.url)
        out = c.request("POST", "/3/ModelBuilders/glm",
                        {"training_frame": "chaos_rest_fr",
                         "response_column": "y", "family": "binomial",
                         "max_iterations": 5000,
                         "max_runtime_secs": 1e-4})
        jk = out["job"]["key"]["name"]
        for _ in range(600):
            j = c.job(jk)
            if j["status"] in ("DONE", "FAILED", "CANCELLED"):
                break
            _t.sleep(0.05)
        assert j["status"] == "CANCELLED"
        assert j["deadline_exceeded"] is True
        assert "max_runtime_secs" in j["progress_msg"]
    finally:
        s.stop()


def test_auto_recoverable_only_advertised_where_snapshots_exist(rng,
                                                                tmp_path):
    """auto_recoverable must be a PROMISE, not an echo of the param: a
    builder that never writes snapshots (GLM) ignores auto_recovery_dir,
    so a client trusting the flag never restarts into a from-scratch
    build."""
    from h2o3_tpu.api import schemas
    fr = _binfr(rng)
    b = GLM(family="binomial", lambda_=1e-4, max_iterations=3,
            auto_recovery_dir=str(tmp_path / "glm_rec"))
    b.train(y="y", training_frame=fr)
    v3 = schemas.job_v3(b.job.key, b.job)
    assert v3["auto_recoverable"] is False
    assert v3["auto_recovery_dir"] is None


def test_zero_tree_partial_scores_and_resumes(rng):
    """A deadline that trips before the FIRST chunk yields a legal
    zero-tree model (the partial-keep path supports it): it must score as
    the null model (f0 only) and must be resumable as a checkpoint without
    crashing the margin fold. Constructed directly — the deadline hitting
    exactly inside that window is not schedulable deterministically."""
    from h2o3_tpu.models.gbm import GBMModel
    from h2o3_tpu.models.model_base import ModelParameters
    fr = _binfr(rng)
    ref = GBM(ntrees=6, max_depth=3, seed=1).train(y="y", training_frame=fr)
    zero = GBMModel(
        key="zero_cp", params=ModelParameters(ref.params),
        data_info=None, response_column="y",
        response_domain=ref.response_domain,
        output=dict(trees=[], edges=ref.output["edges"],
                    f0=ref.output["f0"], learn_rate=0.1,
                    distribution="bernoulli",
                    x_cols=ref.output["x_cols"],
                    feat_domains=ref.output["feat_domains"], ntrees=0))
    p0 = _raw(zero, fr)
    assert np.isfinite(p0).all()              # null-model probabilities
    resumed = GBM(ntrees=6, max_depth=3, seed=1,
                  checkpoint=zero).train(y="y", training_frame=fr)
    np.testing.assert_allclose(_raw(resumed, fr), _raw(ref, fr), atol=0)


def test_zero_round_multinomial_partial_scores(rng):
    from h2o3_tpu.models.gbm import GBMModel
    from h2o3_tpu.models.model_base import ModelParameters
    n = 300
    X = rng.normal(size=(n, 3)).astype(np.float32)
    lab = np.array(["a", "b", "c"])[np.argmax(
        np.stack([X[:, 0], X[:, 1], X[:, 2]], 1), 1)]
    fr = Frame.from_arrays({"x0": X[:, 0], "x1": X[:, 1], "x2": X[:, 2],
                            "y": lab})
    ref = GBM(ntrees=3, max_depth=3, seed=2).train(y="y", training_frame=fr)
    zero = GBMModel(
        key="zero_cp_multi", params=ModelParameters(ref.params),
        data_info=None, response_column="y",
        response_domain=ref.response_domain,
        output=dict(trees_multi=[[], [], []], edges=ref.output["edges"],
                    f0_multi=ref.output["f0_multi"], learn_rate=0.1,
                    distribution="multinomial",
                    x_cols=ref.output["x_cols"],
                    feat_domains=ref.output["feat_domains"], ntrees=0))
    probs = _raw(zero, fr)
    assert probs.shape == (fr.plen, 3) and np.isfinite(probs).all()


# -- FaultInjector thread-safety ----------------------------------------------

def test_fault_injector_is_thread_safe():
    """Satellite: unlocked RNG draws + counter increments under-counted
    faults when chaos ran under windowed_parallel — the injected-fault
    count must equal the raised-fault count exactly."""
    inj = FaultInjector(drop_rate=0.5, seed=9)
    raised = [0] * 8

    def hammer(i):
        from h2o3_tpu.utils.timeline import FaultInjected
        for _ in range(500):
            try:
                inj.maybe_fault("hammer")
            except FaultInjected:
                raised[i] += 1

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert inj.dropped == sum(raised)
    assert inj._site_calls["hammer"] == 8 * 500


def test_site_rates_scope_faults_to_one_call_site(rng):
    import jax.numpy as jnp
    x = jnp.ones(16, jnp.float32)
    with inject_faults(site_rates={"elsewhere": {"drop_rate": 1.0}}) as inj:
        out = map_reduce(lambda s: s.sum(), x)   # map_reduce not targeted
    assert float(out) == 16.0 and inj.dropped == 0


# -- crash kind: process-fatal, resume across a REAL kill (slow) --------------

def _run_crash_script(body: str, tmp_path) -> subprocess.CompletedProcess:
    script = textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["H2O3TPU_DISPATCH_BACKOFF_MS"] = "1"
    return subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


_CRASH_PRELUDE = """
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from h2o3_tpu.frame.frame import Frame
rng = np.random.default_rng(42)
n = 500
X = rng.normal(size=(n, 5)).astype(np.float32)
logit = X[:, 0] * 1.5 - X[:, 1] + 0.3 * X[:, 2]
y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "yes", "no")
cols = {f"x{i}": X[:, i] for i in range(5)}
cols["y"] = y
fr = Frame.from_arrays(cols)
"""


@pytest.mark.slow
def test_crash_kind_kills_process_and_gbm_resumes_bit_identical(rng,
                                                                tmp_path):
    """Tentpole (d): a ``crash`` fault is PROCESS-FATAL (os._exit mid-build,
    the kill -9 scenario). The restarted process resumes from the
    auto-checkpoint and produces bit-identical final trees."""
    rdir = str(tmp_path / "rec")
    crash = _run_crash_script(_CRASH_PRELUDE + f"""
import os
os.environ["H2O3TPU_CHECKPOINT_EVERY"] = "4"
from h2o3_tpu.models.gbm import GBM
from h2o3_tpu.utils import timeline
timeline.FAULTS = timeline.FaultInjector(
    site_rates={{"gbm_chunk": {{"crash_after": 2}}}})
GBM(ntrees=12, max_depth=3, seed=1, trees_per_dispatch=4,
    auto_recovery_dir={rdir!r}).train(y="y", training_frame=fr)
print("UNREACHABLE")
""", tmp_path)
    assert crash.returncode == 86, (crash.stdout, crash.stderr[-2000:])
    assert "UNREACHABLE" not in crash.stdout
    assert os.path.exists(os.path.join(rdir, "model_snapshot.bin"))

    resume = _run_crash_script(_CRASH_PRELUDE + f"""
import os, json
os.environ["H2O3TPU_CHECKPOINT_EVERY"] = "4"
import jax
from h2o3_tpu.models.gbm import GBM
clean = GBM(ntrees=12, max_depth=3, seed=1,
            trees_per_dispatch=4).train(y="y", training_frame=fr)
resumed = GBM(ntrees=12, max_depth=3, seed=1, trees_per_dispatch=4,
              auto_recovery_dir={rdir!r}).train(y="y", training_frame=fr)
identical = all(
    np.array_equal(np.asarray(getattr(tc, ch)), np.asarray(getattr(tr, ch)))
    for tc, tr in zip(clean.output["trees"], resumed.output["trees"])
    for ch in ("feat", "thresh_bin", "thresh_val", "na_left", "is_split",
               "leaf"))
print(json.dumps({{"ntrees": resumed.output["ntrees"],
                   "identical": identical}}))
""", tmp_path)
    assert resume.returncode == 0, resume.stderr[-2000:]
    out = json.loads(resume.stdout.strip().splitlines()[-1])
    assert out == {"ntrees": 12, "identical": True}


@pytest.mark.slow
def test_grid_crash_resume_skips_built_combos_and_matches_leaderboard(
        rng, tmp_path):
    """Satellite: kill a grid search mid-combo (chaos ``crash``), restart
    from the recovery dir — already-built combos are skipped and the final
    leaderboard matches an uninterrupted run."""
    rdir = str(tmp_path / "grid_rec")
    crash = _run_crash_script(_CRASH_PRELUDE + f"""
from h2o3_tpu.orchestration.grid import GridSearch
from h2o3_tpu.models.gbm import GBM
from h2o3_tpu.utils import timeline
timeline.FAULTS = timeline.FaultInjector(
    site_rates={{"gbm_chunk": {{"crash_after": 3}}}})
GridSearch(GBM, {{"max_depth": [2, 3, 4]}}, grid_id="chaos_grid",
           recovery_dir={rdir!r}, ntrees=3, seed=1).train(
    y="y", training_frame=fr)
print("UNREACHABLE")
""", tmp_path)
    assert crash.returncode == 86, (crash.stdout, crash.stderr[-2000:])

    resume = _run_crash_script(_CRASH_PRELUDE + f"""
import json
from h2o3_tpu.orchestration.grid import GridSearch
from h2o3_tpu.models.gbm import GBM
from h2o3_tpu.persist.recovery import Recovery
rec = Recovery({rdir!r})
pre_built = len(rec._state["built"])
g = GridSearch(GBM, {{"max_depth": [2, 3, 4]}}, grid_id="chaos_grid",
               recovery_dir={rdir!r}, ntrees=3, seed=1).train(
    y="y", training_frame=fr)
ref = GridSearch(GBM, {{"max_depth": [2, 3, 4]}}, grid_id="ref_grid",
                 ntrees=3, seed=1).train(y="y", training_frame=fr)
lb = [round(float(m.training_metrics.auc), 9) for m in g.sorted_models()]
lb_ref = [round(float(m.training_metrics.auc), 9)
          for m in ref.sorted_models()]
print(json.dumps({{"pre_built": pre_built, "models": len(g.models),
                   "depths": sorted(m.output["hyper_values"]["max_depth"]
                                    for m in g.models),
                   "match": lb == lb_ref}}))
""", tmp_path)
    assert resume.returncode == 0, resume.stderr[-2000:]
    out = json.loads(resume.stdout.strip().splitlines()[-1])
    # the crash landed mid-3rd-build: ≥1 combo was recovered from disk,
    # the space completed once, and the leaderboard matches fault-free
    assert out["pre_built"] >= 1
    assert out["models"] == 3 and out["depths"] == [2, 3, 4]
    assert out["match"] is True
