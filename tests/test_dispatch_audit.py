"""Dispatch-count regression tests (ISSUE 7): the iterative hot paths pay
exactly ONE blocking host fetch per K-step megastep / per GBM chunk — a
future reintroduction of a per-iteration ``device_get`` fails here fast.

Counting strategy: ``jax.device_get`` is monkeypatched with a counting
wrapper for the duration of each fit (every blocking batched fetch in the
drivers goes through it), and the builders' ``_dispatch_audit`` pins the
loop-level accounting (iterations, host syncs, compiled dispatches).
"""

import numpy as np
import pytest

import jax


@pytest.fixture
def count_device_get(monkeypatch):
    """Count jax.device_get calls; the models modules call through the
    ``jax`` module attribute, so one patch covers every driver."""
    counter = {"n": 0}
    real = jax.device_get

    def counting(*args, **kwargs):
        counter["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(jax, "device_get", counting)
    return counter


def _glm_frame(rng, n=512, k=6):
    from h2o3_tpu.frame.frame import Frame
    X = rng.normal(size=(n, k)).astype(np.float32)
    logit = X[:, :3] @ np.array([0.9, -0.6, 0.3], np.float32)
    cols = {f"x{i}": X[:, i] for i in range(k)}
    cols["y"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "a", "b")
    cols["y3"] = rng.choice(["p", "q", "r"], size=n)
    cols["t"] = (X[:, 0] * 2 + 0.1 * rng.normal(size=n)).astype(np.float32)
    return Frame.from_arrays(cols), [f"x{i}" for i in range(k)]


def test_glm_irls_one_sync_per_megastep(rng, count_device_get, monkeypatch):
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.models.model_base import megastep_k

    monkeypatch.setenv("H2O3TPU_MEGASTEP_K", "4")
    assert megastep_k() == 4
    fr, x = _glm_frame(rng)
    b = GLM(family="binomial", lambda_=1e-4, max_iterations=20)
    before = count_device_get["n"]
    m = b.train(y="y", training_frame=fr, x=x)
    total_gets = count_device_get["n"] - before

    audit = b._dispatch_audit["glm_irls"]
    iters = m.output["iterations"]
    assert audit["iterations"] == iters
    # exactly ONE blocking fetch per megastep: ceil(iterations / K)
    assert audit["host_syncs"] == -(-iters // 4)
    assert audit["syncs_per_iteration"] <= 1.0 / 4 + 0.26  # ragged last chunk
    # whole-fit guard: init + IRLS megasteps + post-fit reporting. A
    # reintroduced per-iteration fetch adds ~`iters` gets and fails this.
    assert total_gets < 10 + audit["host_syncs"] + iters / 2, (
        f"{total_gets} device_get calls for {iters} IRLS iterations — "
        "a per-iteration host sync came back")
    # scoring history survives the batched fetch: one deviance per iteration
    assert len(b._iter_devs) == iters


def test_glm_megastep_results_match_per_step_path(rng, monkeypatch):
    """K=8 megasteps vs K=1 (per-step semantics): identical coefficients,
    deviance, and reported iteration counts — the acceptance criterion for
    the device-resident convergence test."""
    from h2o3_tpu.models.glm import GLM

    fr, x = _glm_frame(rng)
    out = {}
    for k in ("1", "8"):
        monkeypatch.setenv("H2O3TPU_MEGASTEP_K", k)
        m = GLM(family="binomial", lambda_=1e-4, max_iterations=25).train(
            y="y", training_frame=fr, x=x)
        out[k] = (m.output["iterations"], m.output["residual_deviance"],
                  np.asarray(m.output["coef"]))
    assert out["1"][0] == out["8"][0]                 # same iteration count
    assert abs(out["1"][1] - out["8"][1]) < 1e-6 * max(abs(out["1"][1]), 1.0)
    np.testing.assert_allclose(out["1"][2], out["8"][2], atol=1e-6)


def test_glm_multinomial_one_sync_per_megastep(rng, count_device_get,
                                               monkeypatch):
    from h2o3_tpu.models.glm import GLM

    monkeypatch.setenv("H2O3TPU_MEGASTEP_K", "4")
    fr, x = _glm_frame(rng)
    b = GLM(family="multinomial", max_iterations=12)
    before = count_device_get["n"]
    m = b.train(y="y3", training_frame=fr, x=x)
    total_gets = count_device_get["n"] - before

    audit = b._dispatch_audit["glm_multinomial"]
    iters = m.output["iterations"]
    assert audit["iterations"] == iters
    assert audit["host_syncs"] == -(-iters // 4)
    assert total_gets < 10 + audit["host_syncs"] + iters / 2


def test_sparse_glm_one_sync_per_megastep(rng, count_device_get, monkeypatch):
    from h2o3_tpu.frame.sparse import SparseFrame, SparseMatrix
    from h2o3_tpu.frame.vec import Vec
    from h2o3_tpu.models.glm import GLM

    monkeypatch.setenv("H2O3TPU_MEGASTEP_K", "4")
    n, k = 256, 40
    rows = np.repeat(np.arange(n), 3).astype(np.int32)
    cols = rng.integers(0, k, size=3 * n).astype(np.int32)
    vals = rng.normal(size=3 * n).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    sf = SparseFrame(SparseMatrix.from_scipy_like(rows, cols, vals, n, k),
                     {"y": Vec.from_numpy(y)})
    b = GLM(family="binomial", lambda_=1e-3, max_iterations=12)
    before = count_device_get["n"]
    m = b.train(y="y", training_frame=sf)
    total_gets = count_device_get["n"] - before

    audit = b._dispatch_audit["glm_sparse_irls"]
    iters = m.output["iterations"]
    assert audit["iterations"] == iters
    assert audit["host_syncs"] == -(-iters // 4)
    assert total_gets < 10 + audit["host_syncs"] + iters / 2


def test_gbm_one_sync_per_chunk(rng, count_device_get):
    from h2o3_tpu.models.gbm import GBM

    fr, x = _glm_frame(rng, n=256)
    b = GBM(ntrees=12, max_depth=3, nbins=16, seed=1, trees_per_dispatch=4)
    before = count_device_get["n"]
    m = b.train(y="y", training_frame=fr, x=x)
    total_gets = count_device_get["n"] - before

    audit = b._dispatch_audit["gbm_round"]
    assert audit["iterations"] == 12                  # boosting rounds
    assert audit["host_syncs"] == 3                   # 12 trees / 4 per chunk
    assert m.output["ntrees"] == 12
    # f0 init + per-chunk heap fetches + metrics; NOT one per round
    assert total_gets < 10 + audit["host_syncs"] + 12 / 2


def test_gbm_auto_chunking_single_dispatch(rng, count_device_get):
    """Default sizing at test scale: the whole ensemble in ONE compiled
    dispatch and one heap fetch."""
    from h2o3_tpu.models.gbm import GBM

    fr, x = _glm_frame(rng, n=256)
    b = GBM(ntrees=10, max_depth=3, nbins=16, seed=1)
    b.train(y="y", training_frame=fr, x=x)
    assert b._dispatch_audit["gbm_round"]["host_syncs"] == 1


def test_gbm_trees_per_dispatch_validated(rng):
    from h2o3_tpu.models.gbm import GBM

    fr, x = _glm_frame(rng, n=128)
    with pytest.raises(ValueError, match="trees_per_dispatch"):
        GBM(ntrees=4, trees_per_dispatch=-1).train(
            y="y", training_frame=fr, x=x)


def test_dl_epochs_no_per_epoch_sync(rng, count_device_get, monkeypatch):
    from h2o3_tpu.models.deeplearning import DeepLearning

    monkeypatch.setenv("H2O3TPU_MEGASTEP_K", "4")
    fr, x = _glm_frame(rng, n=256)
    b = DeepLearning(hidden=[8], epochs=8, mini_batch_size=32, seed=3)
    before = count_device_get["n"]
    m = b.train(y="y", training_frame=fr, x=x)
    total_gets = count_device_get["n"] - before

    audit = b._dispatch_audit["dl_epoch"]
    assert audit["iterations"] == 8                   # epochs
    assert audit["device_dispatches"] == 2            # 8 epochs / K=4
    assert audit["host_syncs"] == 1                   # one post-loop fetch
    assert len(m.output["score_history"]) == 8        # per-epoch losses kept
    # loss series + samples_trained + metrics — never one get per epoch
    assert total_gets < 12


def test_dispatch_gauge_published(rng):
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.utils.telemetry import DISPATCHES_PER_ITER

    fr, x = _glm_frame(rng)
    GLM(family="binomial", lambda_=1e-4, max_iterations=10).train(
        y="y", training_frame=fr, x=x)
    vals = {labels["loop"]: child.value
            for labels, child in DISPATCHES_PER_ITER.children()}
    assert "glm_irls" in vals and 0 < vals["glm_irls"] <= 1.0
