"""Metrics parity tests (reference: hex/AUC2, ModelMetrics* semantics)."""

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.models.metrics import binomial_metrics, multinomial_metrics, regression_metrics


def _device(rng, arr):
    return Vec.from_numpy(np.asarray(arr, np.float32)).data


def test_auc_matches_sklearn(rng):
    n = 5000
    y = (rng.uniform(size=n) < 0.4).astype(np.float32)
    p = np.clip(y * 0.3 + rng.uniform(size=n) * 0.7, 0, 1).astype(np.float32)
    pd_, yd = _device(rng, p), _device(rng, y)
    mask = jnp.arange(pd_.shape[0]) < n
    m = binomial_metrics(pd_, yd, mask)
    from sklearn.metrics import roc_auc_score, log_loss
    # 400-bin histogram AUC is exact to ~1/400 (reference accepts this too)
    assert abs(m.auc - roc_auc_score(y, p)) < 0.004
    assert abs(m.logloss - log_loss(y, np.clip(p, 1e-15, 1 - 1e-15))) < 1e-5
    assert m.nobs == n
    assert m.confusion_matrix.sum() == n


def test_regression_metrics(rng):
    n = 3000
    y = rng.normal(size=n).astype(np.float32)
    pred = y + rng.normal(scale=0.5, size=n).astype(np.float32)
    yd, pd_ = _device(rng, y), _device(rng, pred)
    mask = jnp.arange(yd.shape[0]) < n
    m = regression_metrics(pd_, yd, mask)
    np.testing.assert_allclose(m.mse, ((pred - y) ** 2).mean(), rtol=1e-4)
    np.testing.assert_allclose(m.mae, np.abs(pred - y).mean(), rtol=1e-4)
    ss_res = ((pred - y) ** 2).sum()
    ss_tot = ((y - y.mean()) ** 2).sum()
    np.testing.assert_allclose(m.r2, 1 - ss_res / ss_tot, atol=1e-4)


def test_multinomial_metrics(rng):
    n, k = 2000, 4
    y = rng.integers(0, k, size=n).astype(np.float32)
    logits = rng.normal(size=(n, k)).astype(np.float32)
    logits[np.arange(n), y.astype(int)] += 2.0
    probs = np.exp(logits) / np.exp(logits).sum(1, keepdims=True)
    from h2o3_tpu.frame.vec import padded_len
    plen = padded_len(n)
    P = np.zeros((plen, k), np.float32)
    P[:n] = probs
    yd = _device(rng, y)
    mask = jnp.arange(plen) < n
    m = multinomial_metrics(jnp.asarray(P), yd, mask, k)
    from sklearn.metrics import log_loss, confusion_matrix
    np.testing.assert_allclose(m.logloss, log_loss(y, probs, labels=list(range(k))), rtol=1e-4)
    np.testing.assert_array_equal(m.confusion_matrix, confusion_matrix(y, probs.argmax(1)))
    assert m.accuracy > 0.7


def test_gains_lift_table(rng):
    """Reference: hex/GainsLift.java — table invariants at the last row:
    cumulative data fraction 1.0, cumulative capture rate 1.0, cum lift 1.0."""
    import jax.numpy as jnp
    from h2o3_tpu.models.metrics import binomial_metrics

    n = 4000
    p = rng.random(n).astype(np.float32)
    y = (rng.random(n) < p).astype(np.float32)   # well-calibrated scores
    m = binomial_metrics(jnp.asarray(p), jnp.asarray(y), jnp.ones(n, bool))
    gl = m.gains_lift(groups=16)
    assert 10 <= len(gl) <= 16
    last = gl[-1]
    assert last["cumulative_data_fraction"] == pytest.approx(1.0, abs=1e-9)
    assert last["cumulative_capture_rate"] == pytest.approx(1.0, abs=1e-9)
    assert last["cumulative_lift"] == pytest.approx(1.0, abs=1e-6)
    # calibrated scores → top group lift well above 1, monotone-ish capture
    assert gl[0]["lift"] > 1.5
    assert m.ks > 0.3
    # KS column max matches the scalar KS metric up to binning
    assert max(r["kolmogorov_smirnov"] for r in gl) == pytest.approx(m.ks, abs=0.05)


def _bincount64(p, y, mask, nbins=400):
    """The score histogram in numpy float64, by the pass's own bucket rule."""
    y, p = y[mask].astype(np.float64), p[mask]
    bins = np.clip((p * np.float32(nbins)).astype(np.int32), 0, nbins - 1)
    return (np.bincount(bins, weights=y, minlength=nbins),
            np.bincount(bins, weights=1.0 - y, minlength=nbins),
            np.bincount(bins, weights=p.astype(np.float64), minlength=nbins))


def _scores(rng, case):
    """(p, mask) of one case of the blocked histogram. A block is 512 rows
    up to 262,144 rows and a power of two that covers the rows in 512 steps
    past that (``metrics._hist_block``)."""
    if case == "under-one-block":
        return rng.random(300).astype(np.float32), None
    if case == "one-block":
        return rng.random(512).astype(np.float32), None
    if case == "three-blocks-and-17":
        return rng.random(3 * 512 + 17).astype(np.float32), None
    if case == "longer-blocks":                # 2,048 rows a block, 293 blocks
        return rng.random(600_000).astype(np.float32), None
    if case == "one-bucket":
        return np.full(2 * 512 + 5, 0.3, np.float32), None
    if case == "zero-and-one":
        p = rng.random(5000).astype(np.float32)
        p[:100], p[100:300] = 0.0, 1.0
        return p, None
    if case == "masked-nan":
        p = rng.random(512 + 300).astype(np.float32)
        mask = rng.random(p.size) < 0.8
        p[~mask] = np.nan
        return p, mask
    assert case == "few-distinct-scores"      # a five-tree GBM's leaves
    leaves = rng.random(40).astype(np.float32)
    return leaves[rng.integers(0, 40, size=300_999)], None


@pytest.mark.parametrize("case", [
    "under-one-block", "one-block", "three-blocks-and-17", "longer-blocks",
    "one-bucket", "zero-and-one", "masked-nan", "few-distinct-scores"])
def test_binomial_pass_histogram_matches_float64_bincount(rng, case):
    """The blocked one-hot product against a numpy float64 ``bincount``:
    counts equal exactly, score sums to float32's last bits."""
    from h2o3_tpu.models import metrics
    p, mask = _scores(rng, case)
    assert metrics._hist_block(p.size) == {
        "under-one-block": 300, "longer-blocks": 2048,
        "few-distinct-scores": 1024}.get(case, 512)
    mask = np.ones(p.size, bool) if mask is None else mask
    y = (rng.random(p.size) < 0.4).astype(np.float32)
    y[~mask] = np.nan                      # a masked row's response too
    r = jax.device_get(metrics._binomial_pass(
        jnp.asarray(p), jnp.asarray(y), jnp.asarray(mask)))
    tp, fp, s = _bincount64(p, y, mask)
    np.testing.assert_array_equal(r["tp_h"], tp)
    np.testing.assert_array_equal(r["fp_h"], fp)
    np.testing.assert_allclose(r["s_h"], s, rtol=1e-6, atol=0)
    assert r["tp_h"].sum() + r["fp_h"].sum() == mask.sum()
    if case == "zero-and-one":
        assert tp[0] + fp[0] >= 100 and tp[399] + fp[399] >= 200
    if case == "one-bucket":
        assert np.count_nonzero(tp + fp) == 1


def test_binomial_pass_sums_row_sharded_scores_where_they_live(rng):
    """Scores split over the mesh's devices give the histogram of one
    device, and ``binomial_metrics`` reads the split off the array."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from h2o3_tpu.models import metrics
    from h2o3_tpu.parallel.mesh import ROWS, get_mesh
    mesh = get_mesh()
    ndev = mesh.shape[ROWS]
    n = ndev * 1000
    p = rng.random(n).astype(np.float32)
    y = (rng.random(n) < p).astype(np.float32)
    mask = rng.random(n) < 0.9
    rows = NamedSharding(mesh, P(ROWS))
    pd_, yd, md = (jax.device_put(a, rows) for a in (p, y, mask))
    assert metrics._row_shards(pd_) == ndev
    assert metrics._row_shards(jnp.asarray(p)) == 1
    r = jax.device_get(metrics._binomial_pass(pd_, yd, md, shards=ndev))
    tp, fp, s = _bincount64(p, y, mask)
    np.testing.assert_array_equal(r["tp_h"], tp)
    np.testing.assert_array_equal(r["fp_h"], fp)
    np.testing.assert_allclose(r["s_h"], s, rtol=1e-6, atol=0)
    one = binomial_metrics(jnp.asarray(p), jnp.asarray(y), jnp.asarray(mask))
    split = binomial_metrics(pd_, yd, md)
    assert split.auc == one.auc and split.ks == one.ks
    np.testing.assert_array_equal(split.confusion_matrix, one.confusion_matrix)


def test_binomial_metrics_from_the_blocked_histogram(rng):
    """What ``test_auc_matches_sklearn`` and ``test_gains_lift_table`` pin,
    on scores that span several blocks: every metric read off the histogram
    equals the same metric worked from a float64 ``bincount``."""
    n = 7 * 512 + 321
    p = rng.random(n).astype(np.float32)
    y = (rng.random(n) < p).astype(np.float32)
    mask = np.ones(n, bool)
    m = binomial_metrics(jnp.asarray(p), jnp.asarray(y), jnp.asarray(mask))
    tp, fp, s = _bincount64(p, y, mask)
    np.testing.assert_array_equal(m._tp_h, tp)
    np.testing.assert_array_equal(m._fp_h, fp)
    tps, fps = np.cumsum(tp[::-1]), np.cumsum(fp[::-1])
    tpr = np.concatenate([[0.0], tps / tp.sum(), [1.0]])
    fpr = np.concatenate([[0.0], fps / fp.sum(), [1.0]])
    assert m.auc == float(np.trapezoid(tpr, fpr))
    assert m.ks == float(np.max(tps / tp.sum() - fps / fp.sum()))
    assert m.confusion_matrix.sum() == n and m.nobs == n
    from sklearn.metrics import roc_auc_score
    assert abs(m.auc - roc_auc_score(y, p)) < 0.004
    gl = m.gains_lift(groups=16)
    assert gl[-1]["cumulative_data_fraction"] == pytest.approx(1.0, abs=1e-9)
    assert gl[-1]["cumulative_capture_rate"] == pytest.approx(1.0, abs=1e-9)
    assert gl[-1]["cumulative_score"] == pytest.approx(p.mean(), rel=1e-6)


def test_metric_hist_counter_counts_a_trace_once():
    """``h2o3_metric_hist_total{path="matmul"}`` moves where the pass is
    traced: once for a new row count, not at all for a cached program."""
    from h2o3_tpu.models import metrics
    from h2o3_tpu.utils.telemetry import METRIC_HIST
    counter = METRIC_HIST.labels(path="matmul")
    rows = 1237                            # a row count no other test uses
    p = jnp.linspace(0.0, 1.0, rows, dtype=jnp.float32)
    y = (p > 0.5).astype(jnp.float32)
    before = counter.value
    metrics.binomial_metrics(p, y, jnp.ones(rows, bool))
    assert counter.value == before + 1
    metrics.binomial_metrics(p * 0.5, y, jnp.ones(rows, bool))
    assert counter.value == before + 1


def test_auc2_threshold_criteria(rng):
    """AUC2 ThresholdCriterion table (reference hex/AUC2.java:24-36):
    max-F1 from the table must match the sweep, and counts must be
    consistent at every threshold."""
    import numpy as np
    from h2o3_tpu.models.metrics import binomial_metrics
    import jax.numpy as jnp

    n = 2000
    y = (rng.random(n) < 0.4).astype(np.float32)
    p = np.clip(0.6 * y + 0.4 * rng.random(n), 0, 1).astype(np.float32)
    mm = binomial_metrics(jnp.asarray(p), jnp.asarray(y),
                          jnp.ones(n, bool))
    cols, rows = mm.threshold_table()
    assert len(rows) == 400 and cols[0] == "threshold"
    mcols, mrows = mm.max_criteria_and_metric_scores()
    names = [r[0] for r in mrows]
    for crit in ("max f1", "max f2", "max f0point5", "max accuracy",
                 "max absolute_mcc", "max min_per_class_accuracy",
                 "max mean_per_class_accuracy", "max tps", "max tns"):
        assert crit in names
    j = {c: i for i, c in enumerate(cols)}
    P = y.sum()
    N = n - P
    for r in rows[::37]:
        assert abs(r[j["tps"]] + r[j["fns"]] - P) < 1e-6
        assert abs(r[j["fps"]] + r[j["tns"]] - N) < 1e-6
    # max f1 row agrees with a direct sweep over the same histogram grid
    f1_max_tbl = next(r[2] for r in mrows if r[0] == "max f1")
    f1s = [r[j["f1"]] for r in rows]
    assert abs(f1_max_tbl - max(f1s)) < 1e-12


def test_coxph_concordance(rng):
    """Harrell's C (reference CoxPH.java:737) — Fenwick path vs brute force,
    and a discriminating model scores > 0.5."""
    import numpy as np
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.coxph import CoxPH

    n = 250
    x = rng.normal(size=n)
    t = rng.exponential(scale=np.exp(-0.9 * x))
    e = (rng.random(n) < 0.75).astype(np.float32)
    fr = Frame.from_arrays({"x": x.astype(np.float32),
                            "t": t.astype(np.float32), "e": e})
    m = CoxPH(stop_column="t").train(x=["x"], y="e", training_frame=fr)
    c = m.concordance()
    assert 0.6 < c <= 1.0
    lp = m.output["train_lp"]; tt = m.output["train_time"]
    ee = m.output["train_event"]
    conc = disc = tied = 0
    for i in range(n):
        if ee[i] <= 0:
            continue
        for k in range(n):
            if tt[i] < tt[k]:
                if lp[i] > lp[k]:
                    conc += 1
                elif lp[i] < lp[k]:
                    disc += 1
                else:
                    tied += 1
    assert abs(c - (conc + 0.5 * tied) / (conc + disc + tied)) < 1e-9


def test_scoring_history_tree_glm_dl(rng):
    """scoring_history is populated for iterative builders (VERDICT r2 §3:
    reference SharedTree.java:798 doScoringAndSaveModel)."""
    import numpy as np
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import GBM
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.models.kmeans import KMeans

    n = 600
    X = rng.normal(size=(n, 2))
    y = (X[:, 0] - X[:, 1] + 0.5 * rng.normal(size=n) > 0)
    fr = Frame.from_arrays({"a": X[:, 0], "b": X[:, 1],
                            "y": np.array(["n", "p"], dtype=object)[y.astype(int)]})
    m = GBM(ntrees=8, max_depth=3, seed=1).train(y="y", training_frame=fr)
    cols, rows = m.scoring_history
    assert [c[0] for c in cols][:4] == ["timestamp", "duration",
                                        "number_of_trees", "training_deviance"]
    assert len(rows) == 8
    assert rows[0][3] > rows[-1][3]          # deviance decreases

    g = GLM(family="binomial", lambda_=1e-3).train(y="y", training_frame=fr)
    gcols, grows = g.scoring_history
    assert [c[0] for c in gcols][2:] == ["iterations",
                                         "negative_log_likelihood", "objective"]
    assert len(grows) >= 1

    km = KMeans(k=2, seed=1).train(x=["a", "b"], training_frame=fr)
    kcols, krows = km.scoring_history
    assert kcols[-1][0] == "within_cluster_sum_of_squares" and len(krows) >= 1


def test_gbm_early_stopping_fused_semantics(rng):
    """Fused chunked early stopping reproduces per-tree ScoreKeeper
    semantics: stopping triggers, history length == kept trees, and
    retraining with ntrees=K(kept) yields the identical ensemble."""
    import numpy as np
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import GBM

    n = 1500
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] - X[:, 1] > 0)
    tr = Frame.from_arrays({"a": X[:1000, 0], "b": X[:1000, 1], "c": X[:1000, 2],
                            "y": np.array(["n", "p"], dtype=object)[y[:1000].astype(int)]})
    va = Frame.from_arrays({"a": X[1000:, 0], "b": X[1000:, 1], "c": X[1000:, 2],
                            "y": np.array(["n", "p"], dtype=object)[y[1000:].astype(int)]})
    m = GBM(ntrees=150, max_depth=3, seed=5, stopping_rounds=3,
            stopping_tolerance=1e-3).train(y="y", training_frame=tr,
                                           validation_frame=va)
    k = len(m.output["trees"])
    assert k < 150
    assert len(m.scoring_history[1]) == k
    m2 = GBM(ntrees=k, max_depth=3, seed=5).train(y="y", training_frame=tr,
                                                  validation_frame=va)
    import jax
    for t1, t2 in zip(m.output["trees"], m2.output["trees"]):
        np.testing.assert_array_equal(np.asarray(jax.device_get(t1.feat)),
                                      np.asarray(jax.device_get(t2.feat)))
        np.testing.assert_allclose(np.asarray(jax.device_get(t1.leaf)),
                                   np.asarray(jax.device_get(t2.leaf)),
                                   rtol=1e-6)
