"""ModelMetrics — per-problem-type metrics computed device-side in one pass.

Reference: the ``hex/ModelMetrics*.java`` hierarchy computed chunk-parallel via
``MetricBuilder`` reduces; binomial AUC uses a 400-bin streaming histogram of
scores (``hex/AUC2.java:24-36,347-362``) from which ROC, PR, max-F1/F2/MCC
criteria and the confusion matrix are derived; regression metrics in
``ModelMetricsRegression.java``; multinomial in ``ModelMetricsMultinomial.java``.

Here each builder is one jitted reduction over the sharded prediction/response
columns; the 400-bin AUC histogram is kept (it is exactly the right algorithm
for a data-parallel machine — fixed-shape partials, psum-reducible).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from h2o3_tpu.utils.telemetry import METRIC_HIST

NBINS = 400  # reference: AUC2.NBINS=400

#: the score histogram's row blocks (:func:`_bucket_sums`): a power of two
#: that covers the rows in about ``_HIST_STEPS`` steps, between these ends.
#: The short end is what compiles as fast as the scatter-adds it replaced (a
#: v5e compiles a 512-row block in 0.13 s and a 30,000-row one in 0.45 s, and
#: a small frame's first call is all compile); the long end is what runs
#: fastest on many rows (22M rows: 11 ms, 19 ms at 4,096), and keeps a
#: block's bucket to 2**16 products of 8 significant bits: an exact float32
#: sum when they are equal (a tree model's few dozen distinct scores).
_HIST_STEPS = 512
_HIST_BLOCK_MIN, _HIST_BLOCK_MAX = 512, 65_536

# -- containers --------------------------------------------------------------


@dataclasses.dataclass
class MetricsBase:
    nobs: int
    mse: float

    @property
    def rmse(self) -> float:
        return float(np.sqrt(self.mse))


@dataclasses.dataclass
class ModelMetricsRegression(MetricsBase):
    mae: float
    rmsle: float
    mean_residual_deviance: float
    r2: float

    def __repr__(self):
        return (f"ModelMetricsRegression(rmse={self.rmse:.6g}, mse={self.mse:.6g}, "
                f"mae={self.mae:.6g}, deviance={self.mean_residual_deviance:.6g}, r2={self.r2:.4f})")


@dataclasses.dataclass
class ModelMetricsBinomial(MetricsBase):
    auc: float
    pr_auc: float
    logloss: float
    mean_per_class_error: float
    max_f1_threshold: float
    confusion_matrix: np.ndarray  # 2x2 at max-F1 threshold, rows=actual
    ks: float = 0.0               # Kolmogorov-Smirnov (max TPR-FPR)
    gini: float = dataclasses.field(init=False)
    # score histograms retained for gains/lift (not shown in repr)
    _tp_h: np.ndarray | None = dataclasses.field(default=None, repr=False)
    _fp_h: np.ndarray | None = dataclasses.field(default=None, repr=False)
    _s_h: np.ndarray | None = dataclasses.field(default=None, repr=False)

    def gains_lift(self, groups: int = 16):
        """Gains/Lift table rows (reference: ``hex/GainsLift.java`` — the
        TwoDimTable columns ``GainsLift.java:150``). Groups are quantile bins
        of the predicted score, resolved on the 400-bin AUC histogram (the
        reference runs a separate Quantile model; same table up to bin
        granularity)."""
        if self._tp_h is None:
            return []
        tp_h = np.asarray(self._tp_h, np.float64)[::-1]   # descending score
        fp_h = np.asarray(self._fp_h, np.float64)[::-1]
        s_h = np.asarray(self._s_h, np.float64)[::-1]
        n_h = tp_h + fp_h
        N = n_h.sum()
        E = tp_h.sum()
        if N <= 0:
            return []
        P = E / N
        cum_n = np.cumsum(n_h)
        cum_e = np.cumsum(tp_h)
        cum_s = np.cumsum(s_h)
        nb = len(n_h)
        rows = []
        prev_idx = -1
        prev = np.zeros(3)
        for g in range(groups):
            target = N * (g + 1) / groups
            idx = int(np.searchsorted(cum_n, target - 1e-9))
            idx = min(idx, nb - 1)
            if idx <= prev_idx and g < groups - 1:
                continue                      # empty group (coarse histogram)
            idx = nb - 1 if g == groups - 1 else idx
            e_i = cum_e[idx] - prev[0]
            n_i = cum_n[idx] - prev[1]
            s_i = cum_s[idx] - prev[2]
            if n_i <= 0:
                continue
            p_i = e_i / n_i
            lift = p_i / P if P > 0 else np.nan
            cum_lift = cum_e[idx] / cum_n[idx] / P if P > 0 else np.nan
            cum_event = cum_e[idx] / max(E, 1e-30)
            tot_ne = N - E
            cum_non_event = 0.0 if tot_ne == 0 else \
                (cum_n[idx] - cum_e[idx]) / tot_ne
            rows.append(dict(
                group=len(rows) + 1,
                cumulative_data_fraction=cum_n[idx] / N,
                lower_threshold=(nb - 1 - idx) / nb,
                lift=lift,
                cumulative_lift=cum_lift,
                response_rate=p_i,
                score=s_i / n_i,
                cumulative_response_rate=cum_e[idx] / cum_n[idx],
                cumulative_score=cum_s[idx] / cum_n[idx],
                capture_rate=e_i / max(E, 1e-30),
                cumulative_capture_rate=cum_event,
                gain=100 * (lift - 1) if np.isfinite(lift) else np.nan,
                cumulative_gain=100 * (cum_lift - 1) if np.isfinite(cum_lift) else np.nan,
                kolmogorov_smirnov=cum_event - cum_non_event,
            ))
            prev_idx = idx
            prev = np.array([cum_e[idx], cum_n[idx], cum_s[idx]])
        return rows

    def __post_init__(self):
        self.gini = 2.0 * self.auc - 1.0

    #: criteria maximized over thresholds (reference: ``hex/AUC2.java:24-36``
    #: ThresholdCriterion enum; the tns/fns/fps/tps count rows each maximize
    #: the count itself, appended in max_criteria_and_metric_scores)
    MAX_CRITERIA = ("f1", "f2", "f0point5", "accuracy", "precision",
                    "recall", "specificity", "absolute_mcc",
                    "min_per_class_accuracy", "mean_per_class_accuracy")

    def threshold_table(self):
        """Per-threshold criterion values over the 400-bin score histogram
        (reference: ``hex/AUC2.java`` — the ``thresholds_and_metric_scores``
        table h2o-py's ``perf.F1()``/``perf.mcc()`` read). Returns
        (columns, rows) with thresholds descending."""
        if self._tp_h is None:
            return [], []
        tp_h = np.asarray(self._tp_h, np.float64)[::-1]   # descending score
        fp_h = np.asarray(self._fp_h, np.float64)[::-1]
        P, N = tp_h.sum(), fp_h.sum()
        tps = np.cumsum(tp_h)          # predicted-positive counts at ≥ thr
        fps = np.cumsum(fp_h)
        fns, tns = P - tps, N - fps
        nb = len(tp_h)
        thr = (nb - 1 - np.arange(nb)) / nb
        eps = 1e-30
        precision = tps / np.maximum(tps + fps, eps)
        recall = tps / max(P, eps)                      # = tpr
        specificity = tns / max(N, eps)                 # = tnr
        accuracy = (tps + tns) / max(P + N, eps)
        f1 = 2 * precision * recall / np.maximum(precision + recall, eps)
        f2 = 5 * precision * recall / np.maximum(4 * precision + recall, eps)
        f05 = 1.25 * precision * recall / np.maximum(
            0.25 * precision + recall, eps)
        mcc_den = np.sqrt(np.maximum(
            (tps + fps) * (tps + fns) * (tns + fps) * (tns + fns), eps))
        mcc = np.abs((tps * tns - fps * fns) / mcc_den)
        minpca = np.minimum(recall, specificity)
        meanpca = 0.5 * (recall + specificity)
        cols = ["threshold", "f1", "f2", "f0point5", "accuracy", "precision",
                "recall", "specificity", "absolute_mcc",
                "min_per_class_accuracy", "mean_per_class_accuracy",
                "tns", "fns", "fps", "tps", "tnr", "fnr", "fpr", "tpr", "idx"]
        rows = [[float(thr[i]), float(f1[i]), float(f2[i]), float(f05[i]),
                 float(accuracy[i]), float(precision[i]), float(recall[i]),
                 float(specificity[i]), float(mcc[i]), float(minpca[i]),
                 float(meanpca[i]), float(tns[i]), float(fns[i]),
                 float(fps[i]), float(tps[i]), float(specificity[i]),
                 float(fns[i] / max(P, eps)), float(fps[i] / max(N, eps)),
                 float(recall[i]), i]
                for i in range(nb)]
        return cols, rows

    def max_criteria_and_metric_scores(self, table=None):
        """The AUC2 max-criteria table (reference: ``hex/AUC2.java:24-36``;
        h2o-py ``find_threshold_by_max_metric``). Rows:
        (metric, threshold, value, idx). Pass an already-computed
        ``threshold_table()`` result to avoid rebuilding the 400-row sweep."""
        cols, rows = table if table is not None else self.threshold_table()
        if not rows:
            return [], []
        arr = np.asarray([r[:11] for r in rows], np.float64)
        out = []
        for j, name in enumerate(self.MAX_CRITERIA, start=1):
            i = int(np.argmax(arr[:, j]))
            out.append([f"max {name}", float(arr[i, 0]), float(arr[i, j]), i])
        # count criteria report the count at ITS OWN max (reference: tns..tps
        # maximize the count itself)
        for name, col in (("tns", 11), ("fns", 12), ("fps", 13), ("tps", 14)):
            vals = np.asarray([r[col] for r in rows], np.float64)
            i = int(np.argmax(vals))
            out.append([f"max {name}", float(rows[i][0]), float(vals[i]), i])
        return ["metric", "threshold", "value", "idx"], out

    def __repr__(self):
        return (f"ModelMetricsBinomial(auc={self.auc:.5f}, pr_auc={self.pr_auc:.5f}, "
                f"logloss={self.logloss:.5f}, rmse={self.rmse:.5f}, "
                f"mean_per_class_error={self.mean_per_class_error:.5f})")


@dataclasses.dataclass
class ModelMetricsMultinomial(MetricsBase):
    logloss: float
    mean_per_class_error: float
    confusion_matrix: np.ndarray

    @property
    def accuracy(self) -> float:
        cm = self.confusion_matrix
        return float(np.trace(cm) / max(cm.sum(), 1))

    def __repr__(self):
        return (f"ModelMetricsMultinomial(logloss={self.logloss:.5f}, "
                f"mean_per_class_error={self.mean_per_class_error:.5f}, "
                f"accuracy={self.accuracy:.4f})")


# -- regression ---------------------------------------------------------------


@jax.jit
def _regression_pass(pred, y, mask, dev):
    w = mask.astype(jnp.float32)
    n = w.sum()
    err = jnp.where(mask, pred - y, 0.0)
    mse = (err * err).sum() / n
    mae = jnp.abs(err).sum() / n
    both_pos = mask & (pred > -1) & (y > -1)
    le = jnp.where(both_pos, jnp.log1p(jnp.maximum(pred, -1 + 1e-10)) - jnp.log1p(y), 0.0)
    rmsle = jnp.sqrt((le * le).sum() / n)
    ymean = jnp.where(mask, y, 0.0).sum() / n
    ss_tot = jnp.where(mask, (y - ymean) ** 2, 0.0).sum()
    r2 = 1.0 - (err * err).sum() / jnp.maximum(ss_tot, 1e-30)
    mrd = jnp.where(mask, dev, 0.0).sum() / n
    return dict(n=n, mse=mse, mae=mae, rmsle=rmsle, r2=r2, mrd=mrd)


def regression_metrics(pred: jax.Array, y: jax.Array, mask: jax.Array,
                       family=None) -> ModelMetricsRegression:
    from h2o3_tpu.models.distributions import get_family
    fam = family or get_family("gaussian")
    dev = fam.deviance(y, jnp.maximum(pred, 1e-10) if fam.name != "gaussian" else pred)
    r = jax.device_get(_regression_pass(pred, y, mask, dev))
    return ModelMetricsRegression(
        nobs=int(r["n"]), mse=float(r["mse"]), mae=float(r["mae"]),
        rmsle=float(r["rmsle"]), mean_residual_deviance=float(r["mrd"]), r2=float(r["r2"]))


# -- binomial -----------------------------------------------------------------


def _row_shards(arr) -> int:
    """Into how many pieces the devices split ``arr``'s rows (1 on one device
    or replicated), read off its sharding OUTSIDE jit."""
    sharding = getattr(arr, "sharding", None)
    if sharding is None or not arr.shape[0]:
        return 1
    return arr.shape[0] // sharding.shard_shape(arr.shape)[0]


def _hist_block(rows: int) -> int:
    """Rows a step of the score histogram contracts, for ``rows`` in all."""
    covering = 1 << (-(-rows // _HIST_STEPS) - 1).bit_length()
    return min(rows, max(_HIST_BLOCK_MIN, min(covering, _HIST_BLOCK_MAX)))


def _bf16_digits(x):
    """``x`` (float32) as three bfloat16 digits that add back to it exactly:
    8 + 8 + 8 significant bits. ``lax.reduce_precision``, because XLA's TPU
    pipeline removes an ``astype(bfloat16).astype(float32)`` pair and the
    lower digits with it."""
    def round8(v):
        return lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    hi = round8(x)
    mid = round8(x - hi)
    return [d.astype(jnp.bfloat16) for d in (hi, mid, round8(x - hi - mid))]


def _bucket_sums(p, y, mask, nbins: int, shards: int = 1):
    """Per score bucket, over the unmasked rows: the counts of ``y`` and
    ``1 - y`` (``[nbins, 2]`` int32) and the sum of ``p`` (``[nbins]``
    float32): the AUC2 histogram as a matrix product,
    ``onehot(bucket)ᵀ · [y, 1 - y, p]``, which the MXU streams where a
    scatter-add pays 8.7 ns a row and statistic on a v5e.

    Blocks of :func:`_hist_block` rows, one ``[blk, nbins]ᵀ x [blk, 5]``
    product each, so only a block's one-hot ever exists (XLA makes it inside the
    product's own fusion); the last block is the array's last ``blk`` rows
    with the rows an earlier block counted masked out, so nothing is padded
    or copied. Precision: the one-hot and the 0/1 class indicators are exact
    in bfloat16, and ``p`` goes in as three bfloat16 digits
    (:func:`_bf16_digits`), so ONE bfloat16 pass gives float32 sums: every
    product is exact, the MXU accumulates in float32. (float32 operands at
    ``Precision.HIGHEST`` would split the one-hot into digits too: six
    passes for the same numbers.) Blocks' counts add in int32, exact past
    the 2**24 rows at which a float32 scatter-add stalls; their score sums
    add in float32, digit by digit. A masked row adds zeros by ``where``:
    its score may be NaN.

    ``shards`` > 1: ``p``'s rows are split that many ways over devices; each
    piece is summed where it lives (``vmap`` over the pieces, which implicit
    SPMD partitions along them) and the pieces' sums added once."""
    if shards > 1:
        pieces = [x.reshape(shards, -1) for x in (p, y, mask)]
        counts, sums = jax.vmap(lambda *a: _bucket_sums(*a, nbins))(*pieces)
        return counts.sum(0), sums.sum(0)
    rows = p.shape[0]
    blk = _hist_block(rows)

    def step(i, acc):
        start = jnp.minimum(i * blk, rows - blk)
        pb, yb, mb = (lax.dynamic_slice_in_dim(x, start, blk)
                      for x in (p, y, mask))
        mb &= start + lax.iota(jnp.int32, blk) >= i * blk
        bins = jnp.clip((pb * nbins).astype(jnp.int32), 0, nbins - 1)
        stats = jnp.stack(
            [jnp.where(mb, yb, 0.0).astype(jnp.bfloat16),
             jnp.where(mb, 1.0 - yb, 0.0).astype(jnp.bfloat16),
             *_bf16_digits(jnp.where(mb, pb, 0.0))], axis=1)
        part = lax.dot_general(
            jax.nn.one_hot(bins, nbins, dtype=jnp.bfloat16), stats,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return acc[0] + part[:, :2].astype(jnp.int32), acc[1] + part[:, 2:]

    counts, sums = lax.fori_loop(
        0, -(-rows // max(blk, 1)), step,
        (jnp.zeros((nbins, 2), jnp.int32), jnp.zeros((nbins, 3), jnp.float32)))
    return counts, sums.sum(1)


@partial(jax.jit, static_argnames=("nbins", "shards"))
def _binomial_pass(p, y, mask, nbins=NBINS, shards=1):
    """One fused pass: 400-bin score histogram (AUC2 semantics) + logloss + MSE."""
    METRIC_HIST.labels(path="matmul").inc()
    w = mask.astype(jnp.float32)
    n = w.sum()
    pc = jnp.clip(p, 1e-7, 1 - 1e-7)
    logloss = -(w * (y * jnp.log(pc) + (1 - y) * jnp.log1p(-pc))).sum() / n
    err = jnp.where(mask, p - y, 0.0)
    mse = (err * err).sum() / n
    counts, s_h = _bucket_sums(p, y, mask, nbins, shards)
    return dict(n=n, logloss=logloss, mse=mse, tp_h=counts[:, 0],
                fp_h=counts[:, 1], s_h=s_h)


def binomial_metrics(p: jax.Array, y: jax.Array, mask: jax.Array) -> ModelMetricsBinomial:
    r = jax.device_get(_binomial_pass(p, y, mask, shards=_row_shards(p)))
    tp_h, fp_h = np.asarray(r["tp_h"], np.float64), np.asarray(r["fp_h"], np.float64)
    P, N = tp_h.sum(), fp_h.sum()
    # descending threshold sweep: cumulative TP/FP from the top bin down
    tps = np.cumsum(tp_h[::-1])[::-1]   # tps[b] = positives with score >= bin b
    fps = np.cumsum(fp_h[::-1])[::-1]
    # tps/fps are monotone non-increasing in b, so the descending-b sweep IS
    # the ROC polyline (both coordinates non-decreasing) — no re-sorting.
    # Sorting by fpr alone is wrong: stable ties put high-tpr points first,
    # ending each vertical ROC segment at its BOTTOM (a two-valued score
    # distribution then reads as auc=0.5 despite perfect separation).
    tpr_pts = np.concatenate([[0.0], (tps / max(P, 1e-30))[::-1], [1.0]])
    fpr_pts = np.concatenate([[0.0], (fps / max(N, 1e-30))[::-1], [1.0]])
    auc = float(np.trapezoid(tpr_pts, fpr_pts))
    # PR curve — same descending-b traversal (recall non-decreasing)
    prec = tps / np.maximum(tps + fps, 1e-30)
    rec = tps / max(P, 1e-30)
    pr_auc = float(np.trapezoid(prec[::-1], rec[::-1]))
    # max-F1 threshold + confusion matrix (reference AUC2.ThresholdCriterion.f1)
    f1 = 2 * prec * rec / np.maximum(prec + rec, 1e-30)
    b = int(np.argmax(f1))
    thr = b / NBINS
    tp, fp = tps[b], fps[b]
    fn, tn = P - tp, N - fp
    cm = np.array([[tn, fp], [fn, tp]])
    mpce = 0.5 * (fp / max(N, 1e-30) + fn / max(P, 1e-30))
    ks = float(np.max(tps / max(P, 1e-30) - fps / max(N, 1e-30)))
    return ModelMetricsBinomial(
        nobs=int(r["n"]), mse=float(r["mse"]), auc=auc, pr_auc=pr_auc,
        logloss=float(r["logloss"]), mean_per_class_error=float(mpce),
        max_f1_threshold=float(thr), confusion_matrix=cm, ks=ks,
        _tp_h=tp_h, _fp_h=fp_h, _s_h=np.asarray(r["s_h"], np.float64))


# -- multinomial --------------------------------------------------------------


@partial(jax.jit, static_argnames=("nclass",))
def _multinomial_pass(probs, y, mask, nclass):
    w = mask.astype(jnp.float32)
    n = w.sum()
    yi = jnp.where(mask, y.astype(jnp.int32), 0)
    p_true = jnp.clip(jnp.take_along_axis(probs, yi[:, None], axis=1)[:, 0], 1e-15, 1.0)
    logloss = -(w * jnp.log(p_true)).sum() / n
    mse = (w * (1.0 - p_true) ** 2).sum() / n
    pred = jnp.argmax(probs, axis=1)
    idx = jnp.where(mask, yi * nclass + pred, 0)
    cm = jax.ops.segment_sum(w, idx, num_segments=nclass * nclass).reshape(nclass, nclass)
    return dict(n=n, logloss=logloss, mse=mse, cm=cm)


def multinomial_metrics(probs: jax.Array, y: jax.Array, mask: jax.Array,
                        nclass: int) -> ModelMetricsMultinomial:
    r = jax.device_get(_multinomial_pass(probs, y, mask, nclass))
    cm = np.asarray(r["cm"], np.float64)
    row = cm.sum(axis=1)
    per_class_err = 1.0 - np.diag(cm) / np.maximum(row, 1e-30)
    mpce = float(per_class_err[row > 0].mean()) if (row > 0).any() else 0.0
    return ModelMetricsMultinomial(
        nobs=int(r["n"]), mse=float(r["mse"]), logloss=float(r["logloss"]),
        mean_per_class_error=mpce, confusion_matrix=cm)
