"""Uplift DRF — treatment-effect forests + AUUC metrics.

Reference: ``hex/tree/uplift/UpliftDRF.java`` (725 LoC) grows forests whose
splits maximize treatment/control divergence (KL, Euclidean, ChiSquared), and
``hex/AUUC.java`` ranks rows by predicted uplift and accumulates the uplift
curve (qini / lift / gain) over ``auuc_nbins`` thresholds.

TPU-native: trees grow on the shared level-synchronous histogram engine via
the transformed-outcome target Z = Y·T/p − Y·(1−T)/(1−p) (Athey–Imbens), whose
per-leaf mean is an unbiased uplift estimate — this keeps the (G,H,W)
3-channel histogram layout intact, where the reference's divergence gains
require 4 channels. The AUUC computation follows the reference exactly
(threshold bins over ranked uplift, qini default).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.data_info import response_as_float
from h2o3_tpu.models.gbm import SharedTreeBuilder, SharedTreeModel, tree_matrix
from h2o3_tpu.models.job import Job
from h2o3_tpu.models.model_base import make_model_key
from h2o3_tpu.models.tree import TreeParams, grow_trees_batched


class ModelMetricsBinomialUplift:
    """AUUC family (reference: ``hex/ModelMetricsBinomialUplift.java``)."""

    def __init__(self, auuc, qini, auuc_normalized, nbins):
        self.auuc = auuc
        self.qini = qini
        self.auuc_normalized = auuc_normalized
        self.nbins = nbins

    def __repr__(self):
        return (f"ModelMetricsBinomialUplift(auuc={self.auuc:.5f}, "
                f"qini={self.qini:.5f}, norm={self.auuc_normalized:.5f})")


def compute_auuc(uplift_pred, y, treat, mask, nbins: int = 1000):
    """AUUC by ranked-threshold bins (reference ``AUUC.java``: rows sorted by
    predicted uplift, per-bin (n_t, n_c, y_t, y_c) accumulated, qini value
    qini(i) = y_t(i) − y_c(i)·n_t(i)/n_c(i) summed over bins)."""
    u = jnp.where(mask, uplift_pred, -jnp.inf)
    order = jnp.argsort(-u)   # descending predicted uplift
    ys = y[order]
    ts = treat[order]
    ms = mask[order].astype(jnp.float32)
    n = jnp.maximum(ms.sum(), 1.0)

    cum_t = jnp.cumsum(ms * ts)
    cum_c = jnp.cumsum(ms * (1 - ts))
    cum_yt = jnp.cumsum(ms * ts * ys)
    cum_yc = jnp.cumsum(ms * (1 - ts) * ys)

    # qini curve at nbins thresholds
    plen = ys.shape[0]
    idx = jnp.clip((jnp.arange(1, nbins + 1) * n / nbins).astype(jnp.int32) - 1,
                   0, plen - 1)
    nt, nc = cum_t[idx], cum_c[idx]
    yt, yc = cum_yt[idx], cum_yc[idx]
    qini_curve = yt - yc * nt / jnp.maximum(nc, 1.0)
    auuc = qini_curve.sum() / nbins

    # random-targeting baseline: straight line to the final qini value
    final = qini_curve[-1]
    random_auuc = final / 2.0
    qini = auuc - random_auuc
    norm = jnp.where(jnp.abs(final) > 1e-12, auuc / jnp.abs(final), 0.0)
    return (float(jax.device_get(auuc)), float(jax.device_get(qini)),
            float(jax.device_get(norm)))


class UpliftDRFModel(SharedTreeModel):
    algo = "upliftdrf"

    def _contrib_scale_bias(self):
        return 1.0 / max(len(self.output["trees"]), 1), 0.0

    def _score_raw(self, frame: Frame):
        raw = self._tree_raw_sum(frame) / max(len(self.output["trees"]), 1)
        return raw   # predicted uplift per row

    def predict(self, frame: Frame) -> Frame:
        from h2o3_tpu.frame.types import VecType
        from h2o3_tpu.frame.vec import Vec
        u = self._score_raw(frame)
        return Frame(["uplift_predict"],
                     [Vec(u.astype(jnp.float32), VecType.NUM, frame.nrows)])

    def model_performance(self, frame: Frame):
        y, valid = response_as_float(frame.vec(self.response_column))
        t = frame.vec(self.output["treatment_column"]).as_float()
        mask = frame.row_mask() & valid & ~jnp.isnan(t)
        u = self._score_raw(frame)
        nbins = int(self.params.get("auuc_nbins") or -1)
        if nbins <= 0:
            nbins = 1000   # reference AUUC default bin count
        return ModelMetricsBinomialUplift(
            *compute_auuc(u, y, jnp.where(mask, t, 0.0), mask, nbins),
            nbins=nbins)


class UpliftDRF(SharedTreeBuilder):
    """h2o-py surface: ``H2OUpliftRandomForestEstimator``."""

    algo = "upliftdrf"

    @classmethod
    def defaults(cls) -> dict:
        d = super().defaults()
        d.update(treatment_column=None, uplift_metric="KL",
                 auuc_type="qini", auuc_nbins=-1, ntrees=50,
                 mtries=-1, sample_rate=0.632)
        return d

    def _validate(self, frame: Frame, x, y):
        super()._validate(frame, x, y)
        tc = self.params.get("treatment_column")
        if not tc:
            raise ValueError("treatment_column is required")
        tv = frame.vec(tc)
        if not tv.is_categorical or tv.cardinality() != 2:
            raise ValueError("treatment_column must be a 2-level categorical "
                             "(control first level, treatment second)")

    def _fit(self, job: Job, frame: Frame, x, y, weights) -> UpliftDRFModel:
        p = self.params
        tc = p["treatment_column"]
        x = [c for c in x if c != tc]
        yvec = frame.vec(y)
        if not yvec.is_categorical or yvec.cardinality() != 2:
            raise ValueError("uplift response must be a 2-level categorical")
        X, edges, binned, yy, valid, yvec, domains = self._prepare(frame, x, y, weights)
        t = frame.vec(tc).as_float()           # codes 0 (control) / 1 (treatment)
        w = weights * valid * ~jnp.isnan(t)
        t = jnp.where(w > 0, t, 0.0)
        yy = jnp.where(w > 0, yy, 0.0)

        # transformed outcome: E[Z|x] = uplift(x) (propensity from the data)
        pt = float(jax.device_get((w * t).sum() / jnp.maximum(w.sum(), 1e-30)))
        pt = min(max(pt, 1e-6), 1 - 1e-6)
        z = yy * t / pt - yy * (1 - t) / (1 - pt)

        tp = TreeParams(max_depth=int(p["max_depth"]), nbins=self._n_bins,
                        min_rows=float(p["min_rows"]), reg_lambda=0.0,
                        min_split_improvement=float(p["min_split_improvement"]))
        ntrees = int(p["ntrees"])
        seed = int(p.get("seed") or 0) or 23
        key = jax.random.PRNGKey(seed)
        col_rate = 1.0
        if int(p.get("mtries") or -1) > 0:
            col_rate = min(1.0, int(p["mtries"]) / max(len(x), 1))
        trees = []
        batch = 8
        for s in range(0, ntrees, batch):
            k = min(batch, ntrees - s)
            keys = jax.random.split(jax.random.fold_in(key, s), k + 1)
            gs, hs, ws = [], [], []
            for i in range(k):
                wk = self._row_weights(keys[i], w, float(p["sample_rate"]), True)
                gs.append(-wk * z)
                hs.append(wk)
                ws.append(wk)
            grown, _ = grow_trees_batched(
                binned, edges, jnp.stack(gs), jnp.stack(hs), jnp.stack(ws),
                tp, jnp.ones(binned.shape[1], bool), col_rate, keys[-1],
                cat_feats=self._cat_feats, bins_used=self._bins_used)
            trees.extend(grown)
            job.update((s + k) / ntrees, f"{s + k}/{ntrees} trees")

        model = UpliftDRFModel(
            key=make_model_key(self.algo, self.model_id),
            params=self.params, data_info=None, response_column=y,
            response_domain=yvec.domain,
            output=dict(trees=trees, x_cols=list(x), feat_domains=domains,
                        treatment_column=tc, propensity=pt,
                        **self._cat_output()),
        )
        return model

    def _holdout_metrics(self, model, frame, y, w):
        return model.model_performance(frame)