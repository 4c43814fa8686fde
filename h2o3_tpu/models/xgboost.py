"""XGBoost-parity booster.

Reference: ``h2o-extensions/xgboost`` wraps libxgboost (CUDA ``gpu_hist``,
``XGBoostModel.java:396-398``) over a rabit allreduce ring
(``RabitTrackerH2O.java:14``). The TPU replacement (SURVEY.md §2.9) is the same
histogram tree algorithm implemented natively: global-quantile binning,
(g, h) gradient-pair histograms all-reduced over ICI by XLA, exact XGBoost gain
``0.5*(GL²/(HL+λ)+GR²/(HR+λ)−G²/(H+λ))−γ`` with learned default direction for
missing values — which is precisely what :mod:`h2o3_tpu.models.tree` computes.
So "XGBoost" here is the shared tree engine with XGBoost's parameterization
rather than a second engine; rabit's ring allreduce has no user-visible
equivalent to port — XLA emits the collective.

Beyond the shared engine, this builder carries XGBoost's distinguishing
features (reference ``XGBoostModel.XGBoostParameters``):

- ``booster="dart"`` — DART (Rashmi & Gilad-Bachrach 2015): per round a
  random subset of prior trees is DROPPED, the new tree fits the gradients
  of the reduced ensemble, and the dropped + new trees are renormalized
  (``normalize_type`` tree/forest, ``rate_drop``, ``skip_drop``,
  ``one_drop``). Tree weights are baked into leaf values at the end so
  every scoring artifact (raw/MOJO/POJO/SHAP) works unchanged.
- ``col_sample_by_level`` / ``col_sample_by_node`` — the by-node rate
  folds into the per-level rate (per-node sampling would break the
  single-batched-argmax split search; the compromise mirrors LightGBM's
  feature_fraction granularity and is noted in PARITY.md).
- ``offset_column``, ``monotone_constraints``, ``interaction_constraints``,
  categorical ``enum`` group splits — inherited from the shared engine.
- XGBoost-native aliases: eta, max_bin, subsample, colsample_bytree/
  bylevel/bynode, min_child_weight, min_split_loss, reg_lambda/reg_alpha.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.models.gbm import GBM, GBMModel, _grad_hess
from h2o3_tpu.models.job import Job, JobCancelled
from h2o3_tpu.models.model_base import make_model_key
from h2o3_tpu.models.tree import TreeParams, grow_trees_batched


class XGBoostModel(GBMModel):
    algo = "xgboost"


#: h2o-py H2OXGBoostEstimator parameter names → shared-engine names
_ALIASES = {
    "eta": "learn_rate",
    "max_bin": "nbins",
    "subsample": "sample_rate",
    "colsample_bytree": "col_sample_rate_per_tree",
    "colsample_bylevel": "col_sample_rate",
    "colsample_bynode": "col_sample_by_node",
    "min_child_weight": "min_rows",
    "min_split_loss": "gamma",
    "max_delta_step": None,          # accepted, inert (rarely used)
    "grow_policy": None,             # depthwise only (level-synchronous)
    "tree_method": None,             # always hist
    "backend": None,
    "gpu_id": None,
    "dmatrix_type": None,
}


class XGBoost(GBM):
    """h2o-py surface: ``H2OXGBoostEstimator`` (tree_method=hist semantics)."""

    algo = "xgboost"

    @classmethod
    def defaults(cls) -> dict:
        d = super().defaults()
        d.update(
            ntrees=50,
            max_depth=6,
            learn_rate=0.3,        # eta
            reg_lambda=1.0,        # lambda
            reg_alpha=0.0,         # alpha (leaf L1; applied as soft threshold)
            gamma=0.0,             # min_split_loss
            min_rows=1.0,          # min_child_weight
            nbins=256,             # max_bin
            sample_rate=1.0,       # subsample
            col_sample_rate=1.0,   # colsample_bylevel
            col_sample_rate_per_tree=1.0,  # colsample_bytree
            col_sample_by_node=1.0,        # colsample_bynode (folds into level)
            booster="gbtree",      # gbtree | dart | (gblinear → use GLM)
            rate_drop=0.0,         # DART: P(tree is dropped) per round
            skip_drop=0.0,         # DART: P(round skips dropping entirely)
            one_drop=False,        # DART: always drop >= 1 tree
            normalize_type="tree",  # DART: tree | forest
        )
        return d

    def __init__(self, **params):
        for alias, target in _ALIASES.items():
            if alias in params:
                v = params.pop(alias)
                if target is not None:
                    params.setdefault(target, v)
        super().__init__(**params)

    def _effective_col_rate(self) -> float:
        # by-node sampling folds into the per-level rate (see module docs);
        # derived here so stored params keep the user's values
        return (float(self.params["col_sample_rate"])
                * float(self.params.get("col_sample_by_node") or 1.0))

    def supports_auto_recovery(self) -> bool:
        # DART neither checkpoints nor resumes (renormalized prior-tree
        # weights); gbtree shares GBM's chunk snapshots
        return str(self.params.get("booster") or "gbtree").lower() != "dart"

    def validate_request(self) -> None:
        """REST fail-fast: DART cannot resume a checkpoint (per-round
        renormalization rescales prior tree weights, so the ensemble the
        checkpoint froze no longer exists) — the server turns this into a
        structured 400 instead of a background FAILED job."""
        super().validate_request()
        if str(self.params.get("booster") or "").lower() == "dart" \
                and self.params.get("checkpoint"):
            raise ValueError("checkpoint resume is not supported with "
                             "booster='dart' (prior-tree weights would have "
                             "been renormalized away)")

    def _fit(self, job, frame, x, y, weights):
        booster = str(self.params.get("booster") or "gbtree").lower()
        if booster == "gblinear":
            raise ValueError("booster='gblinear' is a linear model — use GLM "
                             "(the reference routes it to a linear booster)")
        if booster not in ("gbtree", "dart"):
            raise ValueError(f"unknown booster {booster!r}")
        if booster == "dart":
            model = self._fit_dart(job, frame, x, y, weights)
        else:
            model = super()._fit(job, frame, x, y, weights)
        model.__class__ = XGBoostModel
        return model

    # -- DART ---------------------------------------------------------------

    def _fit_dart(self, job: Job, frame, x, y, weights):
        """DART boosting: per-round tree dropout + renormalization.

        Rounds run as a host loop (each round re-weights PRIOR trees, which
        a fused scan cannot express); per-round compute (gradient refresh,
        dropped-ensemble margin, one tree growth) stays on device.
        """
        p = self.params
        if p.get("checkpoint"):
            raise ValueError("checkpoint resume is not supported with "
                             "booster='dart' (prior-tree weights would have "
                             "been renormalized away)")
        X, edges, binned, yy, valid, yvec, domains = self._prepare(frame, x, y, weights)
        dist = str(p["distribution"])
        if dist.lower() == "auto":
            dist = "AUTO"
        if yvec.is_categorical:
            if yvec.cardinality() != 2:
                raise ValueError("booster='dart' supports binomial and "
                                 "regression responses here")
            dist = "bernoulli"
        elif dist == "bernoulli":
            raise ValueError("bernoulli distribution requires a categorical "
                             "(2-level) response")
        elif dist == "AUTO":
            dist = "gaussian"
        w = weights * valid
        yc = jnp.where(w > 0, yy, 0.0)

        ybar = float(jax.device_get((w * yc).sum() /
                                    jnp.maximum(w.sum(), 1e-30)))
        if dist == "bernoulli":
            ybar = min(max(ybar, 1e-6), 1 - 1e-6)
            f0 = float(np.log(ybar / (1 - ybar)))
        else:
            f0 = ybar

        lr = float(p["learn_rate"])
        ntrees = int(p["ntrees"])
        nbins = self._n_bins
        seed = int(p["seed"]) if int(p["seed"]) >= 0 else 42
        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(seed)
        tp = TreeParams(
            max_depth=int(p["max_depth"]), nbins=nbins,
            min_rows=float(p["min_rows"]), reg_lambda=float(p["reg_lambda"]),
            reg_alpha=float(p["reg_alpha"]), gamma=float(p["gamma"]),
            min_split_improvement=float(p["min_split_improvement"]))
        mono, reach = self._constraint_arrays(x, frame)
        fmask = jnp.ones(binned.shape[1], bool)

        rate_drop = float(p.get("rate_drop") or 0.0)
        skip_drop = float(p.get("skip_drop") or 0.0)
        one_drop = bool(p.get("one_drop"))
        norm_forest = str(p.get("normalize_type") or "tree") == "forest"
        sample_rate = float(p["sample_rate"])
        col_tree_rate = float(p["col_sample_rate_per_tree"])
        sr = int(p.get("stopping_rounds") or 0)
        metric = str(p.get("stopping_metric") or "AUTO")
        metric = {m.lower(): m for m in self.STOPPING_METRICS}.get(
            metric.lower(), metric)
        tol = float(p.get("stopping_tolerance") or 1e-3)
        best, since = np.inf, 0

        trees, wts, preds = [], [], []   # preds: per-tree [rows] leaf sums
        Fcur = jnp.full(binned.shape[0], f0, jnp.float32)
        oc = p.get("offset_column")
        if oc:
            Fcur = Fcur + jnp.nan_to_num(frame.vec(oc).as_float(), nan=0.0)

        for m in range(ntrees):
            drop = np.zeros(len(trees), bool)
            if trees and rng.random() >= skip_drop:
                drop = rng.random(len(trees)) < rate_drop
                if one_drop and not drop.any():
                    drop[rng.integers(0, len(trees))] = True
            k = int(drop.sum())
            F_drop = 0.0
            if k:
                F_drop = sum(wts[i] * preds[i]
                             for i in range(len(trees)) if drop[i])
            F_eff = Fcur - F_drop
            key, ks, kf, kt = jax.random.split(key, 4)
            wt = w
            if sample_rate < 1.0:       # subsample (per-round row thinning)
                wt = w * (jax.random.uniform(ks, w.shape) < sample_rate)
            tmask = fmask
            if col_tree_rate < 1.0:     # colsample_bytree
                sub = jax.random.uniform(kf, fmask.shape) < col_tree_rate
                sub = sub.at[jax.random.randint(
                    jax.random.fold_in(kf, 1), (), 0, fmask.shape[0])].set(True)
                tmask = jnp.where((fmask & sub).any(), fmask & sub, fmask)
            g, h = _grad_hess(dist, F_eff, yc, wt,
                              float(p["quantile_alpha"]),
                              float(p["huber_alpha"]),
                              float(p["tweedie_power"]))
            new, pred = grow_trees_batched(
                binned, edges, g[None], h[None], wt[None], tp, tmask,
                col_rate=self._effective_col_rate(), key=kt,
                mono=mono, reach=reach, cat_feats=self._cat_feats,
                bins_used=self._bins_used)
            pred = pred[0]
            if k:
                # renormalize (XGBoost DART): tree: new w = lr/(k+lr),
                # dropped *= k/(k+lr); forest: lr/(1+lr) and 1/(1+lr)
                if norm_forest:
                    w_new, scale = lr / (1.0 + lr), 1.0 / (1.0 + lr)
                else:
                    w_new, scale = lr / (k + lr), k / (k + lr)
                for i in range(len(trees)):
                    if drop[i]:
                        wts[i] *= scale
                Fcur = F_eff + scale * F_drop + w_new * pred
            else:
                w_new = lr
                Fcur = Fcur + w_new * pred
            trees.append(new[0])
            wts.append(w_new)
            preds.append(pred)
            try:
                job.update(0.1 + 0.8 * (m + 1) / ntrees,
                           f"DART tree {m + 1}/{ntrees} (dropped {k})")
            except JobCancelled:
                # deadline/cancel between rounds: DART keeps its grown
                # trees like the other tree builders (partial model, job
                # terminates CANCELLED)
                job.keep_partial()
                break
            if sr > 0:                  # ScoreKeeper early stopping
                dev = self._stop_score(metric, dist, Fcur, yc, w, 0)
                if dev < best - tol * abs(best) or not np.isfinite(best):
                    best, since = dev, 0
                else:
                    since += 1
                    if since >= sr:
                        break

        # bake weights into leaves: every downstream scorer (raw/binned/
        # MOJO/POJO/SHAP) then treats the ensemble uniformly with lr=1
        baked = [dataclasses.replace(t, leaf=t.leaf * wt)
                 for t, wt in zip(trees, wts)]

        if dist == "bernoulli":
            pe = jax.nn.sigmoid(Fcur)
            self._last_train_raw = jnp.stack([1 - pe, pe], axis=1)
        else:
            self._last_train_raw = Fcur

        model = XGBoostModel(
            key=make_model_key(self.algo, self.model_id),
            params=self.params, data_info=None, response_column=y,
            response_domain=yvec.domain if yvec.is_categorical else None,
            output=dict(trees=baked, edges=edges, f0=f0, learn_rate=1.0,
                        distribution=dist, x_cols=list(x),
                        feat_domains=domains, ntrees=len(baked),
                        dart_weights=[float(v) for v in wts],
                        **self._cat_output()),
        )
        self._maybe_calibrate(model)
        return model
