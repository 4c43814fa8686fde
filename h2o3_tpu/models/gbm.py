"""GBM and DRF — gradient boosting and random forest on the shared tree engine.

Reference: ``hex/tree/gbm/GBM.java`` (driver loop ``scoreAndBuildTrees``,
``SharedTree.java:481,519``), ``hex/tree/drf/DRF.java``. GBM grows one tree per
iteration on the gradient of the loss at the current prediction; DRF grows
independent trees on bootstrap resamples with per-tree feature subsampling and
averages. Distribution semantics follow ``hex/DistributionFactory`` (bernoulli
log-odds F, gaussian residuals, poisson log-link).

TPU-native notes: bootstrap resampling is Poisson(1) *weighting* (identical in
expectation, static shapes — no row gather); per-split column sampling of the
reference becomes per-tree feature masks; binning is global-quantile
(XGBoost-hist style) rather than the reference's per-node adaptive histograms
— same family of estimator, better fit for fixed-shape compilation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.data_info import _remap_codes
from h2o3_tpu.models.job import Job, JobCancelled
from h2o3_tpu.models.model_base import (Model, ModelBuilder, make_model_key,
                                        publish_dispatch_audit)
from h2o3_tpu.utils import telemetry as _tm
from h2o3_tpu.utils.costs import accounted_jit
from h2o3_tpu.utils.timeline import timed_event
from jax import lax

from h2o3_tpu.models.tree import (Tree, _grow_tree_device, _walk_binned,
                                  fold_binned, predict_binned, predict_raw)
from h2o3_tpu.ops.quantile import (bin_column, bin_features, bin_levels,
                                    cat_bins_for_codes, compute_bin_edges)


def tree_matrix(frame: Frame, cols: list[str], domains: dict[str, tuple]) -> jax.Array:
    """[plen, F] raw feature matrix with train-domain-adapted cat codes."""
    arrs = []
    for c in cols:
        v = frame.vec(c)
        if v.is_categorical and domains.get(c) and v.domain != domains[c]:
            codes = _remap_codes(v.data, v.domain or (), domains[c])
            arrs.append(jnp.where(codes < 0, jnp.nan, codes.astype(jnp.float32)))
        else:
            arrs.append(v.as_float())
    return jnp.stack(arrs, axis=1)


def _weighted_quantile_host(y, w, prob: float) -> float:
    """Weighted quantile of y over rows with w>0 (host-side, init only)."""
    yh = np.asarray(jax.device_get(y), np.float64)
    wh = np.asarray(jax.device_get(w), np.float64)
    ok = wh > 0
    if not ok.any():
        return 0.0
    order = np.argsort(yh[ok])
    ys, ws = yh[ok][order], wh[ok][order]
    cw = np.cumsum(ws)
    idx = int(np.searchsorted(cw, prob * cw[-1]))
    return float(ys[min(idx, len(ys) - 1)])


@partial(jax.jit, static_argnames=("dist", "custom_id"))
def _grad_hess(dist: str, F, y, w, quantile_alpha: float = 0.5,
               huber_alpha: float = 0.9, tweedie_power: float = 1.5,
               custom_id: int = -1):
    """Per-distribution (g, h) pairs (reference: hex/Distribution.java loss
    families; non-smooth losses use the standard GBM pseudo-residual with
    unit hessian, leaf value = weighted mean pseudo-residual)."""
    if dist == "custom":
        # user-uploaded CDistributionFunc (water/udf/CDistributionFunc.java):
        # host callback once per boosting iteration on full columns; the
        # scan stays one compiled program around it
        from h2o3_tpu.utils import udf
        shp = jax.ShapeDtypeStruct(F.shape, jnp.float32)
        return jax.pure_callback(udf.grad_hess_host(custom_id), (shp, shp),
                                 F, y, w)
    if dist == "bernoulli":
        p = jax.nn.sigmoid(F)
        return w * (p - y), w * jnp.maximum(p * (1 - p), 1e-10)
    if dist == "poisson":
        mu = jnp.exp(jnp.clip(F, -30, 30))
        return w * (mu - y), w * mu
    if dist == "gamma":
        # log link; deviance grad: 1 - y*exp(-F)
        ey = y * jnp.exp(jnp.clip(-F, -30, 30))
        return w * (1.0 - ey), w * ey
    if dist == "tweedie":
        p_ = tweedie_power
        e1 = jnp.exp(jnp.clip((1.0 - p_) * F, -30, 30))
        e2 = jnp.exp(jnp.clip((2.0 - p_) * F, -30, 30))
        g = w * (-y * e1 + e2)
        h = w * (-(1.0 - p_) * y * e1 + (2.0 - p_) * e2)
        return g, jnp.maximum(h, 1e-10)
    if dist == "laplace":
        return w * jnp.sign(F - y), w
    if dist == "quantile":
        a = quantile_alpha
        return w * jnp.where(y > F, -a, 1.0 - a), w
    if dist == "huber":
        # reference: delta = huber_alpha quantile of |residual|, refreshed
        # every iteration (DistributionFactory huber). Weighted quantile so
        # zero-weight rows (shard padding, excluded rows) cannot bias delta.
        r = F - y
        ar = jnp.abs(r)
        order = jnp.argsort(ar)
        cw = jnp.cumsum(w[order])
        tgt = huber_alpha * jnp.maximum(cw[-1], 1e-30)
        idx = jnp.clip(jnp.searchsorted(cw, tgt), 0, ar.shape[0] - 1)
        delta = ar[order][idx]
        return w * jnp.clip(r, -delta, delta), w
    return w * (F - y), w  # gaussian


def _linkinv_device(link: str, f):
    """Inverse link on device for custom distributions (reference
    ``LinkFunction*.java`` families; names per CDistributionFunc.link())."""
    if link == "log":
        return jnp.exp(jnp.clip(f, -30, 30))
    if link == "logit":
        return jax.nn.sigmoid(f)
    if link == "inverse":
        return 1.0 / jnp.where(jnp.abs(f) < 1e-30, 1e-30, f)
    return f


def _metric_device(metric: str, dist: str, F, y, w, nclass: int,
                   custom_link: str | None = None):
    """Stopping/score metric as traced device code (less-is-better; AUC is
    negated), so the fused scan can emit one scalar per tree with zero host
    round-trips (reference: ``ScoreKeeper`` scores between driver
    iterations). ``dist="drf_prob"`` means F already IS the prediction
    (probability / mean), the DRF averaging semantics."""
    n = jnp.maximum(w.sum(), 1e-30)
    if nclass > 1:
        prob = F if dist == "drf_prob" else jax.nn.softmax(F, axis=1)
        prob = jnp.clip(prob, 1e-15, 1.0)
        if metric in ("AUTO", "deviance", "logloss"):
            picked = jnp.take_along_axis(
                jnp.log(prob), y.astype(jnp.int32)[:, None], 1)[:, 0]
            return -(w * picked).sum() / n
        if metric in ("MSE", "RMSE"):
            ptrue = jnp.take_along_axis(prob, y.astype(jnp.int32)[:, None],
                                        1)[:, 0]
            mse = (w * (1.0 - ptrue) ** 2).sum() / n
            return jnp.sqrt(mse) if metric == "RMSE" else mse
        if metric == "misclassification":
            pred = jnp.argmax(prob, axis=1).astype(jnp.float32)
            return (w * (pred != y)).sum() / n
        raise ValueError(f"unsupported multinomial stopping_metric {metric!r}")
    if dist == "bernoulli":
        prob = jax.nn.sigmoid(F)
    elif dist == "drf_prob":
        prob = jnp.clip(F, 0.0, 1.0)
    elif dist in ("poisson", "gamma", "tweedie"):
        prob = None
        mu = jnp.exp(jnp.clip(F, -30, 30))
    elif dist == "custom":
        # score in RESPONSE space, not link space (review r3 finding)
        prob = None
        mu = _linkinv_device(custom_link or "identity", F)
    else:
        prob = None
        mu = F
    if metric in ("AUTO", "deviance", "logloss"):
        if prob is not None:         # bernoulli margins or DRF probabilities
            pc = jnp.clip(prob, 1e-7, 1 - 1e-7)
            return -(w * (y * jnp.log(pc) +
                          (1 - y) * jnp.log1p(-pc))).sum() / n
        if dist in ("poisson", "gamma", "tweedie"):
            return (w * (mu - y * jnp.clip(F, -30, 30))).sum() / n
        return (w * (mu - y) ** 2).sum() / n
    if metric in ("MSE", "RMSE"):
        err = ((prob - y) ** 2 if prob is not None else (mu - y) ** 2)
        mse = (w * err).sum() / n
        return jnp.sqrt(mse) if metric == "RMSE" else mse
    if metric == "misclassification":
        pred = (prob > 0.5).astype(jnp.float32)
        return (w * (pred != y)).sum() / n
    if metric == "AUC":
        # weighted Mann-Whitney with EXACT tie handling (reference
        # ScoreKeeper scores tied predictions at half credit): positives in
        # a tie group earn cumneg-before-group + half the group's negative
        # weight. Negated so the stopping comparison stays less-is-better.
        order = jnp.argsort(prob)
        s = prob[order]
        ys, ws = y[order], w[order]
        negw = ws * (1.0 - ys)
        cumneg = jnp.cumsum(negw)
        lo = jnp.searchsorted(s, s, side="left")
        hi = jnp.searchsorted(s, s, side="right") - 1
        before = jnp.where(lo > 0, cumneg[jnp.maximum(lo - 1, 0)], 0.0)
        credit = before + 0.5 * (cumneg[hi] - before)
        posw = ws * ys
        tot = jnp.maximum(posw.sum() * negw.sum(), 1e-30)
        return -(posw * credit).sum() / tot
    raise ValueError(f"unsupported stopping_metric {metric!r}")


def _traverse_heap_device(binned_v, heap, n_bins: int, has_mask: bool):
    """Leaf values of ONE freshly grown tree for held-out rows, straight from
    the device heap channels (feat, thresh_bin, thresh_val, na_left,
    is_split, leaf, gain, cover[, left_mask]) — lets the fused scan carry
    validation margins without leaving the device."""
    split = {"left_mask": heap[8]} if has_mask else {"thresh_bin": heap[1]}
    idx = _walk_binned(binned_v, heap[0], heap[3], heap[4], n_bins, **split)
    return heap[5][idx]


@jax.jit
def _grad_hess_multinomial(F, y, w):
    """Softmax gradients for all K classes at once (reference: GBM.java
    multinomial pseudo-residuals). F: [rows, K]; y: int class ids."""
    p = jax.nn.softmax(F, axis=1)
    yoh = jax.nn.one_hot(y.astype(jnp.int32), F.shape[1], dtype=F.dtype)
    return w[:, None] * (p - yoh), w[:, None] * jnp.maximum(p * (1 - p), 1e-10)


def _pack_hp(col_rate, sample_rate, col_tree_rate, min_rows, reg_lambda,
             reg_alpha, gamma, min_split_improvement, lr,
             quantile_alpha=0.5, huber_alpha=0.9, tweedie_power=1.5):
    """The ``_boost_scan_jit`` hp-vector layout — the ONE place the slot
    order lives (the dryrun audit in ``__graft_entry__`` packs with this
    too, so it can never silently audit a differently-wired program)."""
    return jnp.asarray([col_rate, sample_rate, col_tree_rate, min_rows,
                        reg_lambda, reg_alpha, gamma, min_split_improvement,
                        lr, quantile_alpha, huber_alpha, tweedie_power],
                       jnp.float32)


def _boost_scan(binned, edges, yc, w, fmask_base, Fcur0, keys, *,
                dist: str, depth: int, n_bins: int, col_rate: float,
                sample_rate: float, col_tree_rate: float, min_rows: float,
                reg_lambda: float, reg_alpha: float, gamma: float,
                min_split_improvement: float, lr: float,
                bootstrap: bool, drf: bool, nclass: int,
                quantile_alpha: float = 0.5, huber_alpha: float = 0.9,
                tweedie_power: float = 1.5, mono=None, reach=None,
                cat_feats=None, track: str | None = None, val=None,
                ntrees_prior: int = 0, custom_id: int = -1,
                custom_link: str | None = None, bins_used=None):
    """The WHOLE boosting/bagging run in one compiled program.

    Reference: ``SharedTree.scoreAndBuildTrees`` loops trees on the driver
    node, publishing to DKV per iteration. Here the loop is a ``lax.scan``
    whose body is gradient refresh + row/feature sampling + one fused tree
    growth, so the ensemble trains in ONE device dispatch per chunk with no
    host-visible op between trees.

    Hyperparameter floats (lr, rates, regularization) are packed into ONE
    traced f32 vector, NOT static jit args: AutoML's random grids vary them
    per model, and as compile-time constants every config would pay a fresh
    XLA compile. One packed vector costs one host→device upload per
    *model*; every config of one shape shares the compiled program. Only
    shape/control-flow params (dist, depth, bins, sampling on/off) remain
    static.

    ``keys``: [M, 3, 2] per-remaining-tree PRNG keys (precomputed from the
    base seed so checkpoint resume replays the same per-tree randomness).
    ``nclass`` > 1 grows one tree per class per round (multinomial), vmapped.
    Returns stacked heap arrays [M(, K), heap] + final margins Fcur.
    """
    hp = _pack_hp(col_rate, sample_rate, col_tree_rate, min_rows,
                  reg_lambda, reg_alpha, gamma, min_split_improvement,
                  lr, quantile_alpha, huber_alpha, tweedie_power)
    from h2o3_tpu.models.tree import hist_mesh
    return _boost_scan_jit(
        binned, edges, yc, w, fmask_base, Fcur0, keys, hp,
        dist=dist, depth=depth, n_bins=n_bins, bootstrap=bootstrap, drf=drf,
        nclass=nclass,
        do_row_sample=bool(sample_rate < 1.0),
        do_tree_col_sample=bool(col_tree_rate < 1.0),
        do_col_sample=bool(col_rate < 1.0),
        mono=mono, reach=reach, cat_feats=cat_feats, track=track, val=val,
        ntrees_prior=ntrees_prior, custom_id=custom_id,
        custom_link=custom_link, mesh=hist_mesh(binned), bins_used=bins_used)


# the boosting chunk's host-dispatched program — registered with the
# compute observatory (utils/costs.py): each (rows, K, depth, mesh)
# signature's compile wall time and cost_analysis FLOPs/bytes show in
# /3/Compute, and a shape-changed rebuild records a recompile event
@accounted_jit("gbm:boost_scan", loop="gbm_chunk",
               static_argnames=("dist", "depth", "n_bins", "bootstrap",
                                "drf", "nclass", "do_row_sample",
                                "do_tree_col_sample", "do_col_sample",
                                "track", "ntrees_prior", "custom_id",
                                "custom_link", "mesh", "bins_used"))
def _boost_scan_jit(binned, edges, yc, w, fmask_base, Fcur0, keys, hp, *,
                    dist: str, depth: int, n_bins: int, bootstrap: bool,
                    drf: bool, nclass: int, do_row_sample: bool,
                    do_tree_col_sample: bool, do_col_sample: bool,
                    mono=None, reach=None, cat_feats=None,
                    track: str | None = None, val=None,
                    ntrees_prior: int = 0, custom_id: int = -1,
                    custom_link: str | None = None, mesh=None,
                    bins_used=None):
    (col_rate, sample_rate, col_tree_rate, min_rows, reg_lambda, reg_alpha,
     gamma, min_split_improvement, lr, quantile_alpha, huber_alpha,
     tweedie_power) = hp
    F = binned.shape[1]
    binned_T = binned.T   # hoisted once by XLA; the Pallas kernel wants [F, R]

    def sample_w(k1):
        if bootstrap:
            return w * jax.random.poisson(k1, sample_rate, w.shape).astype(jnp.float32)
        if do_row_sample:
            return w * (jax.random.uniform(k1, w.shape) < sample_rate)
        return w

    def sample_fmask(k2):
        if not do_tree_col_sample:
            return fmask_base
        ku, kf = jax.random.split(k2)
        # force a guaranteed feature BEFORE intersecting with the base mask
        # so the sample can never re-enable a feature the base mask bans
        sub = jax.random.uniform(ku, (F,)) < col_tree_rate
        sub = sub.at[jax.random.randint(kf, (), 0, F)].set(True)
        m = fmask_base & sub
        return jnp.where(m.any(), m, fmask_base)

    def grow(g, h, wt, fmask, k3):
        return _grow_tree_device(
            binned, binned_T, edges, g, h, wt, fmask, k3, depth, n_bins,
            min_rows, reg_lambda, reg_alpha, gamma, min_split_improvement,
            col_rate, do_col_sample=do_col_sample,
            mono=mono, reach=reach, cat_feats=cat_feats, mesh=mesh,
            bins_used=bins_used)

    # -- optional per-tree metric tracking (fused ScoreKeeper) ---------------
    # `track` emits one train-metric scalar per tree from the carried
    # margins; `val` additionally carries held-out margins, traversing each
    # fresh tree on the validation bins inside the scan — scoring history and
    # early stopping then cost ZERO extra dispatches. DRF carries the running
    # SUM of tree predictions; its metric divides by the tree count.
    track_dist = "drf_prob" if drf else dist
    has_mask = cat_feats is not None
    M_prior = float(ntrees_prior)

    def scores(i, Ft, Fv):
        outs = []
        if drf:
            denom = jnp.maximum(i + 1.0 + M_prior, 1.0)
            Ft = Ft / denom
            Fv = None if Fv is None else Fv / denom
        if track is not None:
            outs.append(_metric_device(track, track_dist, Ft, yc, w, nclass,
                                       custom_link))
        if Fv is not None:
            vb, yv, wv, _ = val
            outs.append(_metric_device(track or "AUTO", track_dist, Fv, yv,
                                       wv, nclass, custom_link))
        return tuple(outs)

    def update_val(Fval, heap):
        if val is None:
            return None
        vb = val[0]
        if nclass <= 1:
            step = _traverse_heap_device(vb, heap, n_bins, has_mask)
            return Fval + (step if drf else lr * step)
        step = jnp.stack(
            [_traverse_heap_device(vb, [h[k] for h in heap], n_bins, has_mask)
             for k in range(nclass)], axis=1)
        return Fval + (step if drf else lr * step)

    def update(i, Fcur, Fval, heap, row_leaf):
        """The round's tail: the margin update and the tracked metric."""
        with jax.named_scope("update"):
            Fnew = Fcur + (row_leaf if drf else lr * row_leaf)
            Fval = update_val(Fval, heap)
            return (Fnew, Fval), (heap, *scores(i, Fnew, Fval))

    # the round's parts are named (sample, grad, level<d>/..., update) by
    # jax.named_scope: metadata for a profile's op_name, no operation
    if nclass <= 1:
        def body(carry, xs):
            ks, i = xs
            Fcur, Fval = carry
            with jax.named_scope("sample"):
                wt = sample_w(ks[0])
                fmask = sample_fmask(ks[1])
            with jax.named_scope("grad"):
                if drf:
                    g, h = -yc * wt, wt      # leaf = weighted in-node mean
                else:
                    g, h = _grad_hess(dist, Fcur, yc, wt, quantile_alpha,
                                      huber_alpha, tweedie_power, custom_id)
            out = grow(g, h, wt, fmask, ks[2])
            heap, row_leaf = out[:-1], out[-1]
            return update(i, Fcur, Fval, heap, row_leaf)
    else:
        yoh = jax.nn.one_hot(yc.astype(jnp.int32), nclass)

        def body(carry, xs):
            ks, i = xs
            Fcur, Fval = carry
            with jax.named_scope("sample"):
                wt = sample_w(ks[0])
                fmask = sample_fmask(ks[1])
                kk = jax.random.split(ks[2], nclass)
            with jax.named_scope("grad"):
                if drf:
                    G = -(yoh * wt[:, None])
                    H = jnp.broadcast_to(wt[:, None], G.shape)
                else:
                    G, H = _grad_hess_multinomial(Fcur, yc, wt)
            outs = jax.vmap(lambda gk, hk, k: grow(gk, hk, wt, fmask, k))(
                G.T, H.T, kk)
            heap, row_leaf = outs[:-1], outs[-1]       # row_leaf: [K, R]
            return update(i, Fcur, Fval, heap, row_leaf.T)

    Fval0 = val[3] if val is not None else None
    idx = jnp.arange(keys.shape[0], dtype=jnp.float32)
    (Fend, Fvend), ys = lax.scan(body, (Fcur0, Fval0), (keys, idx))
    heap = ys[0]
    extras = ys[1:]      # (tscore[, vscore]) per-tree metric arrays
    return Fend, heap, extras, Fvend


def _trees_from_stacked(heap, m: int, k: int | None = None) -> Tree:
    """Tree m (class k) from _boost_scan's stacked heap arrays.

    ``heap`` should be host-side (see ``_heap_to_host``): slicing device
    arrays per tree would cost a dispatch each — hundreds per model."""
    pick = (lambda a: a[m] if k is None else a[m][k])
    vals = [pick(a) for a in heap]
    hf, ht, htv, hna, hsp, hlf, hg, hc = vals[:8]
    hm = vals[8] if len(vals) > 8 else None   # group-split membership masks
    return Tree(feat=hf, thresh_bin=ht, thresh_val=htv, na_left=hna,
                is_split=hsp, leaf=hlf, gain=hg, cover=hc, left_mask=hm)


def _heap_to_host(heap):
    """ONE batched transfer for the whole stacked ensemble (the heap arrays
    are tiny: ntrees x 2^(depth+1) nodes; per-leaf device_get would pay one
    transfer PER CHANNEL)."""
    return jax.tree.map(np.asarray, jax.device_get(heap))


class SharedTreeModel(Model):
    def _tree_raw_sum(self, frame: Frame) -> jax.Array:
        if not self.output["trees"]:
            # a deadline-cancelled build may legitimately hold zero trees;
            # it scores as the null model (f0 margin only)
            return jnp.zeros(frame.plen, jnp.float32)
        X = tree_matrix(frame, self.output["x_cols"], self.output["feat_domains"])
        return predict_raw(X, self.output["trees"],
                           cat_card=self.output.get("cat_card"),
                           n_bins=int(self.output.get("cat_bins") or 0))

    def predict(self, frame: Frame) -> Frame:
        """Score; a calibrated binomial model appends ``cal_p0``/``cal_p1``
        (reference: ``CalibrationHelper.postProcessPredictions``)."""
        out = super().predict(frame)
        cal = self.output.get("calibration")
        if cal is not None:
            p1 = np.clip(out.vec(2).to_numpy(), 1e-15, 1 - 1e-15)
            if cal["method"] == "PlattScaling":
                z = cal["a"] * np.log(p1 / (1 - p1)) + cal["b"]
                cp1 = 1.0 / (1.0 + np.exp(-z))
            else:                     # IsotonicRegression: PAV step interp
                cp1 = np.interp(p1, cal["xs"], cal["ys"])
            from h2o3_tpu.frame.types import VecType
            from h2o3_tpu.frame.vec import Vec
            out.add("cal_p0", Vec.from_numpy((1 - cp1).astype(np.float32),
                                             type=VecType.NUM))
            out.add("cal_p1", Vec.from_numpy(cp1.astype(np.float32),
                                             type=VecType.NUM))
        return out

    def varimp(self, use_pandas: bool = False):
        """Per-feature split-gain importance (reference: ``SharedTree``
        relative importance = accumulated squared-error reduction; h2o-py
        ``model.varimp()`` rows = (variable, relative, scaled, percentage))."""
        cols = self.output["x_cols"]
        rel = np.zeros(len(cols))
        all_trees = self.output.get("trees") or [
            t for ts in self.output.get("trees_multi", []) for t in ts]
        # getattr: artifacts pickled before the gain/cover channels restore
        # __dict__ directly, bypassing the dataclass defaults
        with_gain = [t for t in all_trees
                     if getattr(t, "gain", None) is not None]
        # ONE batched transfer for the whole ensemble — per-tree device_gets
        # paid 2 host round-trips per tree (graftlint TRC003)
        fetched = jax.device_get([(t.feat, t.gain) for t in with_gain])
        for feat, gain in fetched:
            feat, gain = np.asarray(feat), np.asarray(gain)
            ok = feat >= 0
            np.add.at(rel, feat[ok], np.maximum(gain[ok], 0.0))
        mx = rel.max() if rel.max() > 0 else 1.0
        tot = rel.sum() if rel.sum() > 0 else 1.0
        rows = sorted(zip(cols, rel, rel / mx, rel / tot),
                      key=lambda r: -r[1])
        if use_pandas:
            import pandas as pd
            return pd.DataFrame(rows, columns=["variable", "relative_importance",
                                               "scaled_importance", "percentage"])
        return rows

    def _contrib_scale_bias(self) -> tuple[float, float]:
        """(scale, extra_bias) mapping summed tree-leaf SHAP onto this model's
        raw margin: margin = scale * tree_sum + extra_bias."""
        return 1.0, 0.0

    def predict_contributions(self, frame: Frame) -> Frame:
        """Per-row SHAP contributions + BiasTerm (reference:
        ``Model.scoreContributions`` → genmodel TreeSHAP; h2o-py
        ``model.predict_contributions``). Row sums equal the model's raw
        margin (logit for bernoulli, mean prediction for DRF/regression)."""
        from h2o3_tpu.frame.types import VecType
        from h2o3_tpu.frame.vec import Vec
        from h2o3_tpu.genmodel.treeshap import ensemble_contributions
        if "trees" not in self.output:
            raise ValueError("contributions need a single-tree-set model")
        X = np.asarray(jax.device_get(
            tree_matrix(frame, self.output["x_cols"],
                        self.output["feat_domains"])))[: frame.nrows]
        phi = ensemble_contributions(
            self.output["trees"], X,
            cat_card=self.output.get("cat_card"),
            n_bins=int(self.output.get("cat_bins") or 0))
        scale, bias = self._contrib_scale_bias()
        phi *= scale
        phi[:, -1] += bias
        names = list(self.output["x_cols"]) + ["BiasTerm"]
        return Frame(names, [Vec.from_numpy(phi[:, i].astype(np.float32),
                                            type=VecType.NUM)
                             for i in range(phi.shape[1])])

    def _tree_raw_sum_per_class(self, frame: Frame) -> jax.Array:
        """[rows, K] per-class sums for multinomial (trees_multi[k] = class k)."""
        if not any(self.output["trees_multi"]):
            # zero-round deadline-cancelled partial: null model (f0 only),
            # same contract as the single-class guard in _tree_raw_sum
            return jnp.zeros((frame.plen, len(self.output["trees_multi"])),
                             jnp.float32)
        X = tree_matrix(frame, self.output["x_cols"], self.output["feat_domains"])
        cc = self.output.get("cat_card")
        nb = int(self.output.get("cat_bins") or 0)
        return jnp.stack([predict_raw(X, ts, cat_card=cc, n_bins=nb)
                          for ts in self.output["trees_multi"]], axis=1)


class GBMModel(SharedTreeModel):
    algo = "gbm"

    def _contrib_scale_bias(self):
        return float(self.output["learn_rate"]), float(self.output["f0"])

    def _score_raw(self, frame: Frame) -> jax.Array:
        if self.output["distribution"] == "multinomial":
            f = jnp.asarray(self.output["f0_multi"])[None, :] \
                + self.output["learn_rate"] * self._tree_raw_sum_per_class(frame)
            return jax.nn.softmax(f, axis=1)
        f = self.output["f0"] + self.output["learn_rate"] * self._tree_raw_sum(frame)
        oc = self.params.get("offset_column")
        if oc:
            if oc not in frame:
                raise ValueError(f"scoring frame lacks offset column {oc!r}")
            f = f + jnp.nan_to_num(frame.vec(oc).as_float(), nan=0.0)
        if self.output["distribution"] == "bernoulli":
            p = jax.nn.sigmoid(f)
            return jnp.stack([1 - p, p], axis=1)
        if self.output["distribution"] in ("poisson", "gamma", "tweedie"):
            return jnp.exp(jnp.clip(f, -30, 30))   # log link
        if self.output["distribution"] == "custom":
            return _linkinv_device(self.output["custom_link"], f)
        return f


class SharedTreeBuilder(ModelBuilder):
    """Common driver for boosting/bagging (reference: hex/tree/SharedTree.java)."""

    @classmethod
    def defaults(cls) -> dict:
        return dict(
            super().defaults(),
            ntrees=50,
            max_depth=5,
            min_rows=10.0,
            nbins=64,
            sample_rate=1.0,
            col_sample_rate_per_tree=1.0,
            min_split_improvement=1e-5,
            stopping_rounds=0,
            stopping_metric="AUTO",      # deviance (logloss/MSE) like reference
            stopping_tolerance=1e-3,
            score_tree_interval=0,   # history row cadence; the fused tracker
            score_each_iteration=False,  # scores EVERY tree at no cost, so
                                         # these only thin the reported table
            monotone_constraints=None,       # {col: ±1} (Constraints.java)
            interaction_constraints=None,    # [[cols...], ...] (BranchInteractionConstraints)
            calibrate_model=False,           # CalibrationHelper.java:18
            calibration_frame=None,
            calibration_method="PlattScaling",   # or IsotonicRegression
            nbins_cats=1024,                 # DHistogram enum bins: a bin a level up to this, whatever nbins is
            categorical_encoding="AUTO",     # AUTO/enum = group splits; ordinal = thresholds
            offset_column=None,              # per-row margin offset (Model.Parameters._offset)
        )

    # Dense-heap trees cap depth at 16 (2^17 nodes); the reference's default 20
    # assumes sparse node storage.
    MAX_TREE_DEPTH = 16

    #: scoring-history column name per stopping metric (AUC is tracked
    #: negated for less-is-better stopping; the table shows the true value)
    _HIST_NAMES = {"AUTO": "deviance", "deviance": "deviance",
                   "logloss": "logloss", "MSE": "mse", "RMSE": "rmse",
                   "AUC": "auc", "misclassification": "classification_error"}

    def _scoring_history(self, model):
        """Per-tree metric rows from the fused scan's tracked series
        (reference: ``SharedTree.doScoringAndSaveModel`` →
        ``createScoringHistoryTable``)."""
        series = getattr(self, "_score_series", None)
        if not series:
            return None
        metric, tser, vser = series
        name = self._HIST_NAMES.get(metric, "deviance")
        sign = -1.0 if metric == "AUC" else 1.0   # tracked negated
        cols = [("number_of_trees", "long", "%d"),
                (f"training_{name}", "double", "%.5f")]
        if vser is not None:
            cols.append((f"validation_{name}", "double", "%.5f"))
        # score_tree_interval thins the REPORTED table (reference scores on
        # that cadence; the fused tracker gets every tree anyway) — the last
        # tree always reports, matching doScoringAndSaveModel(finalScoring)
        sti = int(self.params.get("score_tree_interval") or 0)
        if self.params.get("score_each_iteration"):
            sti = 1
        values = [[i + 1, sign * float(tv)] +
                  ([sign * float(vser[i])] if vser is not None else [])
                  for i, tv in enumerate(tser)
                  if sti <= 1 or (i + 1) % sti == 0 or i == len(tser) - 1]
        return self._history_table(model, cols, values)

    def _prepare(self, frame: Frame, x: list[str], y: str, weights=None):
        depth = int(self.params["max_depth"])
        if depth > self.MAX_TREE_DEPTH:
            raise ValueError(f"max_depth={depth} exceeds the dense-heap limit "
                             f"{self.MAX_TREE_DEPTH}")
        yvec = frame.vec(y)
        # edges from a strided host sample assembled per COLUMN — stacking a
        # full [rows, F] float matrix on TPU pads F to 128 lanes (4.6x HBM;
        # 5.6GB at HIGGS-11M), so the raw design matrix is never materialized
        nrows = frame.nrows
        stride = max(1, nrows // 100_000)
        idx = jnp.arange(0, nrows, stride)
        sample_dev = jnp.stack([frame.vec(c).as_float()[idx] for c in x],
                               axis=1)
        sample = np.asarray(jax.device_get(sample_dev))
        w_sample = None
        if weights is not None:
            # weighted edges keep the weights-as-replication contract
            # (compute_bin_edges docstring); same strided sample of rows
            w_sample = np.asarray(jax.device_get(weights[idx])).astype(np.float64)
        # the two phases a profile reads by name: host numpy while the
        # device waits, then the instant binning is first dispatched (it
        # runs on asynchronously and drains at _fit's f0 fetch)
        self._setup_cat_info(frame, x)
        with timed_event("phase", f"{self.algo}:prepare.edges"):
            edges = compute_bin_edges(sample, int(self.params["nbins"]),
                                      w_sample)
            # the edges' width IS the engine's bin count (bin_column and
            # bin_features read it off them): inf-padded up to it where a
            # categorical column has more bins than ``nbins``
            edges = jnp.asarray(np.pad(
                edges, ((0, 0), (0, self._n_bins - 1 - edges.shape[1])),
                constant_values=np.inf))
        with timed_event("phase", f"{self.algo}:prepare.bin"):
            binned = self._bin_frame(frame, x, edges)
        from h2o3_tpu.models.data_info import response_as_float
        yy, valid = response_as_float(yvec)
        domains = {c: frame.vec(c).domain for c in x if frame.vec(c).is_categorical}
        return None, edges, binned, yy, valid, yvec, domains

    def _bin_frame(self, frame: Frame, x: list[str], edges) -> jax.Array:
        """Per-column binning → [rows, F] int8/int16 (the only row-major
        matrix training keeps). A numeric column's bin is the count of its
        edges <= x (``quantile.bin_column``: fused compares, no gather — a
        binary search's per-step gathers from the edge table cost 21 s of
        a 33 s build at 255 edges on the v5e, PERF.md PR 25); a categorical
        column's is its level code (``quantile.bin_levels``; range-grouped
        past ``nbins_cats``). Bin count, NA bin and dtype are the edges'
        width's (``self._n_bins - 1``): the dtype is the narrowest that
        holds every bin id PLUS the Pallas pad sentinel (n_bins_tot + 1):
        int8 up to 125 bins halves HBM reads of the histogram kernel's
        dominant input vs int16 (the default 64-bin config packs; the
        256-bin XGBoost config stays int16) — VERDICT r4 next #2.

        Every bin id lies inside ``self._bins_used`` or is the missing bin:
        the histogram kernel's contract (``_binning_edges``)."""
        n_bins = edges.shape[1] + 1
        cc, cat_bins = (self._cat_info if self._cat_info is not None
                        else (None, 0))
        edges = self._binning_edges(edges)
        cols = []
        for j, c in enumerate(x):
            v = frame.vec(c).as_float()
            if cc is not None and int(cc[j]) > 0:
                b = bin_levels(v, int(cc[j]), cat_bins, n_bins)
            else:
                b = bin_column(v, edges[j])
            cols.append(b)
        return jnp.stack(cols, axis=1)

    def _binning_edges(self, edges):
        """``edges`` as a numeric column is binned against them. The padding
        past ``nbins - 1`` is ``inf`` in the model (a threshold there sends
        every value left) and would count an INFINITE value in, up to the
        engine's last bin; NaN compares false to everything, so such a value
        bins as the largest finite one does and every id stays inside
        ``[0, nbins)``, as ``_bins_used`` declares. Edges without padding
        (no categorical column past ``nbins``) are handed back as they
        are."""
        nbins = int(self.params["nbins"])
        if edges.shape[1] < nbins:
            return edges
        return edges.at[:, nbins - 1:].set(jnp.nan)

    def _setup_cat_info(self, frame: Frame, x: list[str]) -> None:
        """Categorical group-split binning state, and the engine's bin count
        ``self._n_bins`` (reference: DHistogram gives an enum one bin per
        level up to ``nbins_cats`` whatever ``nbins`` is, then range-groups;
        ``categorical_encoding="ordinal"`` opts back into threshold splits).
        One bin count for the whole engine: ``nbins``, or the largest
        categorical column's ``min(cardinality, nbins_cats)`` where that is
        more, the NA bin after it; numeric columns leave the upper bins
        empty. A model without categorical columns has ``nbins``.

        ``self._bins_used`` says which: the bins each column CAN hold, from
        the frame's schema alone and with no read of the data (``nbins`` a
        numeric column, ``min(cardinality, nbins_cats)`` a categorical
        one), ``None`` where all hold the same. It is the histogram
        kernel's ``bins_used``; being the schema's, it is the same for every
        fold, grid member and refit of one frame, which so share one
        compiled program. Binning keeps every id inside it
        (``_binning_edges``, ``quantile.cat_bins_for_codes``)."""
        enc = str(self.params.get("categorical_encoding") or "AUTO").lower()
        cat_card = np.zeros(len(x), np.int32)
        if enc in ("auto", "enum"):
            for j, c in enumerate(x):
                if frame.vec(c).is_categorical:
                    cat_card[j] = frame.vec(c).cardinality()
        elif enc not in ("ordinal", "label_encoder", "labelencoder"):
            raise ValueError(f"unsupported categorical_encoding {enc!r}; "
                             "have AUTO, enum, ordinal/label_encoder")
        nbins = int(self.params["nbins"])
        if cat_card.any():
            cat_bins = int(self.params.get("nbins_cats") or nbins)
            self._cat_info = (jnp.asarray(cat_card), cat_bins)
            self._n_bins = max(nbins, min(int(cat_card.max()), cat_bins))
            used = tuple(min(int(c), cat_bins) if c > 0 else nbins
                         for c in cat_card)
            self._bins_used = used if len(set(used)) > 1 else None
        else:
            self._cat_info = None
            self._n_bins = nbins
            self._bins_used = None

    def _apply_cat_bins(self, X, binned):
        """Re-bin the categorical columns of a ``bin_features`` result (a
        validation frame): bin = (possibly range-grouped) level code,
        missing stays the NA bin; count and dtype are ``binned``'s own."""
        if self._cat_info is None:
            return binned
        cc, cat_bins = self._cat_info
        cb = cat_bins_for_codes(X, cc, cat_bins)
        is_cat = cc[None, :] > 0
        nan = jnp.isnan(X)
        out = jnp.where(is_cat & ~nan, cb, binned)
        return jnp.where(is_cat & nan, self._n_bins, out).astype(binned.dtype)

    @property
    def _cat_feats(self):
        return None if self._cat_info is None else self._cat_info[0] > 0

    def _cat_output(self) -> dict:
        """Extra model-output entries for group-split models."""
        if self._cat_info is None:
            return {}
        cc, cat_bins = self._cat_info
        return dict(cat_card=cc, cat_bins=cat_bins)

    def _maybe_calibrate(self, model) -> None:
        """Fit probability calibration on a held-out frame (reference:
        ``hex/tree/CalibrationHelper.java:18`` — Platt scaling or isotonic
        regression on the model's predicted p1 vs the actual class)."""
        if not self.params.get("calibrate_model"):
            return
        if model.nclasses != 2:
            raise ValueError("calibrate_model requires a binomial model "
                             "(reference: CalibrationHelper)")
        cf = self.params.get("calibration_frame")
        if cf is None:
            raise ValueError("calibrate_model requires calibration_frame")
        if isinstance(cf, str):
            from h2o3_tpu.utils.registry import DKV
            cf = DKV[cf]
        method = str(self.params.get("calibration_method") or "PlattScaling")
        if method not in ("PlattScaling", "IsotonicRegression"):
            raise ValueError(f"unknown calibration_method {method!r}")
        from h2o3_tpu.models.data_info import response_adapted
        from h2o3_tpu.parallel.distributed import fetch
        raw = model._score_raw(cf)
        yv, valid = response_adapted(cf.vec(model.response_column),
                                     model.response_domain)
        mask = fetch(cf.row_mask() & valid)[:cf.nrows]
        p1 = np.clip(fetch(raw)[:cf.nrows, 1][mask], 1e-15, 1 - 1e-15)
        y = fetch(yv)[:cf.nrows][mask]
        if method == "PlattScaling":
            f = np.log(p1 / (1 - p1))
            # Platt's target smoothing: t+=(N++1)/(N++2), t-=1/(N-+2)
            npos, nneg = float(y.sum()), float((1 - y).sum())
            t = np.where(y > 0, (npos + 1) / (npos + 2), 1 / (nneg + 2))
            a, b = 1.0, 0.0
            for _ in range(50):
                p = 1 / (1 + np.exp(-(a * f + b)))
                g = np.array([np.sum((p - t) * f), np.sum(p - t)])
                W = np.maximum(p * (1 - p), 1e-10)
                Hm = np.array([[np.sum(W * f * f) + 1e-9, np.sum(W * f)],
                               [np.sum(W * f), np.sum(W) + 1e-9]])
                step = np.linalg.solve(Hm, g)
                a, b = a - step[0], b - step[1]
                if np.abs(step).max() < 1e-10:
                    break
            model.output["calibration"] = dict(method=method, a=float(a),
                                               b=float(b))
        else:
            order = np.argsort(p1)
            xs, ys = p1[order], y[order].astype(np.float64)
            # pool-adjacent-violators (reference hex/isotonic)
            vals, wts, cnt = list(ys), [1.0] * len(ys), list(xs)
            i = 0
            merged_v, merged_w, merged_x = [], [], []
            for v, wt, xx in zip(vals, wts, cnt):
                merged_v.append(v); merged_w.append(wt); merged_x.append(xx)
                while len(merged_v) > 1 and merged_v[-2] > merged_v[-1]:
                    v2, w2 = merged_v.pop(), merged_w.pop()
                    merged_x.pop()
                    merged_v[-1] = (merged_v[-1] * merged_w[-1] + v2 * w2) / (merged_w[-1] + w2)
                    merged_w[-1] += w2
            model.output["calibration"] = dict(
                method=method,
                xs=[float(v) for v in merged_x],
                ys=[float(v) for v in merged_v])

    def _constraint_arrays(self, x: list[str], frame: Frame):
        """(mono[F], reach[F,F]) device arrays from the constraint params.

        Reference: ``hex/tree/Constraints.java:7`` (monotone directions) and
        ``BranchInteractionConstraints.java`` (allowed-feature propagation).
        Unlisted features form singleton interaction sets (XGBoost
        semantics: they may split anywhere but nothing else may follow)."""
        mc = self.params.get("monotone_constraints") or {}
        ic = self.params.get("interaction_constraints")
        mono = reach = None
        if mc:
            bad = set(mc) - set(x)
            if bad:
                raise ValueError(f"monotone_constraints name non-feature "
                                 f"columns: {sorted(bad)}")
            for c in mc:
                if frame.vec(c).is_categorical:
                    raise ValueError(f"monotone constraint on categorical "
                                     f"column {c!r} (reference: numeric only)")
                if int(mc[c]) not in (-1, 0, 1):
                    raise ValueError(f"monotone_constraints[{c!r}] must be "
                                     "-1, 0 or 1")
            mono = jnp.asarray([int(mc.get(c, 0)) for c in x], jnp.int32)
        if ic:
            F = len(x)
            reach_np = np.zeros((F, F), bool)
            listed: set[int] = set()
            for group in ic:
                bad = set(group) - set(x)
                if bad:
                    raise ValueError(f"interaction_constraints name "
                                     f"non-feature columns: {sorted(bad)}")
                idxs = [x.index(c) for c in group]
                for i in idxs:
                    reach_np[i, idxs] = True
                listed.update(idxs)
            for f in range(F):
                if f not in listed:
                    reach_np[f, f] = True
            reach = jnp.asarray(reach_np)
        return mono, reach

    def _effective_col_rate(self) -> float:
        """Per-level feature-sampling rate (XGBoost overrides to fold
        colsample_bynode in without mutating the stored params)."""
        return float(self.params["col_sample_rate"])

    def _feat_mask(self, key, F: int, rate: float) -> jax.Array:
        if rate >= 1.0:
            return jnp.ones(F, bool)
        ku, kf = jax.random.split(key)
        m = jax.random.uniform(ku, (F,)) < rate
        # guarantee at least one feature
        return m.at[jax.random.randint(kf, (), 0, F)].set(True)

    def _check_checkpoint(self, cp, x, dist: str | None):
        """Validate checkpoint compatibility (reference: SharedTree.java:241
        checks immutable params against the prior model)."""
        if cp is None:
            return
        if list(cp.output["x_cols"]) != list(x):
            raise ValueError("checkpoint feature columns differ from this train")
        if dist is not None and cp.output["distribution"] != dist:
            raise ValueError(f"checkpoint distribution {cp.output['distribution']!r}"
                             f" != {dist!r}")
        for immut in ("max_depth", "nbins"):
            if int(cp.params.get(immut, self.params[immut])) != int(self.params[immut]):
                raise ValueError(f"checkpoint {immut} differs; tree structure "
                                 "params are immutable across resume")
        # group-split state must match: mixing masked and threshold trees in
        # one ensemble would mis-route every categorical (the traversal mode
        # is chosen per ensemble)
        cp_grouped = cp.output.get("cat_card") is not None
        if cp_grouped != (getattr(self, "_cat_info", None) is not None):
            raise ValueError(
                "checkpoint categorical encoding differs (group splits vs "
                "ordinal); set categorical_encoding to match the checkpoint")
        if cp_grouped and int(cp.output.get("cat_bins") or 0) != \
                int(self._cat_info[1]):
            raise ValueError("checkpoint nbins_cats differs; immutable "
                             "across resume")
        if cp.output["edges"].shape[1] + 1 != self._n_bins:
            raise ValueError(
                "checkpoint bin count differs (a categorical column's "
                "cardinality changed); immutable across resume")
        # learn_rate scales EVERY tree at scoring time — changing it across a
        # resume would silently rescale the checkpoint's trees too
        if "learn_rate" in self.params and "learn_rate" in cp.params:
            if float(cp.params["learn_rate"]) != float(self.params["learn_rate"]):
                raise ValueError("checkpoint learn_rate differs; it is immutable "
                                 "across resume (it rescales prior trees)")
        prior = int(cp.output["ntrees"])
        if int(self.params["ntrees"]) <= prior:
            raise ValueError(f"ntrees must exceed the checkpoint's {prior} "
                             "to continue training")

    def _row_weights(self, key, w, rate: float, bootstrap: bool):
        if bootstrap:
            # Poisson(rate) ≈ bootstrap of a `rate` fraction (sample_rate honored)
            return w * jax.random.poisson(key, rate, w.shape).astype(jnp.float32)
        if rate >= 1.0:
            return w
        return w * (jax.random.uniform(key, w.shape) < rate)


class GBM(SharedTreeBuilder):
    """h2o-py surface: ``H2OGradientBoostingEstimator``."""

    algo = "gbm"

    def supports_auto_recovery(self) -> bool:
        return True     # chunk-boundary snapshots in _grow_with_stopping

    def _retag_model(self, m: GBMModel) -> GBMModel:
        """Partial-model snapshots must carry the builder's model class so
        a resume passes the checkpoint algo check (XGBoost re-classes its
        models the same way at the end of ``_fit``)."""
        if self.algo == "xgboost":
            from h2o3_tpu.models.xgboost import XGBoostModel
            m.__class__ = XGBoostModel
        return m

    @classmethod
    def defaults(cls) -> dict:
        return dict(
            super().defaults(),
            learn_rate=0.1,
            distribution="AUTO",
            reg_lambda=0.0,
            col_sample_rate=1.0,   # per-level feature sampling inside grow_tree
            quantile_alpha=0.5,    # quantile distribution target
            huber_alpha=0.9,       # huber delta = this quantile of |residual|
            tweedie_power=1.5,
            custom_distribution_func=None,  # "python:key=module.Class" UDF
            # boosting rounds per compiled device program (0 = auto-size to
            # the watchdog budget); each dispatch pays ONE host sync for the
            # early-stopping decision. GBM/XGBoost only: DRF and the other
            # bagging builders grow their whole forest in one dispatch, so
            # the knob would be inert there
            trees_per_dispatch=0,
        )

    def _fit(self, job: Job, frame: Frame, x, y, weights) -> GBMModel:
        p = self.params
        X, edges, binned, yy, valid, yvec, domains = self._prepare(
            frame, x, y, weights)
        cp = self._resolve_checkpoint()
        if cp is not None:
            # validate BEFORE re-binning: a feature-list mismatch must raise
            # the intended error, not a shape error from bin_features
            self._check_checkpoint(cp, x, None)
            # binning must match the prior model's edges exactly, else tree
            # thresholds silently shift (reference keeps the checkpoint's
            # DHistogram bins)
            edges = cp.output["edges"]
            binned = self._bin_frame(frame, x, edges)
        dist = str(p["distribution"])
        if dist.lower() == "auto":   # h2o-py sends lowercase enum names
            dist = "AUTO"
        if yvec.is_categorical:
            if dist not in ("AUTO", "bernoulli", "multinomial"):
                raise ValueError(f"distribution {dist!r} requires a numeric response")
            if dist == "bernoulli" and yvec.cardinality() != 2:
                raise ValueError("Binomial requires the response to be a 2-class "
                                 "categorical")
            dist = "bernoulli" if yvec.cardinality() == 2 else "multinomial"
        else:
            if dist == "AUTO":
                dist = "gaussian"
            if dist == "bernoulli":
                raise ValueError("bernoulli distribution requires a categorical (2-level) response")
            if dist not in ("gaussian", "poisson", "gamma", "tweedie",
                            "laplace", "quantile", "huber", "custom"):
                raise ValueError(f"unsupported distribution {dist!r}; "
                                 "have gaussian, bernoulli, poisson, gamma, "
                                 "tweedie, laplace, quantile, huber, custom, "
                                 "AUTO")
        custom_id, custom_dist = -1, None
        if dist == "custom":
            ref = p.get("custom_distribution_func")
            if not ref:
                raise ValueError("distribution='custom' requires "
                                 "custom_distribution_func "
                                 "(h2o.upload_custom_distribution reference)")
            from h2o3_tpu.utils import udf as _udf
            custom_id, custom_dist = _udf.resolve_distribution(ref)
        w = weights * valid
        yc = jnp.where(w > 0, yy, 0.0)

        if dist == "multinomial":
            if p.get("offset_column"):
                raise ValueError("offset_column is not supported for "
                                 "multinomial distributions")
            return self._fit_multinomial(job, frame, x, y, w, yc, yvec,
                                         X, edges, binned, domains, cp)
        self._check_checkpoint(cp, x, dist)

        if cp is not None:
            f0 = float(cp.output["f0"])
        else:
            ybar = float(jax.device_get((w * yc).sum() / jnp.maximum(w.sum(), 1e-30)))
            if dist == "bernoulli":
                ybar = min(max(ybar, 1e-6), 1 - 1e-6)
                f0 = float(np.log(ybar / (1 - ybar)))
            elif dist in ("poisson", "gamma", "tweedie"):
                f0 = float(np.log(max(ybar, 1e-10)))   # log link
            elif dist in ("laplace", "huber"):
                f0 = _weighted_quantile_host(yy, w, 0.5)
            elif dist == "quantile":
                f0 = _weighted_quantile_host(yy, w, float(p["quantile_alpha"]))
            elif dist == "custom":
                import numpy as _np
                oc_ = p.get("offset_column")
                off = (_np.nan_to_num(np.asarray(frame.vec(oc_).as_float()))
                       if oc_ else None)
                f0 = custom_dist.f0(np.asarray(jax.device_get(yy)),
                                    np.asarray(jax.device_get(w)), off)
            else:
                f0 = ybar

        lr = float(p["learn_rate"])
        seed = int(p["seed"]) if int(p["seed"]) >= 0 else 42
        key = jax.random.PRNGKey(seed)
        Fcur = jnp.full(binned.shape[0], f0, jnp.float32)
        oc = p.get("offset_column")
        if oc:
            # per-row margin offset (reference: offset_column adds to F on
            # both train and score; score0 re-reads it from the scored frame)
            Fcur = Fcur + jnp.nan_to_num(frame.vec(oc).as_float(), nan=0.0)
        trees: list[Tree] = []
        if cp is not None:
            trees = list(cp.output["trees"])
            # fold (not sum-then-scale): the resumed margins must match the
            # uninterrupted scan's accumulation order bit-for-bit, so the
            # remaining trees come out identical (exact-resume contract)
            Fcur = fold_binned(binned, trees, self._n_bins, lr, Fcur)
        ntrees = int(p["ntrees"])
        done = len(trees)
        keys = jax.random.split(key, ntrees * 3).reshape(ntrees, 3, 2)[done:]
        job.update(0.1, f"growing {ntrees - done} trees (one fused program)")
        kwargs = dict(
            dist=dist, depth=int(p["max_depth"]), n_bins=self._n_bins,
            col_rate=self._effective_col_rate(),
            sample_rate=float(p["sample_rate"]),
            col_tree_rate=float(p["col_sample_rate_per_tree"]),
            min_rows=float(p["min_rows"]), reg_lambda=float(p["reg_lambda"]),
            reg_alpha=float(p.get("reg_alpha", 0.0)),
            gamma=float(p.get("gamma", 0.0)),
            min_split_improvement=float(p["min_split_improvement"]), lr=lr,
            bootstrap=False, drf=False, nclass=0,
            quantile_alpha=float(p["quantile_alpha"]),
            huber_alpha=float(p["huber_alpha"]),
            tweedie_power=float(p["tweedie_power"]), custom_id=custom_id,
            custom_link=custom_dist.link_name if custom_dist else None)
        mono, reach = self._constraint_arrays(x, frame)
        kwargs.update(mono=mono, reach=reach, cat_feats=self._cat_feats,
                      bins_used=self._bins_used)
        fmask_base = jnp.ones(binned.shape[1], bool)
        valid = None
        if getattr(self, "_validation_frame", None) is not None or \
                int(p.get("stopping_rounds") or 0) > 0:
            # also tracked without early stopping: the validation series
            # feeds scoring_history (reference scores valid per event)
            valid = self._valid_stop_data(
                edges, 0, f0, lr, domains,
                yvec.domain if yvec.is_categorical else None,
                prior_trees=trees or None)

        # auto-checkpoint constructor: a resumable partial ensemble in the
        # exact shape checkpoint= resume consumes (distinct key — the final
        # model must never be clobbered by its own snapshot)
        self._partial_model_fn = None
        if getattr(self, "_build_recovery", None) is not None:
            def _partial(grown: list) -> GBMModel:
                pm = GBMModel(
                    key=f"{self.model_id or self.algo}_autockpt",
                    params=self.params, data_info=None, response_column=y,
                    response_domain=(yvec.domain if yvec.is_categorical
                                     else None),
                    output=dict(trees=trees + grown, edges=edges, f0=f0,
                                learn_rate=lr, distribution=dist,
                                x_cols=list(x), feat_domains=domains,
                                ntrees=len(trees) + len(grown),
                                **({"custom_link": custom_dist.link_name}
                                   if custom_dist is not None else {}),
                                **self._cat_output()))
                return self._retag_model(pm)
            self._partial_model_fn = _partial
        grown, Fend = self._grow_with_stopping(job, binned, edges, yc, w,
                                               fmask_base, Fcur, keys, dist,
                                               0, kwargs, p, valid=valid)
        self._partial_model_fn = None
        trees += grown
        job.update(0.9, f"{len(trees)} trees grown")
        # final margins double as training predictions (skips the re-score);
        # cached on the transient builder so models never pickle them
        if dist == "bernoulli":
            pe = jax.nn.sigmoid(Fend)
            self._last_train_raw = jnp.stack([1 - pe, pe], axis=1)
        elif dist in ("poisson", "gamma", "tweedie"):
            self._last_train_raw = jnp.exp(jnp.clip(Fend, -30, 30))
        elif dist == "custom":
            self._last_train_raw = _linkinv_device(custom_dist.link_name, Fend)
        else:
            self._last_train_raw = Fend

        model = GBMModel(
            key=make_model_key(self.algo, self.model_id),
            params=self.params, data_info=None, response_column=y,
            response_domain=yvec.domain if yvec.is_categorical else None,
            output=dict(trees=trees, edges=edges, f0=f0, learn_rate=lr,
                        distribution=dist, x_cols=list(x), feat_domains=domains,
                        ntrees=len(trees),
                        **({"custom_link": custom_dist.link_name}
                           if custom_dist is not None else {}),
                        **self._cat_output()),
        )
        self._maybe_calibrate(model)
        return model

    #: early-stopping metrics honored (reference: ScoreKeeper.StoppingMetric)
    STOPPING_METRICS = ("AUTO", "deviance", "logloss", "MSE", "RMSE", "AUC",
                        "misclassification")

    def _stop_score(self, metric: str, dist: str, F, y, w, nclass: int,
                    custom_link: str | None = None) -> float:
        """Less-is-better score for ``stopping_metric`` in host loops (the
        DART driver); same math as the fused scan's :func:`_metric_device`
        — one implementation keeps the two paths from drifting."""
        sdist = "multinomial" if nclass > 1 else dist
        if metric in ("logloss", "misclassification", "AUC") and sdist not in (
                "bernoulli", "multinomial"):
            raise ValueError(f"stopping_metric={metric!r} requires a "
                             "classification distribution")
        if metric == "AUC" and sdist != "bernoulli":
            raise ValueError("stopping_metric='AUC' requires a binomial "
                             "response")
        if metric not in self.STOPPING_METRICS:
            raise ValueError(f"unsupported stopping_metric {metric!r}; have "
                             f"{self.STOPPING_METRICS}")
        return float(jax.device_get(
            _metric_device(metric, sdist, F, y, w, nclass, custom_link)))

    def _valid_stop_data(self, edges, nclass: int, f0, lr: float,
                         domains, y_domain, prior_trees=None):
        """Bin the validation frame with the training edges and seed its
        margins — early stopping then scores the held-out frame per tree
        chunk (reference: ScoreKeeper scores the validation frame when one
        is given). Categorical features and response are remapped to the
        train domains (``Model.adaptTestForTrain`` semantics)."""
        vf = getattr(self, "_validation_frame", None)
        if vf is None:
            return None
        x = self._x_cols
        Xv = tree_matrix(vf, x, domains)
        binned_v = self._apply_cat_bins(
            Xv, bin_features(Xv, self._binning_edges(edges)))
        from h2o3_tpu.models.data_info import response_adapted
        yvec = vf.vec(self._y_col)
        yv, validv = response_adapted(yvec, y_domain)
        wv = vf.row_mask().astype(jnp.float32) * validv
        wcol = self.params.get("weights_column")
        if wcol and wcol in vf:
            wv = wv * vf.vec(wcol).data
        yv = jnp.where(wv > 0, yv, 0.0)
        nbins = self._n_bins
        if nclass > 1:
            Fval = jnp.broadcast_to(
                jnp.asarray(f0, jnp.float32)[None, :],
                (Xv.shape[0], nclass)).astype(jnp.float32)
            if prior_trees:  # checkpoint: [K][ntrees] lists
                Fval = Fval + lr * jnp.stack(
                    [predict_binned(binned_v, ts, nbins) for ts in prior_trees],
                    axis=1)
        else:
            Fval = jnp.full(Xv.shape[0], float(f0), jnp.float32)
            if prior_trees:
                Fval = Fval + lr * predict_binned(binned_v, prior_trees, nbins)
        return binned_v, yv, wv, Fval

    def _grow_with_stopping(self, job, binned, edges, yc, w, fmask_base,
                            Fcur, keys, dist: str, nclass: int, kwargs: dict,
                            p, valid=None) -> list:
        """Run the fused scan in watchdog-sized chunks, with per-tree metric
        series computed INSIDE the scan (train always; validation when a
        frame was given) — scoring history and ``stopping_rounds`` early
        stopping cost zero extra dispatches (reference: ``ScoreKeeper``
        between driver iterations; ``SharedTree.doScoringAndSaveModel``).
        On a stop the surplus chunk tail is discarded and the margins are
        replayed to the kept prefix, so the result is tree-for-tree
        identical to per-tree scoring."""
        M = keys.shape[0]
        sr = int(p.get("stopping_rounds") or 0)
        metric = str(p.get("stopping_metric") or "AUTO")
        # h2o-py sends enum values lowercase
        metric = {m.lower(): m for m in self.STOPPING_METRICS}.get(
            metric.lower(), metric)
        if metric not in self.STOPPING_METRICS:
            raise ValueError(f"unsupported stopping_metric {metric!r}; have "
                             f"{self.STOPPING_METRICS}")
        # validate metric/distribution compatibility up front (the device
        # tracker assumes a classification margin for AUC/logloss/misclass)
        sdist = "multinomial" if nclass > 1 else dist
        if metric in ("logloss", "misclassification", "AUC") and sdist not in (
                "bernoulli", "multinomial"):
            raise ValueError(f"stopping_metric={metric!r} requires a "
                             "classification distribution")
        if metric == "AUC" and sdist == "multinomial":
            raise ValueError("stopping_metric='AUC' requires a binomial "
                             "response")
        out_trees: list = []
        tser: list[float] = []
        vser: list[float] = []

        def collect(heap_h, count):
            if nclass > 1:
                return [[_trees_from_stacked(heap_h, m, k)
                         for k in range(nclass)] for m in range(count)]
            return [_trees_from_stacked(heap_h, m) for m in range(count)]

        # cap rows*trees per dispatch at ~1.5e8 (scaled by bins: histogram
        # cost scales with them), so one fused program stays bounded and
        # the job can report progress, stop and checkpoint between chunks.
        # The 25-tree ceiling decouples the program shape from large
        # ntrees: the common
        # AutoML values (50, 100, 200 trees) all balance to 25-tree chunks
        # and share one compile per (depth, bins) config; other ntrees get
        # waste-free balanced chunks (per = ceil(M/k)) at the cost of their
        # own shape. `trees_per_dispatch` overrides the auto sizing (an
        # upper bound per compiled program — balanced chunking below may
        # round it down to avoid padded surplus trees).
        tpd = int(p.get("trees_per_dispatch") or 0)
        if tpd < 0:
            raise ValueError("trees_per_dispatch must be >= 0 (0 = auto)")
        if tpd > 0:
            per = max(1, min(tpd, max(M, 1)))
        else:
            cost = max(binned.shape[0], 1) * max(int(kwargs["n_bins"]), 64) // 64
            per = max(1, min(int(1.5e8 // cost), 25))
            if sr > 0:
                # bound the discarded overshoot past the stopping point; ≥16
                # trees per chunk keeps the dispatch count low (each chunk
                # pays a host round-trip for the stopping decision)
                per = min(per, max(4 * sr, 16))
        # balanced chunks: ceil(M/k) for k = chunk count. Padding then wastes
        # at most k-1 trees per train instead of up to per-1 (a 20-tree run
        # with per=13 must grow 2x10, not 13 + a padded 7->13)
        k_chunks = max(1, -(-M // per))
        per = -(-M // k_chunks)
        tol = float(p.get("stopping_tolerance") or 1e-3)
        lr = float(kwargs["lr"])
        nbins = int(kwargs["n_bins"])
        best, since = np.inf, 0
        chunks = 0
        # auto-checkpoint plumbing (docs/RELIABILITY.md): the fit installed
        # a partial-model constructor when auto_recovery_dir is set; every
        # ckpt_every grown trees the partial ensemble lands on disk through
        # the SAME artifact format checkpoint= resume consumes
        recovery = getattr(self, "_build_recovery", None)
        partial_fn = getattr(self, "_partial_model_fn", None)
        from h2o3_tpu.persist.recovery import checkpoint_every
        ckpt_every = checkpoint_every()
        last_snap = 0
        deadline_stop = False
        from h2o3_tpu.ops.map_reduce import retrying
        for s0 in range(0, M, per):
            if job.should_stop:
                # cooperative deadline/cancel between chunks: built trees
                # are KEPT — the model returns partial, the job CANCELLED
                deadline_stop = True
                job.keep_partial()
                break
            kchunk = keys[s0:s0 + per]
            take = kchunk.shape[0]
            if take < per and per <= M:
                # pad the final partial chunk to the compiled chunk shape:
                # the surplus trees are grown then discarded (keep cap below)
                # — one margin replay is far cheaper than a second ~30-40s
                # XLA compile of an odd-shaped program
                reps = np.concatenate([np.arange(take),
                                       np.full(per - take, take - 1)])
                kchunk = kchunk[reps]
            F_prev = Fcur

            def _chunk():
                Fc, heap, extras, Fv = _boost_scan(
                    binned, edges, yc, w, fmask_base, F_prev, kchunk,
                    track=metric, val=valid, **kwargs)
                # ONE batched host transfer per chunk (per-leaf gets would
                # pay a dozen); the fetch feeds the host-side early-stopping
                # decision —
                # and surfaces any async dispatch error INSIDE the retry
                # scope
                hh, eh = jax.device_get(  # graftlint: ok(batched chunk fetch)
                    (heap, extras))
                return Fc, hh, eh, Fv

            with timed_event("tree", f"{self.algo}:chunk",
                             observe=_tm.ITER_SECONDS.labels(
                                 loop=f"{self.algo}_chunk")):
                # transient dispatch failures (injected drops, transient
                # runtime errors) retry with backoff instead of killing the
                # build; the chunk is functional over F_prev so a re-run is
                # exact
                Fcur, heap_h, extras_h, Fvend = retrying(
                    f"{self.algo}_chunk", _chunk)
            chunks += 1
            heap_h = jax.tree.map(np.asarray, heap_h)
            new_trees = collect(heap_h, take)
            ts = np.asarray(extras_h[0], np.float64)[:take]
            vs = (np.asarray(extras_h[1], np.float64)[:take]
                  if len(extras_h) > 1 else None)
            if valid is not None:
                valid = (valid[0], valid[1], valid[2], Fvend)
            series = vs if vs is not None else ts
            stop_at = None
            if sr > 0:
                for j, dev in enumerate(series):
                    # sign-safe relative improvement: deviances can be < 0
                    if dev < best - tol * abs(best) or not np.isfinite(best):
                        best, since = dev, 0
                    else:
                        since += 1
                        if since >= sr:
                            stop_at = j
                            break
            keep = take if stop_at is None else stop_at + 1
            out_trees.extend(new_trees[:keep])
            tser.extend(ts[:keep])
            if vs is not None:
                vser.extend(vs[:keep])
            shown = -series[keep - 1] if metric == "AUC" else series[keep - 1]
            try:
                job.update(0.1 + 0.8 * min(s0 + keep, M) / M,
                           f"{len(out_trees)}/{M} trees, {metric} {shown:.5f}")
            except JobCancelled:
                # deadline/cancel tripped inside update: this algorithm
                # keeps partial results, so swallow the cooperative raise
                # and stop growing — the job still terminates CANCELLED
                deadline_stop = True
                job.keep_partial()
            if recovery is not None and partial_fn is not None and \
                    len(out_trees) - last_snap >= ckpt_every:
                pm = partial_fn(list(out_trees))
                # progress counts TOTAL ensemble trees (prior checkpoint
                # included) against the params target, so a resume-of-a-
                # resume keeps its arithmetic straight
                recovery.snapshot(pm, progress=int(pm.output["ntrees"]),
                                  target=int(p["ntrees"]))
                last_snap = len(out_trees)
            if keep < kchunk.shape[0] and not kwargs.get("drf"):
                # the scan's margins include discarded trees (mid-chunk stop
                # or chunk padding) — replay to the kept prefix; one cheap
                # dispatch
                kept = new_trees[:keep]
                if nclass > 1:
                    Fcur = F_prev + lr * jnp.stack(
                        [predict_binned(binned, [t[k] for t in kept], nbins)
                         for k in range(nclass)], axis=1)
                else:
                    Fcur = F_prev + lr * predict_binned(binned, kept, nbins)
            if stop_at is not None or deadline_stop:
                break
        if deadline_stop and recovery is not None and partial_fn is not None \
                and len(out_trees) > last_snap:
            # deadline-cancelled builds stay resumable from exactly where
            # they stopped (train() keeps the snapshot on CANCELLED)
            pm = partial_fn(list(out_trees))
            recovery.snapshot(pm, progress=int(pm.output["ntrees"]),
                              target=int(p["ntrees"]))
        self._score_series = (metric, tser, vser if vser else None)
        # dispatch economy: ONE host sync (the stopping/heap fetch) per
        # `trees_per_dispatch`-sized chunk, not per boosting round
        publish_dispatch_audit(self, f"{self.algo}_round",
                               iterations=max(len(out_trees), 1),
                               host_syncs=chunks, device_dispatches=chunks)
        return out_trees, Fcur

    def _fit_multinomial(self, job: Job, frame, x, y, w, yc, yvec,
                         X, edges, binned, domains, cp=None) -> GBMModel:
        """K one-vs-rest trees per round on softmax gradients (reference:
        GBM.java multinomial — one DTree per class per iteration)."""
        p = self.params
        self._check_checkpoint(cp, x, "multinomial")
        K = yvec.cardinality()
        if cp is not None:
            f0 = np.asarray(cp.output["f0_multi"], np.float32)
        else:
            yoh = jax.nn.one_hot(yc.astype(jnp.int32), K) * w[:, None]
            prior = np.asarray(jax.device_get(yoh.sum(axis=0)), np.float64)
            prior = np.maximum(prior / max(prior.sum(), 1e-30), 1e-10)
            f0 = np.log(prior).astype(np.float32)

        lr = float(p["learn_rate"])
        seed = int(p["seed"]) if int(p["seed"]) >= 0 else 42
        key = jax.random.PRNGKey(seed)
        Fcur = jnp.broadcast_to(jnp.asarray(f0)[None, :],
                                (binned.shape[0], K)).astype(jnp.float32)
        trees_multi: list[list[Tree]] = [[] for _ in range(K)]
        done = 0
        if cp is not None:
            trees_multi = [list(ts) for ts in cp.output["trees_multi"]]
            done = len(trees_multi[0])
            # per-class sequential fold matches the scan's per-round
            # accumulation order exactly (see the single-class path)
            Fcur = jnp.stack(
                [fold_binned(binned, ts, self._n_bins, lr, Fcur[:, ki])
                 for ki, ts in enumerate(trees_multi)], axis=1)
        ntrees = int(p["ntrees"])
        keys = jax.random.split(key, ntrees * 3).reshape(ntrees, 3, 2)[done:]
        job.update(0.1, f"growing {(ntrees - done) * K} trees (one fused program)")
        kwargs = dict(
            dist="multinomial", depth=int(p["max_depth"]),
            n_bins=self._n_bins, col_rate=self._effective_col_rate(),
            sample_rate=float(p["sample_rate"]),
            col_tree_rate=float(p["col_sample_rate_per_tree"]),
            min_rows=float(p["min_rows"]), reg_lambda=float(p["reg_lambda"]),
            reg_alpha=float(p.get("reg_alpha", 0.0)),
            gamma=float(p.get("gamma", 0.0)),
            min_split_improvement=float(p["min_split_improvement"]), lr=lr,
            bootstrap=False, drf=False, nclass=K)
        if self.params.get("monotone_constraints"):
            raise ValueError("monotone_constraints are not supported for "
                             "multinomial distributions (reference: GBM.java)")
        _, reach = self._constraint_arrays(x, frame)
        kwargs.update(mono=None, reach=reach, cat_feats=self._cat_feats,
                      bins_used=self._bins_used)
        valid = None
        if getattr(self, "_validation_frame", None) is not None or \
                int(p.get("stopping_rounds") or 0) > 0:
            valid = self._valid_stop_data(
                edges, K, f0, lr, domains, yvec.domain,
                prior_trees=trees_multi if done else None)
        self._partial_model_fn = None
        if getattr(self, "_build_recovery", None) is not None:
            def _partial(rounds_grown: list) -> GBMModel:
                tm = [list(ts) for ts in trees_multi]
                for per_class in rounds_grown:
                    for k in range(K):
                        tm[k].append(per_class[k])
                pm = GBMModel(
                    key=f"{self.model_id or self.algo}_autockpt",
                    params=self.params, data_info=None, response_column=y,
                    response_domain=yvec.domain,
                    output=dict(trees_multi=tm, edges=edges, f0_multi=f0,
                                learn_rate=lr, distribution="multinomial",
                                x_cols=list(x), feat_domains=domains,
                                ntrees=len(tm[0]), **self._cat_output()))
                return self._retag_model(pm)
            self._partial_model_fn = _partial
        rounds, Fend = self._grow_with_stopping(job, binned, edges, yc, w,
                                                jnp.ones(binned.shape[1], bool),
                                                Fcur, keys, "multinomial", K,
                                                kwargs, p, valid=valid)
        self._partial_model_fn = None
        for per_class in rounds:
            for k in range(K):
                trees_multi[k].append(per_class[k])
        job.update(0.9, f"{len(rounds) * K} trees grown")
        self._last_train_raw = jax.nn.softmax(Fend, axis=1)

        return GBMModel(
            key=make_model_key(self.algo, self.model_id),
            params=self.params, data_info=None, response_column=y,
            response_domain=yvec.domain,
            output=dict(trees_multi=trees_multi, edges=edges, f0_multi=f0,
                        learn_rate=lr, distribution="multinomial",
                        x_cols=list(x), feat_domains=domains, ntrees=ntrees,
                        **self._cat_output()),
        )


class DRFModel(SharedTreeModel):
    algo = "drf"

    def _contrib_scale_bias(self):
        return 1.0 / max(self.output["ntrees"], 1), 0.0

    def _score_raw(self, frame: Frame) -> jax.Array:
        if self.output.get("trees_multi") is not None:
            probs = jnp.clip(self._tree_raw_sum_per_class(frame)
                             / max(self.output["ntrees"], 1), 0.0, 1.0)
            return probs / jnp.maximum(probs.sum(axis=1, keepdims=True), 1e-30)
        mean = self._tree_raw_sum(frame) / max(self.output["ntrees"], 1)
        if self.output["binomial"]:
            pmean = jnp.clip(mean, 0.0, 1.0)
            return jnp.stack([1 - pmean, pmean], axis=1)
        return mean


class DRF(SharedTreeBuilder):
    """h2o-py surface: ``H2ORandomForestEstimator``.

    Reference: ``hex/tree/drf/DRF.java`` — bagged trees, mtries feature
    sampling, predictions averaged. Each tree fits the response directly
    (g=-y, h=1 → leaf = in-node mean)."""

    algo = "drf"

    @classmethod
    def defaults(cls) -> dict:
        d = dict(super().defaults(), mtries=-1)
        d["max_depth"] = 14
        d["min_rows"] = 1.0
        d["sample_rate"] = 0.632
        # reference DRF.java: binomial normally trains ONE tree per round
        # (complement trick); this opts into a tree per class like
        # multinomial (ktrees=2), normalized by vote sum
        d["binomial_double_trees"] = False
        return d

    def _fit(self, job: Job, frame: Frame, x, y, weights) -> DRFModel:
        p = self.params
        X, edges, binned, yy, valid, yvec, domains = self._prepare(
            frame, x, y, weights)
        cp = self._resolve_checkpoint()
        if cp is not None:
            self._check_checkpoint(cp, x, None)   # before the edges swap
            edges = cp.output["edges"]
            binned = self._bin_frame(frame, x, edges)
        classifier = yvec.is_categorical
        nclass = yvec.cardinality() if classifier else 0
        w = weights * valid
        yc = jnp.where(w > 0, yy, 0.0)

        X = None    # training reads only `binned`
        F = binned.shape[1]
        mtries = int(p["mtries"])
        if mtries <= 0:
            mtries = max(1, int(np.sqrt(F)) if classifier else max(F // 3, 1))
        seed = int(p["seed"]) if int(p["seed"]) >= 0 else 42
        key = jax.random.PRNGKey(seed)
        ntrees = int(p["ntrees"])
        fmask = jnp.ones(F, bool)

        if nclass > 2 or (nclass == 2 and p.get("binomial_double_trees")):
            # one class-indicator tree per class per round; leaf = in-node
            # class fraction (reference: DRF.java multinomial ktrees —
            # binomial_double_trees routes 2-class fits here too)
            trees_multi: list[list[Tree]] = [[] for _ in range(nclass)]
            done = 0
            if cp is not None:
                if cp.output.get("trees_multi") is None:
                    raise ValueError(
                        "checkpoint was trained without binomial_double_"
                        "trees; the tree layouts are incompatible")
                trees_multi = [list(ts) for ts in cp.output["trees_multi"]]
                done = len(trees_multi[0])
            keys = jax.random.split(key, ntrees * 3).reshape(ntrees, 3, 2)[done:]
            # deadline checkpoint: DRF grows the whole forest in ONE fused
            # program, so the budget is only observable at dispatch
            # boundaries — a deadline that already tripped cancels here,
            # before the program launches (docs/RELIABILITY.md)
            job.update(0.1, f"growing {(ntrees - done) * nclass} trees "
                            "(one fused program)")
            _, heap, _, _ = _boost_scan(
                binned, edges, yc, w, fmask,
                jnp.zeros((binned.shape[0], nclass), jnp.float32), keys,
                dist="multinomial", depth=int(p["max_depth"]),
                n_bins=self._n_bins, col_rate=mtries / F,
                sample_rate=float(p["sample_rate"]), col_tree_rate=1.0,
                min_rows=float(p["min_rows"]), reg_lambda=0.0, reg_alpha=0.0,
                gamma=0.0,
                min_split_improvement=float(p["min_split_improvement"]),
                lr=1.0, bootstrap=True, drf=True, nclass=nclass,
                cat_feats=self._cat_feats, bins_used=self._bins_used)
            heap = _heap_to_host(heap)
            for m in range(ntrees - done):
                for k in range(nclass):
                    trees_multi[k].append(_trees_from_stacked(heap, m, k))
            try:
                job.update(0.9, f"{ntrees * nclass} trees grown")
            except JobCancelled:
                # deadline tripped while the program ran: the forest is
                # already complete — keep it (job still reads CANCELLED)
                job.keep_partial()
            return DRFModel(
                key=make_model_key(self.algo, self.model_id),
                params=self.params, data_info=None, response_column=y,
                response_domain=yvec.domain,
                output=dict(trees_multi=trees_multi, edges=edges, ntrees=ntrees,
                            binomial=False, x_cols=list(x), feat_domains=domains,
                            f0=0.0, learn_rate=1.0, distribution="multinomial",
                            **self._cat_output()),
            )

        trees: list[Tree] = []
        if cp is not None:
            if cp.output.get("trees") is None:
                # the reverse of the guard above: a double-trees (or
                # multinomial-layout) checkpoint cannot continue as a
                # single-tree forest — refusing beats silently dropping
                # every checkpointed tree
                raise ValueError(
                    "checkpoint was trained with binomial_double_trees; "
                    "the tree layouts are incompatible")
            trees = list(cp.output["trees"])
        done = len(trees)
        keys = jax.random.split(key, ntrees * 3).reshape(ntrees, 3, 2)[done:]
        # deadline checkpoint at the dispatch boundary (see the multinomial
        # branch above): cancel BEFORE the fused forest program launches
        job.update(0.1, f"growing {ntrees - done} trees (one fused program)")
        _, heap, _, _ = _boost_scan(
            binned, edges, yc, w, fmask,
            jnp.zeros(binned.shape[0], jnp.float32), keys,
            dist="gaussian", depth=int(p["max_depth"]), n_bins=self._n_bins,
            col_rate=mtries / F, sample_rate=float(p["sample_rate"]),
            col_tree_rate=1.0, min_rows=float(p["min_rows"]), reg_lambda=0.0,
            reg_alpha=0.0, gamma=0.0,
            min_split_improvement=float(p["min_split_improvement"]),
            lr=1.0, bootstrap=True, drf=True, nclass=0,
            cat_feats=self._cat_feats, bins_used=self._bins_used)
        heap = _heap_to_host(heap)
        trees += [_trees_from_stacked(heap, m) for m in range(ntrees - done)]
        try:
            job.update(0.9, f"{len(trees)} trees grown")
        except JobCancelled:
            # forest is complete by the time the deadline is observable —
            # keep it; the job still terminates CANCELLED
            job.keep_partial()

        model = DRFModel(
            key=make_model_key(self.algo, self.model_id),
            params=self.params, data_info=None, response_column=y,
            response_domain=yvec.domain if classifier else None,
            output=dict(trees=trees, edges=edges, ntrees=len(trees),
                        binomial=classifier, x_cols=list(x), feat_domains=domains,
                        f0=0.0, learn_rate=1.0, distribution="gaussian",
                        **self._cat_output()),
        )
        self._maybe_calibrate(model)
        return model
