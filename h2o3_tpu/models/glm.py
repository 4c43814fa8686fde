"""GLM — generalized linear models with IRLSM.

Reference: ``hex/glm/GLM.java:543,880,1335`` — per-IRLS-iteration the cluster
computes the weighted Gram matrix X'WX via ``GLMIterationTask``
(``hex/glm/GLMTask.java:1509``, a chunk-parallel MRTask), the leader solves by
Cholesky (``hex/gram/Gram.java:452-473``), and iterates to convergence
(``beta_epsilon``/``objective_epsilon``). Regularization: elastic net; L2 goes
into the Gram diagonal, L1 via ADMM (``hex/optimization/ADMM.java``).

TPU-native: the Gram contraction is one ``einsum`` over the row-sharded design
matrix — XLA reduces per-chip partials over ICI (exactly the MRTask tree reduce)
and the [K,K] solve happens replicated. The whole IRLS step is a single jitted
program; only the scalar deviance crosses to host for the convergence test.
L1 is handled by ADMM over the cached Cholesky factor, mirroring the reference.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import erfc

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.models.distributions import get_family
from h2o3_tpu.models.job import Job
from h2o3_tpu.ops.map_reduce import retrying
from h2o3_tpu.models.model_base import (Model, ModelBuilder, ModelParameters,
                                        make_model_key, megastep_k,
                                        publish_dispatch_audit)
from h2o3_tpu.utils import telemetry as _tm
from h2o3_tpu.utils.costs import accounted_jit
from h2o3_tpu.utils.timeline import timed_event


def _fam(family: str, tweedie_p: float):
    """``tweedie_p`` doubles as the family's auxiliary parameter: variance
    power for tweedie, dispersion theta for negativebinomial (one static
    slot through every jitted solver)."""
    if family == "tweedie":
        return get_family(family, p=tweedie_p)
    if family == "negativebinomial":
        return get_family(family, theta=tweedie_p)
    return get_family(family)


_HI = jax.lax.Precision.HIGHEST


def _eta(X, beta, off=0.0):
    """Linear predictor ``X·beta[:-1] + beta[-1] + off`` with the product at
    HIGHEST precision. At the default a TPU rounds both operands to bf16 (8
    bits) whenever the product goes to the MXU, which moves eta by
    ~4e-3·|beta|: the working response, the weights and the deviance would
    then be computed from another model than the float32 Gram solves for
    (with bf16 inputs the 668-wide airline fit ends 0.6 standard errors off,
    PERF.md PR 26). With ONE right-hand side XLA keeps the product on the
    vector unit in float32 whatever is asked, and a multinomial fit's [P, 5]
    coefficients compiled and ran the same at both settings (my chip runs,
    PR 26); stating the precision makes that the program's property and not
    the compiler's choice. It is bound by the read of X either way."""
    return jnp.matmul(X, beta[:-1], precision=_HI) + beta[-1] + off


def _weighted_gram(X, W, l2, nobs, jitter):
    """Left side of the normal equations for weighted LS with an unpenalized
    intercept column: ``gram = [X,1]'W[X,1] + diag(ridge)``, returned with
    ``ridge = l2*nobs*(1..1,0) + j``, the diagonal that was added.
    One contraction over the row-sharded X — XLA reduces per-chip partials over
    ICI (the reference's ``GLMIterationTask`` Gram reduce).

    Contractions run at HIGHEST precision: the TPU MXU's default bf16 inputs
    lose ~1e-2 relative on the Gram, which breaks the Cholesky on
    ill-conditioned designs (the solve is [K,K] — full f32 costs nothing).

    ``j = jitter * (mean diagonal + 1)`` is a ridge towards 0 on every
    coefficient, the intercept too. It keeps collinear designs (e.g. a
    RuleFit rule matrix with complementary 0/1 rules) factorizable, and it is
    what keeps an unpenalised fit of separable data finite (RuleFit's L1 is
    applied after the IRLS phase): both lean on it being a true ridge, so it
    stays one. Its price is a departure from plain IRLS wherever a direction
    of the design is weak: at the airline design (smallest eigenvalue 0.2
    against j = 0.019) it moves the intercept, and the Origin coefficients
    against it, by 1.0e-2, 0.08 standard errors (PERF.md, PR 26, with the
    tolerance it consumes in ``benchmark/checks/glm_coef_vs_reference.py``).
    As a proximal term (a step's right-hand side without ``j*beta``) it would
    cost nothing there, and lets coefficients run to 4e6 on RuleFit's
    separable rules.
    """
    k = X.shape[1]
    Xw = X * W[:, None]
    gram = jnp.empty((k + 1, k + 1), X.dtype)
    gram = gram.at[:k, :k].set(jnp.matmul(Xw.T, X, precision=_HI))
    xw_sum = Xw.sum(axis=0)
    gram = gram.at[:k, k].set(xw_sum).at[k, :k].set(xw_sum).at[k, k].set(W.sum())
    penalty = l2 * nobs * jnp.concatenate([jnp.ones(k), jnp.zeros(1)])
    ridge = penalty + jitter * (jnp.trace(gram) / (k + 1) + 1.0)
    return gram + jnp.diag(ridge), ridge


def _weighted_rhs(X, W, z):
    """``[X,1]'Wz``: the right side of the normal equations where ``z`` is the
    working response, the log-likelihood's gradient where it is the working
    residual."""
    return jnp.concatenate([jnp.matmul((X * W[:, None]).T, z, precision=_HI),
                            (W * z).sum()[None]])


def _nn_solve(gram, rhs, beta0, tol: float = 1e-7, max_passes: int = 100):
    """Non-negative solve of the penalized normal equations by cyclic projected
    coordinate descent (reference: ADMM.java solves the same bound-constrained
    QP; for a convex quadratic, projected CD converges to the NNLS optimum).
    The intercept (last coordinate) stays unconstrained; sweeps stop once the
    largest coordinate move falls below ``tol``."""
    k = gram.shape[0] - 1

    def coord(j, b):
        r = rhs[j] - gram[j] @ b
        bj = b[j] + r / jnp.maximum(gram[j, j], 1e-12)
        return b.at[j].set(jnp.where(j < k, jnp.maximum(bj, 0.0), bj))

    def body(state):
        i, b, _ = state
        nb = jax.lax.fori_loop(0, k + 1, coord, b)
        return i + 1, nb, jnp.max(jnp.abs(nb - b))

    _, beta, _ = jax.lax.while_loop(
        lambda s: (s[0] < max_passes) & (s[2] > tol), body,
        (0, beta0, jnp.asarray(jnp.inf, beta0.dtype)))
    return beta


@partial(jax.jit, static_argnames=("family", "tweedie_p", "non_negative"))
def _irls_step(family: str, tweedie_p: float, X, y, w, beta, l2,
               non_negative: bool = False, off=0.0):
    """One IRLS iteration: weighted Gram + Cholesky solve (all on device);
    under ``non_negative`` the same system is solved with projected CD.
    ``off`` is the per-row margin offset (reference offset_column: enters
    eta but is excluded from the working response the solve fits).

    Returns ``(new_beta, deviance, step_delta)`` — the convergence scalars
    are computed ON DEVICE so the host loop fetches both in one transfer
    (graftlint TRC003: two separate device_gets per iteration doubled the
    host round-trips on the IRLS hot path)."""
    fam = _fam(family, tweedie_p)
    # jax.named_scope: metadata for a profile's op_name, no operation
    with jax.named_scope("eta"):
        eta = _eta(X, beta, off)
    with jax.named_scope("weights"):
        mu = fam.linkinv(eta)
        d = fam.dmu_deta(eta)
        var = fam.variance(mu)
        W = w * d * d / jnp.maximum(var, 1e-12)
        # the working response is eta - off + resid
        resid = (y - mu) / jnp.maximum(d, 1e-12)
        nobs = jnp.maximum(w.sum(), 1.0)
    with jax.named_scope("gram"):
        gram, ridge = _weighted_gram(X, W, l2, nobs, 1e-5)
        if non_negative:
            # the projected solver wants the system in b' itself
            rhs = _weighted_rhs(X, W, eta - off + resid)
        else:
            # the Newton step, gram @ (b' - beta) = gradient of the penalised
            # log-likelihood: gram @ b' = [X,1]'W(eta - off + resid) in exact
            # arithmetic, but a sum of terms that cancel and not the
            # difference of two sums of 1e5 that float32 rounds to 1e-2 each.
            # At a one-hot design whose dropped level is rare (condition
            # number 1e6) that rounding moved the intercept by up to 4e-3,
            # forty times what benchmark/checks/glm_coef_vs_reference.py
            # allows against the same ridge in float64 (PERF.md, PR 26)
            rhs = _weighted_rhs(X, W, resid) - ridge * beta
    with jax.named_scope("solve"):
        if non_negative:
            new_beta = _nn_solve(gram, rhs,
                                 jnp.maximum(beta, 0.0).at[-1].set(beta[-1]))
        else:
            chol = jax.scipy.linalg.cho_factor(gram, lower=True)
            new_beta = beta + jax.scipy.linalg.cho_solve(chol, rhs)
    with jax.named_scope("deviance"):
        dev = (w * fam.deviance(y, mu)).sum()
    return new_beta, dev, jnp.max(jnp.abs(new_beta - beta))


# the host-dispatched IRLS program — registered with the compute
# observatory (utils/costs.py): per-signature compile time + cost_analysis
# FLOPs/bytes land in /3/Compute, and a shape-changed rebuild records a
# recompile event naming the changed dimension
@accounted_jit("glm:irls_megastep", loop="glm_irls",
               static_argnames=("family", "tweedie_p", "non_negative",
                                "k", "has_bounds"))
def _irls_megastep(family: str, tweedie_p: float, X, y, w, beta, l2, k: int,
                   it0, max_it, beta_eps, obj_eps, dev_prev0,
                   non_negative: bool = False, off=0.0, lo=None, hi=None,
                   has_bounds: bool = False):
    """Up to ``k`` IRLS iterations in ONE compiled dispatch, with the
    convergence predicate evaluated ON DEVICE — the host fetches the
    per-step deviances + step count once per megastep instead of blocking
    on (dev, delta) every iteration (the FireCaffe lesson: no host
    round-trip between steps). Semantics are step-for-step identical to the
    per-iteration driver: once the predicate fires (or ``max_it`` global
    iterations are reached) the carry freezes, so iteration counts,
    deviance history, and coefficients match the old loop exactly.

    Returns ``(beta, devs[k], ran[k], done)``: ``ran`` marks which steps
    executed (``devs`` is NaN on unexecuted slots), ``done`` = converged.
    A ``lax.while_loop`` (not a frozen scan) so convergence mid-megastep
    stops COMPUTING, not just updating — the per-iteration cost must drop
    even on CPU, where the Gram dominates and wasted post-convergence
    steps would eat the round-trip savings.
    """
    def cond(state):
        _, _, it, i, done, _, _ = state
        return (~done) & (i < k) & (it < max_it)

    def body(state):
        beta, dev_prev, it, i, done, devs, ran = state
        beta_new, dev, delta = _irls_step(family, tweedie_p, X, y, w, beta,
                                          l2, non_negative=non_negative,
                                          off=off)
        if has_bounds:
            # projected Newton, as in the host driver: clip into the box,
            # re-measure the step against the projected point
            beta_new = jnp.clip(beta_new, lo, hi)
            delta = jnp.max(jnp.abs(beta_new - beta))
        stop = delta < beta_eps
        if family == "gaussian" and not non_negative:
            # weighted LS solves exactly in one step; the second confirms
            stop = stop | (it >= 1)
        stop = stop | (jnp.isfinite(dev_prev)
                       & (jnp.abs(dev_prev - dev)
                          <= obj_eps * jnp.maximum(jnp.abs(dev_prev), 1.0)))
        return (beta_new, dev, it + 1, i + 1, stop,
                devs.at[i].set(dev), ran.at[i].set(True))

    state = (beta, jnp.asarray(dev_prev0, jnp.float32),
             jnp.asarray(it0, jnp.int32), jnp.asarray(0, jnp.int32),
             jnp.asarray(False), jnp.full(k, jnp.nan, jnp.float32),
             jnp.zeros(k, bool))
    beta, _, _, _, done, devs, ran = jax.lax.while_loop(cond, body, state)
    return beta, devs, ran, done


@partial(jax.jit, static_argnames=("family", "tweedie_p"))
def _l1_threshold(family: str, tweedie_p: float, X, y, w, beta, lam1, lam2,
                  off=0.0):
    """Per-coefficient proximal threshold lam1*nobs/(gram_jj + lam2*nobs)."""
    fam = _fam(family, tweedie_p)
    eta = _eta(X, beta, off)
    d = fam.dmu_deta(eta)
    W = w * d * d / jnp.maximum(fam.variance(fam.linkinv(eta)), 1e-12)
    nobs = jnp.maximum(w.sum(), 1.0)
    gram_diag = (W[:, None] * X * X).sum(axis=0) + lam2 * nobs
    return lam1 * nobs / jnp.maximum(gram_diag, 1e-12)


def _wald_inference(family: str, tw: float, X, yy, w, beta, dev: float,
                    off=0.0):
    """Wald standard errors / z / p per coefficient (reference: GLM.java
    ``computePValues`` — inverse information matrix at the MLE; dispersion
    estimated for gaussian/gamma/tweedie, fixed 1 for binomial/poisson)."""
    fam = _fam(family, tw)
    eta = _eta(X, beta, off)
    d = fam.dmu_deta(eta)
    var = fam.variance(fam.linkinv(eta))
    W = w * d * d / jnp.maximum(var, 1e-12)
    nobs = jnp.maximum(w.sum(), 1.0)
    gram, _ = _weighted_gram(X, W, 0.0, nobs, 1e-8)
    inv = jnp.linalg.inv(gram)
    n_eff = float(jax.device_get((w > 0).sum()))
    pdim = X.shape[1] + 1
    phi = (dev / max(n_eff - pdim, 1.0)
           if family in ("gaussian", "gamma", "tweedie") else 1.0)
    cov = np.asarray(jax.device_get(inv), np.float64) * phi
    se = np.sqrt(np.clip(np.diag(cov), 0.0, None))
    z = np.asarray(jax.device_get(beta), np.float64) / np.maximum(se, 1e-30)
    p = erfc(np.abs(z) / np.sqrt(2.0))
    return se, z, p, cov


@partial(jax.jit, static_argnames=("family", "tweedie_p"))
def _deviance_at(family: str, tweedie_p: float, X, y, w, beta, off=0.0):
    fam = _fam(family, tweedie_p)
    mu = fam.linkinv(_eta(X, beta, off))
    return (w * fam.deviance(y, mu)).sum()


@partial(jax.jit, static_argnames=("family", "tweedie_p"))
def _null_deviance(family: str, tweedie_p: float, y, w):
    fam = _fam(family, tweedie_p)
    mu0 = jnp.full_like(y, (w * y).sum() / jnp.maximum(w.sum(), 1e-30))
    return (w * fam.deviance(y, mu0)).sum()


@partial(jax.jit, static_argnames=("family", "nclasses", "tweedie_p"))
def _glm_score(family: str, nclasses: int, tweedie_p: float, X, beta,
               off=0.0):
    if family == "multinomial":
        return jax.nn.softmax(_eta(X, beta), axis=1)
    fam = _fam(family, tweedie_p)
    mu = fam.linkinv(_eta(X, beta, off))
    if nclasses == 2:
        return jnp.stack([1.0 - mu, mu], axis=1)
    return mu


@partial(jax.jit, static_argnames=("nclasses", "non_negative"))
def _multinomial_step(nclasses: int, X, yoh, w, B, l2, l1, non_negative: bool = False):
    """One sweep of per-class quadratic (IRLS) updates for softmax regression.

    Reference: GLM.java multinomial solves class-blocks cyclically with the
    binomial-style working response per class (``GLMTask.GLMMultinomial*``).
    B: [P+1, K] (last row = intercepts). The class loop unrolls in the jit.
    L1 is applied as a per-class proximal soft-threshold with the same
    lam1*nobs/gram_jj units as the binomial ``_admm_l1`` path.
    """
    k_feat = X.shape[1]
    nobs = jnp.maximum(w.sum(), 1.0)
    for c in range(nclasses):
        eta = _eta(X, B)
        p = jax.nn.softmax(eta, axis=1)
        pc = p[:, c]
        W = w * jnp.maximum(pc * (1 - pc), 1e-10)
        z = eta[:, c] + (yoh[:, c] - pc) / jnp.maximum(pc * (1 - pc), 1e-10)
        gram, _ = _weighted_gram(X, W, l2, nobs, 1e-5)
        rhs = _weighted_rhs(X, W, z)
        if non_negative:
            bc = _nn_solve(gram, rhs, jnp.maximum(B[:, c], 0.0).at[-1].set(B[-1, c]))
        else:
            chol = jax.scipy.linalg.cho_factor(gram, lower=True)
            bc = jax.scipy.linalg.cho_solve(chol, rhs)
        thr = l1 * nobs / jnp.maximum(jnp.diag(gram)[:k_feat], 1e-12)
        bc = bc.at[:-1].set(jnp.sign(bc[:-1]) * jnp.maximum(jnp.abs(bc[:-1]) - thr, 0.0))
        B = B.at[:, c].set(bc)
    eta = _eta(X, B)
    logp = jax.nn.log_softmax(eta, axis=1)
    dev = -2.0 * (w * (yoh * logp).sum(axis=1)).sum()
    return B, dev


@accounted_jit("glm:multinomial_megastep", loop="glm_multinomial",
               static_argnames=("nclasses", "non_negative", "k"))
def _multinomial_megastep(nclasses: int, X, yoh, w, B, l2, l1, k: int,
                          it0, max_it, obj_eps, dev_prev0,
                          non_negative: bool = False):
    """Up to ``k`` cyclic per-class IRLS sweeps in ONE compiled dispatch;
    the deviance-plateau stopping test runs on device and the host fetches
    the per-step deviances once per megastep (same stop-computing-on-
    converge ``while_loop`` contract as :func:`_irls_megastep`)."""
    def cond(state):
        _, _, it, i, done, _, _ = state
        return (~done) & (i < k) & (it < max_it)

    def body(state):
        B, dev_prev, it, i, done, devs, ran = state
        B_new, dev = _multinomial_step(nclasses, X, yoh, w, B, l2, l1,
                                       non_negative)
        stop = (jnp.isfinite(dev_prev)
                & (jnp.abs(dev_prev - dev)
                   <= obj_eps * jnp.maximum(jnp.abs(dev_prev), 1.0)))
        return (B_new, dev, it + 1, i + 1, stop,
                devs.at[i].set(dev), ran.at[i].set(True))

    state = (B, jnp.asarray(dev_prev0, jnp.float32),
             jnp.asarray(it0, jnp.int32), jnp.asarray(0, jnp.int32),
             jnp.asarray(False), jnp.full(k, jnp.nan, jnp.float32),
             jnp.zeros(k, bool))
    B, _, _, _, done, devs, ran = jax.lax.while_loop(cond, body, state)
    return B, devs, ran, done


class GLMModel(Model):
    algo = "glm"

    def _score_raw(self, frame) -> jax.Array:
        if self.output.get("sparse"):
            from h2o3_tpu.frame.sparse import SparseFrame
            if not isinstance(frame, SparseFrame):
                raise ValueError("this GLM was trained on a SparseFrame; "
                                 "score SparseFrame inputs")
            beta = self.output["beta"]
            eta = frame.X.matvec(beta[:-1]) + beta[-1]
            fam = self.params["family"]
            if fam == "binomial":
                mu = jax.nn.sigmoid(eta)
                return jnp.stack([1.0 - mu, mu], axis=1)
            if fam == "poisson":
                return jnp.exp(jnp.clip(eta, -30, 30))
            return eta
        if self.params["family"] == "ordinal":
            X = self.data_info.expand(frame)
            eta = jnp.matmul(X, self.output["beta"], precision=_HI)
            theta = self.output["ordinal_theta"]
            cum = jax.nn.sigmoid(theta[None, :] - eta[:, None])
            cdf = jnp.concatenate(
                [jnp.zeros((X.shape[0], 1)), cum,
                 jnp.ones((X.shape[0], 1))], axis=1)
            return jnp.diff(cdf, axis=1)        # [n, J] class probabilities
        oc = self.params.get("offset_column")
        off = 0.0
        if oc:
            if oc not in frame:
                raise ValueError(f"scoring frame lacks offset column {oc!r}")
            import jax.numpy as _jnp
            off = _jnp.nan_to_num(frame.vec(oc).as_float(), nan=0.0)
        if self.params.get("interactions"):
            from h2o3_tpu.models.data_info import expand_interactions
            frame = expand_interactions(
                frame, self.params["interactions"],
                self.output.get("interaction_domains"))
        with timed_event("phase", f"{self.algo}:expand"):
            X = self.data_info.expand(frame)
        return _glm_score(self.params["family"], self.nclasses or 0,
                          float(self.params.get("theta", 1.0))
                          if self.params["family"] == "negativebinomial"
                          else float(self.params["tweedie_variance_power"]),
                          X, self.output["beta"], off)

    def coef(self):
        """Coefficients on the original scale (reference: GLMModel.coefficients()).
        Multinomial models return a per-class nested dict keyed
        ``coefs_class_K`` (the h2o-py multinomial ``coef()`` shape)."""
        return self._coef_dict(np.asarray(self.output["coef"]))

    def coef_norm(self):
        """Standardized coefficients (same multinomial nesting as ``coef``)."""
        return self._coef_dict(np.asarray(jax.device_get(self.output["beta"])))

    def _coef_dict(self, mat: np.ndarray):
        names = self.output["coef_names"] + ["Intercept"]
        if mat.ndim == 1:
            return dict(zip(names, mat))
        return {f"coefs_class_{k}": dict(zip(names, mat[:, k]))
            for k in range(mat.shape[1])}

    def coef_table(self):
        """Rows (name, coefficient, std_error, z_value, p_value) — the
        reference's coefficients table with Wald inference (needs
        ``compute_p_values=True``)."""
        if "p_values" not in self.output:
            raise ValueError("train with compute_p_values=True")
        names = self.output["coef_names"] + ["Intercept"]
        return [dict(name=n, coefficient=float(c), std_error=float(s),
                     z_value=float(z), p_value=float(p))
                for n, c, s, z, p in zip(
                    names, np.asarray(self.output["coef"]),
                    self.output["std_errs"], self.output["z_values"],
                    self.output["p_values"])]

    def get_regularization_path(self):
        """Lambda-search path (h2o-py ``getGLMRegularizationPath``): dicts of
        (lambda_, deviance, dev_explained, nonzero, beta)."""
        path = self.output.get("regularization_path")
        if path is None:
            raise ValueError("train with lambda_search=True")
        return path

    def varimp(self, use_pandas: bool = False):
        """Standardized-coefficient magnitudes per SOURCE column (reference:
        GLM variable importances = abs standardized coefs; one-hot levels of a
        categorical aggregate to the parent column)."""
        beta = np.abs(np.asarray(jax.device_get(self.output["beta"])))
        if beta.ndim == 2:                       # multinomial: sum over classes
            beta = beta.sum(axis=1)
        names = self.output["coef_names"]        # excludes Intercept (last)
        di = self.data_info
        rel: dict[str, float] = {c: 0.0 for c in di.cat_cols + di.num_cols}
        for name, b in zip(names, beta[:len(names)]):
            src = name.split(".", 1)[0] if name.split(".", 1)[0] in rel else name
            rel[src] = rel.get(src, 0.0) + float(b)
        mx = max(rel.values()) if rel and max(rel.values()) > 0 else 1.0
        tot = sum(rel.values()) or 1.0
        rows = sorted(((c, v, v / mx, v / tot) for c, v in rel.items()),
                      key=lambda r: -r[1])
        if use_pandas:
            import pandas as pd
            return pd.DataFrame(rows, columns=["variable", "relative_importance",
                                               "scaled_importance", "percentage"])
        return rows


class GLM(ModelBuilder):
    """h2o-py surface: ``H2OGeneralizedLinearEstimator``."""

    algo = "glm"

    def train(self, x=None, y=None, training_frame=None,
              validation_frame=None, weights=None):
        from h2o3_tpu.frame.sparse import SparseFrame
        if isinstance(training_frame, SparseFrame):
            # wide-sparse path: matrix-free IRLS-CG, no dense design
            from h2o3_tpu.models.glm_sparse import fit_sparse_glm
            from h2o3_tpu.utils.registry import DKV
            if x is not None:
                raise ValueError("column selection (x) is not supported on "
                                 "SparseFrame inputs — slice the COO instead")
            self.job = Job(f"glm-sparse on {training_frame.key or 'frame'}")

            def driver(j):
                model = fit_sparse_glm(self, j, training_frame,
                                       y or "C0", weights)
                if validation_frame is not None:
                    model.validation_metrics = model.model_performance(
                        validation_frame)
                DKV.put(model.key, model)
                return model

            self.job.run(driver)
            if self.job.status == Job.FAILED:
                raise self.job.exception
            self.model = self.job.result
            return self.model
        return super().train(x=x, y=y, training_frame=training_frame,
                             validation_frame=validation_frame,
                             weights=weights)

    def _scoring_history(self, model):
        """Per-IRLS-iteration rows (reference: ``GLM.java``
        ``ScoringHistory`` — iterations / negative_log_likelihood /
        objective; h2o-py's ``model.negative_log_likelihood()`` reads these
        column names)."""
        devs = getattr(self, "_iter_devs", None)
        if not devs:
            return None
        nobs = float(model.training_metrics.nobs) if getattr(
            model.training_metrics, "nobs", 0) else 1.0
        return self._history_table(
            model,
            [("iterations", "long", "%d"),
             ("negative_log_likelihood", "double", "%.5f"),
             ("objective", "double", "%.5f")],
            [[i + 1, d / 2.0, d / (2.0 * nobs)]
             for i, d in enumerate(devs)])

    @classmethod
    def defaults(cls) -> dict:
        return dict(
            super().defaults(),
            family="gaussian",        # AUTO resolved in _validate
            solver="IRLSM",
            alpha=0.0,                # elastic-net mix (L1 fraction)
            lambda_=0.0,              # regularization strength
            tweedie_variance_power=1.5,
            theta=1.0,                # negativebinomial dispersion
            standardize=True,
            use_all_factor_levels=False,
            intercept=True,
            non_negative=False,
            max_iterations=50,
            beta_epsilon=1e-4,
            objective_epsilon=1e-6,
            compute_p_values=False,
            lambda_search=False,
            nlambdas=30,
            lambda_min_ratio=1e-4,
            beta_constraints=None,    # {name: (lower, upper)} or h2o-frame
            #                           style [{"names","lower_bounds",...}]
            offset_column=None,       # per-row margin offset
            interactions=None,        # columns to cross (DataInfo interactions)
            # MeanImputation (default) | Skip | PlugValues (reference
            # GLMParameters.MissingValuesHandling)
            missing_values_handling="MeanImputation",
            # with PlugValues: {numeric_col: value} or a 1-row-frame DKV
            # key (reference _plug_values); categorical plugs not yet
            plug_values=None,
        )

    def _fit_ordinal(self, job: Job, frame, x, y, weights, yvec) -> "GLMModel":
        """Proportional-odds cumulative-logit fit (reference: GLM.java
        ordinal family, ``GLMModel.GLMParameters.Family.ordinal`` — the
        reference solves it by gradient descent too).

        P(y <= j) = sigmoid(theta_j - x·beta) with ordered thresholds
        theta_1 < ... < theta_{J-1} (parameterized theta_j = a + Σ
        softplus(d_i) so ordering is free); full-batch Adam inside one
        ``lax.scan``."""
        params = self.params
        if params.get("interactions") or params.get("offset_column"):
            raise ValueError("interactions/offset_column are not supported "
                             "for the ordinal family")
        di = self._make_data_info(frame, x)
        X = di.expand(frame)
        codes = yvec.data.astype(jnp.int32)
        valid = codes >= 0
        w = weights * valid
        yc = jnp.where(valid, codes, 0)
        J = yvec.cardinality()
        K = X.shape[1]
        lam = float(params["lambda_"])

        def unpack(p):
            beta, a, d = p[:K], p[K], p[K + 1:]
            theta = a + jnp.concatenate(
                [jnp.zeros(1), jnp.cumsum(jax.nn.softplus(d))])
            return beta, theta

        def nll(p):
            beta, theta = unpack(p)
            eta = jnp.matmul(X, beta, precision=_HI)
            cum = jax.nn.sigmoid(theta[None, :] - eta[:, None])   # [n, J-1]
            cdf = jnp.concatenate(
                [jnp.zeros((X.shape[0], 1)), cum,
                 jnp.ones((X.shape[0], 1))], axis=1)
            pj = jnp.take_along_axis(cdf, yc[:, None] + 1, 1)[:, 0] \
                - jnp.take_along_axis(cdf, yc[:, None], 1)[:, 0]
            nobs = jnp.maximum(w.sum(), 1.0)
            return (-(w * jnp.log(jnp.maximum(pj, 1e-12))).sum()
                    + lam * nobs * (beta * beta).sum()) / nobs

        p0 = jnp.zeros(K + J - 1, jnp.float32)
        iters = max(int(params["max_iterations"]), 1) * 20
        lr = 0.5

        @jax.jit
        def run(p0):
            grad = jax.grad(nll)

            def body(carry, _):
                p, m, v, t = carry
                g = grad(p)
                m = 0.9 * m + 0.1 * g
                v = 0.999 * v + 0.001 * g * g
                t = t + 1
                mh = m / (1 - 0.9 ** t)
                vh = v / (1 - 0.999 ** t)
                p = p - lr * mh / (jnp.sqrt(vh) + 1e-8)
                return (p, m, v, t), None

            (p, _, _, _), _ = jax.lax.scan(
                body, (p0, jnp.zeros_like(p0), jnp.zeros_like(p0), 0.0),
                None, length=iters)
            return p, nll(p)

        p, final = run(p0)
        job.update(0.9, f"ordinal nll {float(jax.device_get(final)):.5f}")
        beta, theta = unpack(p)
        # destandardize like the main path: coef_orig = beta_std * mul;
        # centering shifts the thresholds (theta absorbs x·sub terms)
        b = np.asarray(jax.device_get(beta), np.float64)
        coef = b.copy()
        th = np.asarray(jax.device_get(theta), np.float64)
        if params["standardize"] and di.num_cols:
            s0, nnum = di.ncats_expanded, len(di.num_cols)
            mul = di.num_mul.astype(np.float64)
            sub = di.num_sub.astype(np.float64)
            coef[s0:s0 + nnum] = b[s0:s0 + nnum] * mul
            th = th + float((b[s0:s0 + nnum] * mul * sub).sum())

        from h2o3_tpu.models.model_base import ModelParameters
        mparams = ModelParameters(params)
        mparams["family"] = "ordinal"
        model = GLMModel(
            key=make_model_key(self.algo, self.model_id),
            params=mparams, data_info=di, response_column=y,
            response_domain=yvec.domain,
            output=dict(beta=beta, coef=coef,
                        coef_names=di.coef_names,
                        ordinal_theta=theta, ordinal_theta_orig=th,
                        residual_deviance=2.0 * float(jax.device_get(final)),
                        iterations=iters, family="ordinal",
                        lambda_best=lam, regularization_path=None),
        )
        return model

    def _build_beta_bounds(self, di, params, family: str):
        """[lo, hi] per coefficient (+intercept) from ``beta_constraints``
        (reference: GLM BetaConstraints frame — names/lower_bounds/
        upper_bounds). Bounds are given on the ORIGINAL coefficient scale;
        with standardization they transform to the fitted scale
        (beta_std = beta_orig / num_mul)."""
        bc = params.get("beta_constraints")
        if not bc:
            return None
        if family == "multinomial":
            raise ValueError("beta_constraints are not supported for "
                             "multinomial (reference: GLM.java)")
        names = list(di.coef_names)
        items: dict[str, tuple] = {}
        if isinstance(bc, dict):
            for k, v in bc.items():
                items[k] = (v[0], v[1]) if isinstance(v, (tuple, list)) else (v, None)
        else:
            for row in bc:
                items[row["names"]] = (row.get("lower_bounds"),
                                       row.get("upper_bounds"))
        unknown = set(items) - set(names) - {"Intercept"}
        if unknown:
            raise ValueError(f"beta_constraints name unknown coefficients: "
                             f"{sorted(unknown)}")
        K = len(names)
        lo = np.full(K + 1, -np.inf, np.float64)
        hi = np.full(K + 1, np.inf, np.float64)
        for i, n in enumerate(names + ["Intercept"]):
            if n in items:
                l, u = items[n]
                lo[i] = -np.inf if l is None else float(l)
                hi[i] = np.inf if u is None else float(u)
        if params["standardize"] and di.num_cols:
            if "Intercept" in items and np.any(di.num_sub != 0):
                # original intercept = b_int - Σ b_j·mul_j·sub_j: a box on it
                # is not a box on the standardized intercept
                raise ValueError(
                    "an Intercept beta_constraint cannot be honored with "
                    "standardize=True over centered numeric columns; set "
                    "standardize=False")
            s0, nnum = di.ncats_expanded, len(di.num_cols)
            mul = di.num_mul.astype(np.float64)       # 1/sd, > 0
            lo[s0:s0 + nnum] = lo[s0:s0 + nnum] / mul
            hi[s0:s0 + nnum] = hi[s0:s0 + nnum] / mul
        return (jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32))

    def _irls_fit(self, job: Job, family, tw, X, yy, w, beta, lambda_: float,
                  params) -> tuple[jax.Array, float, int]:
        """IRLS to convergence at ONE lambda (reference: GLM.java IRLSM
        iteration loop); elastic-net L1 handled by the ADMM pass.

        The loop runs in K-step MEGASTEPS (``H2O3TPU_MEGASTEP_K``): one
        compiled dispatch carries up to K iterations with the convergence
        test on device, and the host blocks exactly ONCE per megastep to
        fetch the per-step deviances + how many steps actually ran — the
        fetch reconciles exact iteration counts for scoring history."""
        lam = lambda_ * (1.0 - float(params["alpha"]))
        k = megastep_k()
        nn = bool(params.get("non_negative"))
        bounds = getattr(self, "_beta_bounds", None)
        off = getattr(self, "_offset", 0.0)
        lo, hi = bounds if bounds is not None else (None, None)
        max_it = int(params["max_iterations"])
        beta_eps = float(params["beta_epsilon"])
        obj_eps = float(params["objective_epsilon"])
        dev_prev, dev, it_total, done = np.inf, np.inf, 0, False
        megasteps = 0
        while it_total < max_it and not done:
            t0 = time.time_ns()

            def _megastep(beta=beta, it_total=it_total, dev_prev=dev_prev):
                b, devs_d, ran_d, done_d = _irls_megastep(
                    family, tw, X, yy, w, beta, lam, k, it_total, max_it,
                    beta_eps, obj_eps, dev_prev, non_negative=nn, off=off,
                    lo=lo, hi=hi, has_bounds=bounds is not None)
                # the ONE blocking transfer per megastep — per-step deviances,
                # executed-step mask, converged flag together; this fetch IS
                # the convergence test
                devs, ran, done = map(  # graftlint: ok(one batched fetch per megastep)
                    np.asarray, jax.device_get((devs_d, ran_d, done_d)))
                return b, devs, ran, done

            with timed_event("iteration", f"{self.algo}:megastep"):
                # transient dispatch failures retry with backoff (the
                # megastep is functional over beta — a re-run is exact)
                beta, devs, ran, done = retrying("glm_megastep", _megastep)
            megasteps += 1
            n = int(ran.sum())
            _tm.GLM_MEGASTEPS.inc()
            _tm.GLM_ITERATIONS.inc(n)
            steps = [float(d) for d in devs[:n]]
            dev = steps[-1] if steps else dev
            dev_prev = dev
            done = bool(done)
            it_total += n
            if hasattr(self, "_iter_devs"):
                self._iter_devs.extend(steps)
            # per-ITERATION latency: the megastep's wall time amortized over
            # the steps it carried (histogram count keeps matching iterations)
            dt = (time.time_ns() - t0) / 1e9
            for _ in range(max(n, 1)):
                _tm.ITER_SECONDS.labels(loop="glm_irls").observe(
                    dt / max(n, 1))
            job.update(it_total / max_it,
                       f"iter {it_total - 1} deviance {dev:.4f}")
        it = max(it_total - 1, 0)
        publish_dispatch_audit(self, "glm_irls", iterations=max(it_total, 1),
                               host_syncs=megasteps,
                               device_dispatches=megasteps)
        if float(params["alpha"]) > 0 and lambda_ > 0:
            local = ModelParameters(params)
            local["lambda_"] = lambda_
            beta = self._admm_l1(family, tw, X, yy, w, beta, local)
            if bounds is not None:
                beta = jnp.clip(beta, bounds[0], bounds[1])
            dev = float(jax.device_get(_deviance_at(family, tw, X, yy, w,
                                                    beta, off)))
        return beta, dev, it

    def _lambda_search(self, job: Job, family, tw, X, yy, w, beta, params):
        """Regularization path with warm starts (reference: GLM.java lambda
        search / glmnet: geometric grid from lambda_max down; stop when the
        deviance-explained gain plateaus; ``getGLMRegularizationPath``)."""
        alpha = max(float(params["alpha"]), 1e-3)   # glmnet λmax convention
        mu_bar = (w * yy).sum() / jnp.maximum(w.sum(), 1e-30)
        lam_max = float(jax.device_get(
            jnp.max(jnp.abs(jnp.matmul(X.T, w * (yy - mu_bar),
                                       precision=_HI)))
            / jnp.maximum(w.sum(), 1e-30))) / alpha
        lam_max = max(lam_max, 1e-6)
        nlam = int(params["nlambdas"])
        ratio = float(params["lambda_min_ratio"])
        lambdas = lam_max * np.power(ratio, np.linspace(0, 1, nlam))
        null_dev = float(jax.device_get(_null_deviance(family, tw, yy, w)))
        path = []
        dev_prev, flat_steps = null_dev, 0
        for i, lam in enumerate(lambdas):
            beta, dev, it = self._irls_fit(job, family, tw, X, yy, w, beta,
                                           float(lam), params)
            # one batched fetch per lambda: nonzero count + coefficients
            nz, beta_h = jax.device_get(  # graftlint: ok(batched path fetch)
                ((jnp.abs(beta[:-1]) > 1e-8).sum(), beta))
            path.append(dict(lambda_=float(lam), deviance=dev,
                             dev_explained=1.0 - dev / max(null_dev, 1e-30),
                             nonzero=int(nz),
                             beta=np.asarray(beta_h)))
            # stop once extra shrinkage relief stops paying — but only after
            # SUSTAINED flatness: near lambda_max every step is flat because
            # beta is still ~0 (reference stops on devExplained plateau)
            if (dev_prev - dev) < 1e-4 * max(null_dev, 1e-30):
                flat_steps += 1
                if flat_steps >= 3 and path[i]["dev_explained"] > 0:
                    break
            else:
                flat_steps = 0
            dev_prev = dev
        best = min(path, key=lambda e: e["deviance"])
        beta = jnp.asarray(best["beta"])
        return beta, best["deviance"], 0, best["lambda_"], path

    def _make_data_info(self, frame: Frame, x) -> DataInfo:
        """DataInfo with the configured missing-value mode baked into the
        imputation vector: PlugValues overrides the per-column means the
        expander substitutes for NaN — at training AND scoring (reference
        GLM.java imputes with _plug_values wherever MeanImputation would
        use means)."""
        params = self.params
        di = DataInfo.make(frame, x, standardize=params["standardize"],
                           use_all_factor_levels=params["use_all_factor_levels"])
        if self._mvh_mode() != "plugvalues":
            if params.get("plug_values") is not None:
                # reference GLM.java errors on this mismatch — silent
                # mean-imputation would not be what the user configured
                raise ValueError("plug_values requires "
                                 "missing_values_handling='PlugValues'")
            return di
        plugs = params.get("plug_values")
        if isinstance(plugs, str):
            from h2o3_tpu.utils.registry import DKV
            pf = DKV[plugs]
            if pf.nrows != 1:
                raise ValueError(f"plug_values frame {plugs!r} must have "
                                 f"exactly 1 row, got {pf.nrows}")
            plugs = {c: pf.vec(c).to_numpy()[0] for c in pf.names}
        if not isinstance(plugs, dict) or not plugs:
            raise ValueError("missing_values_handling='PlugValues' needs "
                             "plug_values ({column: value} or a 1-row "
                             "frame key)")
        bad = [c for c in plugs if c in di.cat_cols]
        if bad:
            raise ValueError(f"categorical plug values not supported yet: "
                             f"{bad}")
        unknown = [c for c in plugs if c not in di.num_cols]
        if unknown:
            raise ValueError(f"plug_values name unknown numeric columns: "
                             f"{unknown}")
        def _coerce(v) -> float:
            # None / strings / non-numerics all fail the SAME way: as a
            # non-finite plug, caught below with a curated message
            try:
                return float(v)
            except (TypeError, ValueError):
                return float("nan")
        plugs = {c: _coerce(v) for c, v in plugs.items()}
        bad_vals = [c for c, v in plugs.items() if not np.isfinite(v)]
        if bad_vals:
            raise ValueError(f"plug_values must be finite numbers; got "
                             f"non-finite for {bad_vals}")
        means = np.array(di.num_means, np.float32).copy()
        for c, v in plugs.items():
            means[di.num_cols.index(c)] = float(v)
        di.num_means = means
        return di

    def _mvh_mode(self) -> str:
        """Canonical missing_values_handling (h2o-py sends lowercase enum
        forms like mean_imputation) — the ONE normalization site."""
        return str(self.params.get("missing_values_handling")
                   or "MeanImputation").replace("_", "").lower()

    def _fit(self, job: Job, frame: Frame, x, y, weights) -> GLMModel:
        params = self.params
        self._iter_devs = []    # per-IRLS-iteration deviances → scoring_history
        mvh = self._mvh_mode()
        self._metrics_weights = None
        if mvh == "skip":
            # rows with any NA among the used predictors drop out of the
            # fit (weight 0) — reference MissingValuesHandling.Skip; the
            # default path mean-imputes inside DataInfo.expand
            from h2o3_tpu.frame.types import VecType
            na = jnp.zeros(frame.plen, bool)
            for c in x:
                v = frame.vec(c)
                na = na | ((v.data < 0) if v.type is VecType.CAT
                           else jnp.isnan(v.data))
            had_weight = float(jnp.sum(weights)) > 0.0
            weights = weights * (~na)
            if float(jnp.sum(weights)) == 0.0:
                raise ValueError(
                    "missing_values_handling='Skip' removed every row "
                    "(all rows have at least one NA predictor)"
                    if had_weight else
                    "no rows carry training weight (check weights_column)")
            # metrics + CV must see the same reduced row set (model_base
            # reads this after _fit)
            self._metrics_weights = weights
        elif mvh not in ("meanimputation", "plugvalues"):
            raise ValueError(
                f"missing_values_handling {mvh!r} unsupported "
                "(MeanImputation | Skip | PlugValues)")
        if int(params["max_iterations"]) == -1:
            # reference: -1 means solver-chosen default (GLM.java auto)
            params["max_iterations"] = 50
        elif int(params["max_iterations"]) < 1:
            raise ValueError("max_iterations must be >= 1 (or -1 for auto)")
        yvec = frame.vec(y)
        family = params["family"]
        if yvec.is_categorical:
            if family == "ordinal":
                if yvec.cardinality() < 3:
                    raise ValueError("ordinal family needs >= 3 ordered levels")
                return self._fit_ordinal(job, frame, x, y, weights, yvec)
            # multinomial family is honored even for 2-level responses
            # (reference: GLM.java accepts multinomial on a binary y)
            if family == "multinomial" or yvec.cardinality() != 2:
                if family not in ("AUTO", "gaussian", "multinomial"):
                    raise ValueError(f"family {family!r} requires a binary or "
                                     "numeric response")
                return self._fit_multinomial_glm(job, frame, x, y, weights, yvec)
            family = "binomial" if family in ("gaussian", "AUTO") else family
        else:
            if family == "AUTO":
                family = "gaussian"
            if family in ("binomial", "bernoulli"):
                raise ValueError("binomial family requires a categorical (2-level) response")
            if family == "multinomial":
                raise ValueError("multinomial family requires a categorical response")
        tw = (float(params.get("theta", 1.0)) if family == "negativebinomial"
              else float(params["tweedie_variance_power"]))

        if params.get("interactions"):
            from h2o3_tpu.models.data_info import expand_interactions
            inter = list(params["interactions"])
            bad = set(inter) - set(frame.names)
            if bad:
                raise ValueError(f"interactions name unknown columns: "
                                 f"{sorted(bad)}")
            self._interaction_domains = {
                c: frame.vec(c).domain for c in inter
                if frame.vec(c).is_categorical}
            before = set(frame.names)
            frame = expand_interactions(frame, inter,
                                        self._interaction_domains)
            x = list(x) + [c for c in frame.names if c not in before]

        with timed_event("phase", f"{self.algo}:expand"):
            di = self._make_data_info(frame, x)
            X = di.expand(frame)
        _tm.GLM_EXPANDED_WIDTH.set(X.shape[1])
        from h2o3_tpu.models.data_info import response_as_float
        yy, valid = response_as_float(yvec)
        w = weights * valid
        yy = jnp.where(w > 0, yy, 0.0)

        fam = _fam(family, tw)
        mu0 = fam.initialize_mu(yy)
        k = X.shape[1]
        beta = jnp.zeros(k + 1, jnp.float32)
        beta = beta.at[-1].set(
            fam.link((w * mu0).sum() / jnp.maximum(w.sum(), 1e-30)))
        # needs the response alone: queued before the fit, fetched after it
        null_dev = _null_deviance(family, tw, yy, w)

        self._beta_bounds = self._build_beta_bounds(di, params, family)
        oc = params.get("offset_column")
        if oc:
            if family == "multinomial":
                raise ValueError("offset_column is not supported for "
                                 "multinomial")
            self._offset = jnp.nan_to_num(frame.vec(oc).as_float(), nan=0.0)
        else:
            self._offset = 0.0

        if bool(params.get("lambda_search")):
            beta, dev, it, lambda_best, reg_path = self._lambda_search(
                job, family, tw, X, yy, w, beta, params)
        else:
            with timed_event("phase", f"{self.algo}:irls"):
                beta, dev, it = self._irls_fit(job, family, tw, X, yy, w,
                                               beta, float(params["lambda_"]),
                                               params)
            lambda_best, reg_path = float(params["lambda_"]), None

        # destandardize for reporting: X_std = (x - sub) * mul
        b = np.asarray(jax.device_get(beta), np.float64)
        coef = b.copy()
        if params["standardize"] and di.num_cols:
            nnum = len(di.num_cols)
            mul, sub = di.num_mul.astype(np.float64), di.num_sub.astype(np.float64)
            coef[di.ncats_expanded:-1] = b[di.ncats_expanded:-1] * mul
            coef[-1] = b[-1] - float((b[di.ncats_expanded:di.ncats_expanded + nnum] * mul * sub).sum())

        null_dev = float(jax.device_get(null_dev))
        from h2o3_tpu.models.model_base import ModelParameters
        mparams = ModelParameters(self.params)   # snapshot: builder stays reusable
        mparams["family"] = family
        output = dict(beta=beta, coef=coef, coef_names=di.coef_names,
                      residual_deviance=dev, null_deviance=null_dev,
                      iterations=it + 1, family=family,
                      lambda_best=lambda_best, regularization_path=reg_path,
                      interaction_domains=getattr(
                          self, "_interaction_domains", None))
        if bool(params.get("compute_p_values")):
            if float(params["lambda_"]) > 0 or bool(params.get("lambda_search")):
                raise ValueError("compute_p_values requires no regularization "
                                 "(reference: GLM.java p-values need lambda=0)")
            se, zv, pv, cov = _wald_inference(family, tw, X, yy, w, beta,
                                              dev, self._offset)
            if params["standardize"] and di.num_cols:
                # SEs must be on the same (de-standardized) scale as `coef`:
                # se_orig[num] = se_std[num] * mul; intercept via the delta
                # method on b_int - sum_j b_j*mul_j*sub_j using the full cov.
                s0, nnum = di.ncats_expanded, len(di.num_cols)
                se = se.copy()
                se[s0:s0 + nnum] *= mul
                a = np.zeros(len(b))
                a[-1] = 1.0
                a[s0:s0 + nnum] = -(mul * sub)
                se[-1] = float(np.sqrt(max(a @ cov @ a, 0.0)))
                zv = coef / np.maximum(se, 1e-30)
                pv = erfc(np.abs(zv) / np.sqrt(2.0))
            output.update(std_errs=se, z_values=zv, p_values=pv)
        model = GLMModel(
            key=make_model_key(self.algo, self.model_id),
            params=mparams,
            data_info=di,
            response_column=y,
            response_domain=yvec.domain if yvec.is_categorical else None,
            output=output,
        )
        return model

    def _fit_multinomial_glm(self, job: Job, frame: Frame, x, y, weights, yvec
                             ) -> GLMModel:
        """Softmax regression via cyclic per-class IRLS blocks (reference:
        GLM.java multinomial path)."""
        params = self.params
        if params.get("interactions") or params.get("offset_column"):
            raise ValueError("interactions/offset_column are not supported "
                             "for multinomial")
        di = self._make_data_info(frame, x)
        X = di.expand(frame)
        from h2o3_tpu.models.data_info import response_as_float
        yy, valid = response_as_float(yvec)
        w = weights * valid
        K = yvec.cardinality()
        yoh = jax.nn.one_hot(jnp.where(w > 0, yy, 0.0).astype(jnp.int32), K)
        yoh = yoh * (w > 0)[:, None]

        P = X.shape[1]
        B = jnp.zeros((P + 1, K), jnp.float32)
        lam = float(params["lambda_"]) * (1.0 - float(params["alpha"]))
        lam1 = float(params["lambda_"]) * float(params["alpha"])
        nn = bool(params.get("non_negative"))
        k = megastep_k()
        max_it = int(params["max_iterations"])
        obj_eps = float(params["objective_epsilon"])
        dev_prev, dev, it_total, done = np.inf, np.inf, 0, False
        megasteps = 0
        while it_total < max_it and not done:
            t0 = time.time_ns()

            def _megastep(B=B, it_total=it_total, dev_prev=dev_prev):
                B2, devs_d, ran_d, done_d = _multinomial_megastep(
                    K, X, yoh, w, B, jnp.float32(lam), jnp.float32(lam1), k,
                    it_total, max_it, obj_eps, dev_prev, non_negative=nn)
                # ONE blocking fetch per K-step megastep — the per-step
                # deviance series IS the stopping test
                devs, ran, done = map(  # graftlint: ok(one batched fetch per megastep)
                    np.asarray, jax.device_get((devs_d, ran_d, done_d)))
                return B2, devs, ran, done

            with timed_event("iteration", "glm_multinomial"):
                B, devs, ran, done = retrying("glm_megastep", _megastep)
            megasteps += 1
            n = int(ran.sum())
            steps = [float(d) for d in devs[:n]]
            dev = steps[-1] if steps else dev
            dev_prev = dev
            done = bool(done)
            it_total += n
            dt = (time.time_ns() - t0) / 1e9
            for _ in range(max(n, 1)):
                _tm.ITER_SECONDS.labels(loop="glm_multinomial").observe(
                    dt / max(n, 1))
            job.update(it_total / max_it,
                       f"iter {it_total - 1} deviance {dev:.4f}")
        it = max(it_total - 1, 0)
        publish_dispatch_audit(self, "glm_multinomial",
                               iterations=max(it_total, 1),
                               host_syncs=megasteps,
                               device_dispatches=megasteps)

        # destandardized per-class coefficients
        b = np.asarray(jax.device_get(B), np.float64)
        coef = b.copy()
        if params["standardize"] and di.num_cols:
            nnum = len(di.num_cols)
            s = di.ncats_expanded
            mul, sub = di.num_mul.astype(np.float64), di.num_sub.astype(np.float64)
            coef[s:s + nnum, :] = b[s:s + nnum, :] * mul[:, None]
            coef[-1, :] = b[-1, :] - (b[s:s + nnum, :] * (mul * sub)[:, None]).sum(axis=0)

        from h2o3_tpu.models.model_base import ModelParameters
        mparams = ModelParameters(self.params)
        mparams["family"] = "multinomial"
        return GLMModel(
            key=make_model_key(self.algo, self.model_id),
            params=mparams, data_info=di, response_column=y,
            response_domain=yvec.domain,
            output=dict(beta=B, coef=coef, coef_names=di.coef_names,
                        residual_deviance=dev, null_deviance=float("nan"),
                        iterations=it + 1, family="multinomial"),
        )

    def _admm_l1(self, family, tw, X, yy, w, beta, params):
        """L1 via proximal IRLS (simplified ADMM, reference hex/optimization/ADMM.java):
        iterate IRLS steps then soft-threshold non-intercept coefficients.

        Units: the IRLS normal equations carry an L2 term scaled by nobs
        (matching the per-observation lambda convention), so the proximal
        threshold for coefficient j is lam1 * nobs / gram_jj — dividing by the
        curvature keeps L1 and L2 in the same per-observation units."""
        lam1 = float(params["lambda_"]) * float(params["alpha"])
        lam2 = float(params["lambda_"]) * (1.0 - float(params["alpha"]))
        nn = bool(params.get("non_negative"))
        off = getattr(self, "_offset", 0.0)
        for _ in range(10):
            beta, _dev, _delta = _irls_step(family, tw, X, yy, w, beta, lam2,
                                            non_negative=nn, off=off)
            thr = _l1_threshold(family, tw, X, yy, w, beta, lam1, lam2, off)
            mag = jnp.abs(beta[:-1])
            beta = beta.at[:-1].set(jnp.sign(beta[:-1]) * jnp.maximum(mag - thr, 0.0))
        return beta
