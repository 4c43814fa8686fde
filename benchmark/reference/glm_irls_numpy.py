"""Plain reference: binomial GLM by IRLS, numpy float64, no regularisation.

The ONE copy of the mathematics the GLM cells and ``tests/
test_glm_airlines_reference.py`` compare the program with. It follows
H2O-3's IRLSM (``hex/glm/GLM.java``) as ``h2o3_tpu/models/glm.py`` states it,
and shares no code with it:

- design: each categorical column one-hot WITHOUT its first level
  (``use_all_factor_levels`` false; a missing code, -1, is an all-zero
  block), then the numeric columns standardised as ``DataInfo`` defines it
  (minus the mean, over the SAMPLE standard deviation, n - 1; a missing value
  takes the mean), then the intercept. Held as a ``scipy.sparse`` CSR matrix:
  a row has one non-zero a categorical column, so X'WX costs rows x 9^2;
- start: beta 0, intercept logit(mean of (y + 0.5) / 2);
- a step: eta = X beta, mu = sigmoid(eta), W = mu(1 - mu), z = eta +
  (y - mu) / W, beta' = solve(X'WX, X'Wz) — float64 Cholesky, and NO ridge
  on the diagonal (the program adds 1e-5 x the mean diagonal: its departure,
  measured by the checks that use this file);
- stop, the program's rule: after the step whose largest coefficient move is
  under ``beta_epsilon``, or whose deviance (of the beta it started from)
  is within ``objective_epsilon`` (relative) of the step's before, or after
  ``max_iterations`` steps.

``lambda`` 0 only: with no penalty the fit does not depend on the
standardisation, so coefficients are compared de-standardised.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import scipy.linalg
import scipy.sparse as sp


@dataclasses.dataclass
class Design:
    X: sp.csr_matrix              # [rows, K + 1], the last column ones
    names: list[str]              # K coefficient names, the program's form
    num_start: int                # first numeric column
    fill: np.ndarray              # per numeric column: a missing value's
    mean: np.ndarray              # ... what is subtracted (0 unstandardised)
    sd: np.ndarray                # ... and divided by (1 unstandardised)


def design(cat_codes: list[np.ndarray], domains: list[tuple[str, ...]],
           cat_names: list[str], nums: list[np.ndarray], num_names: list[str],
           standardize: bool = True, like: "Design | None" = None) -> Design:
    """The one-hot design of ``rows`` rows. ``like``: a training design whose
    standardisation a scoring frame takes over."""
    rows = len(cat_codes[0]) if cat_codes else len(nums[0])
    row_idx, col_idx, vals, names = [], [], [], []
    k = 0
    for codes, dom, name in zip(cat_codes, domains, cat_names):
        codes = np.asarray(codes, np.int64)
        keep = np.flatnonzero(codes >= 1)
        row_idx.append(keep)
        col_idx.append(k + codes[keep] - 1)
        vals.append(np.ones(len(keep)))
        names += [f"{name}.{lvl}" for lvl in dom[1:]]
        k += max(len(dom) - 1, 0)
    num_start = k
    fills, mean, sd = [], [], []
    for j, (col, name) in enumerate(zip(nums, num_names)):
        col = np.asarray(col, np.float64)
        ok = ~np.isnan(col)
        if like is not None:
            fill, sub, s = like.fill[j], like.mean[j], like.sd[j]
        else:
            fill = col[ok].mean()
            s = col[ok].std(ddof=1) if standardize else 1.0
            s = s if s > 0 and np.isfinite(s) else 1.0
            sub = fill if standardize else 0.0
        fills.append(fill)
        mean.append(sub)
        sd.append(s)
        row_idx.append(np.arange(rows))
        col_idx.append(np.full(rows, k))
        vals.append((np.where(ok, col, fill) - sub) / s)
        names.append(name)
        k += 1
    row_idx.append(np.arange(rows))
    col_idx.append(np.full(rows, k))
    vals.append(np.ones(rows))
    X = sp.csr_matrix((np.concatenate(vals),
                       (np.concatenate(row_idx), np.concatenate(col_idx))),
                      shape=(rows, k + 1))
    return Design(X, names, num_start, np.array(fills), np.array(mean),
                  np.array(sd))


def sigmoid(eta: np.ndarray) -> np.ndarray:
    e = np.exp(-np.abs(eta))
    return np.where(eta >= 0, 1.0, e) / (1.0 + e)


def deviance(y: np.ndarray, eta: np.ndarray) -> float:
    """-2 log likelihood of a 0/1 response: 2 Σ log(1 + e^eta) - y eta."""
    return float(2.0 * (np.logaddexp(0.0, eta) - y * eta).sum())


def normal_equations(X: sp.csr_matrix, y: np.ndarray, beta: np.ndarray):
    """(X'WX dense, X'Wz, deviance at beta) of one IRLS step."""
    eta = X @ beta
    mu = sigmoid(eta)
    W = np.maximum(mu * (1.0 - mu), 1e-300)
    z = eta + (y - mu) / W
    XW = X.multiply(W[:, None]).tocsr()
    gram = np.asarray((X.T @ XW).todense())
    return gram, np.asarray(XW.T @ z).ravel(), deviance(y, eta)


@dataclasses.dataclass
class Fit:
    beta: np.ndarray              # K + 1, on the standardised scale
    coef: np.ndarray              # K + 1, de-standardised (intercept last)
    deviance: float               # at ``beta``
    iterations: int
    deviances: list[float]        # a step: the deviance of the beta it began at


def destandardize(beta: np.ndarray, d: Design) -> np.ndarray:
    coef = beta.copy()
    n = len(d.mean)
    s = d.num_start
    coef[s:s + n] = beta[s:s + n] / d.sd
    coef[-1] = beta[-1] - float((beta[s:s + n] / d.sd * d.mean).sum())
    return coef


def standardized(coef: np.ndarray, d: Design) -> np.ndarray:
    """The inverse of ``destandardize``: coefficients of the raw columns,
    on this design's scale."""
    beta = np.asarray(coef, np.float64).copy()
    n = len(d.mean)
    s = d.num_start
    beta[s:s + n] = coef[s:s + n] * d.sd
    beta[-1] = coef[-1] + float((coef[s:s + n] * d.mean).sum())
    return beta


def fit(d: Design, y: np.ndarray, max_iterations: int = 50,
        beta_epsilon: float = 1e-4, objective_epsilon: float = 1e-6) -> Fit:
    y = np.asarray(y, np.float64)
    beta = np.zeros(d.X.shape[1])
    mu0 = ((y + 0.5) / 2.0).mean()
    beta[-1] = np.log(mu0 / (1.0 - mu0))
    dev_prev, devs = np.inf, []
    for _ in range(int(max_iterations)):
        gram, rhs, dev = normal_equations(d.X, y, beta)
        new = scipy.linalg.cho_solve(scipy.linalg.cho_factor(gram), rhs)
        delta = np.max(np.abs(new - beta))
        beta = new
        devs.append(dev)
        stop = delta < beta_epsilon or (
            np.isfinite(dev_prev)
            and abs(dev_prev - dev) <= objective_epsilon * max(abs(dev_prev), 1.0))
        dev_prev = dev
        if stop:
            break
    return Fit(beta, destandardize(beta, d), deviance(y, d.X @ beta),
               len(devs), devs)


def eta_of(d: Design, beta: np.ndarray) -> np.ndarray:
    """Linear predictor of a design's rows under coefficients on its scale."""
    return np.asarray(d.X @ beta).ravel()


def from_frame(frame, response: str, like: "tuple | None" = None,
               standardize: bool = True):
    """(Design, domains, y) of a program ``Frame``: every column but ``response`` is a
    predictor, categorical columns first, in the frame's order (the layout
    ``DataInfo`` gives). The columns come to the host as codes and floats;
    nothing of the program's expansion is used. ``like``: the ``(Design,
    domains)`` of the training frame; a scoring frame's levels are then
    matched to the training domains BY NAME (an unseen level is missing)."""
    import jax
    names = [n for n in frame.names if n != response]
    vecs = [frame.vec(n) for n in names + [response]]
    host = [np.asarray(a)[: frame.nrows]
            for a in jax.device_get([v.data for v in vecs])]
    cat = [i for i, v in enumerate(vecs[:-1]) if v.is_categorical]
    num = [i for i, v in enumerate(vecs[:-1]) if not v.is_categorical]
    domains = [tuple(vecs[i].domain) for i in cat]
    codes = [host[i].astype(np.int64) for i in cat]
    if like is not None:
        train_design, train_domains = like
        for j, (dom, want) in enumerate(zip(domains, train_domains)):
            at = {lvl: k for k, lvl in enumerate(want)}
            lut = np.array([at.get(lvl, -1) for lvl in dom] + [-1], np.int64)
            codes[j] = lut[codes[j]]          # a missing code, -1, stays -1
        domains = list(train_domains)
    d = design(codes, domains, [names[i] for i in cat],
               [host[i] for i in num], [names[i] for i in num],
               standardize=standardize, like=like[0] if like else None)
    return d, domains, host[-1].astype(np.float64)
