"""The GLM cell's parts, by hand on the CPU: the generator's marginals and
its ``ideal_score``, the plain reference against a fit worked by hand, the
Gram's floor at the cell's shape, the configuration's arithmetic."""

import json
import os

import numpy as np
import pytest

from benchmark import plugins, roofline, roofline_glm
from benchmark.reference import glm_irls_numpy as ref
from benchmark.reference.auc import auc

GEN = plugins.load("generators", "airline_delay")
RESPONSE = "dep_delayed_15min"
ROWS = 200_000


def host(frame):
    return {n: np.asarray(frame.vec(n).data)[: frame.nrows] for n in frame.names}


@pytest.fixture(scope="module")
def frame():
    return GEN.make(12345, 0, {"rows": ROWS, "response": RESPONSE})


def test_the_marginals_are_the_constants(frame):
    cols = host(frame)
    cards = GEN.cardinalities(ROWS)
    assert cards == (12, 31, 7, 22, 300, 300)
    probs, effects = GEN.constants(cards)
    for (name, *_), card, p, eff in zip(GEN.CATEGORICALS, cards, probs, effects):
        assert frame.vec(name).domain == GEN.domain(name, card)
        freq = np.bincount(cols[name], minlength=card) / ROWS
        # four binomial standard deviations, and no level is empty
        assert np.all(np.abs(freq - p) < 4 * np.sqrt(p / ROWS) + 1e-9), name
        assert freq.min() > 0 and eff[0] == 0.0
    # Zipf, exponent 1: the commonest airport 300 times the rarest
    assert probs[4].max() / probs[4].min() == pytest.approx(300)
    dep = cols["DepTime"]
    assert dep.min() >= 500 and dep.max() <= 2359 and np.all(dep % 100 < 60)
    dist = cols["Distance"]
    assert dist.min() >= 30 and dist.max() <= 4960
    assert np.median(dist) < dist.mean()                    # skewed to the right


def test_the_response_is_drawn_from_ideal_score(frame):
    cols = host(frame)
    score = GEN.ideal_score([cols[n] for n in GEN.NAMES])
    y = cols[RESPONSE]
    p = 1.0 / (1.0 + np.exp(-score.astype(np.float64)))
    assert abs(y.mean() - p.mean()) < 4 * np.sqrt(0.16 / ROWS)
    assert 0.18 < y.mean() < 0.22                           # near 20% positive
    assert 0.69 < auc(y, score) < 0.72
    # calibrated by decile of the score
    order = np.argsort(score)
    for part in np.array_split(order, 10):
        assert abs(y[part].mean() - p[part].mean()) < 5 * np.sqrt(0.25 / len(part))


def test_seed_fold_cap_and_domain_order(frame):
    data = {"rows": 8192, "response": RESPONSE}
    a, b = host(GEN.make(7, 0, data)), host(GEN.make(7, 0, data))
    assert all(np.array_equal(a[n], b[n], equal_nan=True) for n in a)
    c = host(GEN.make(7, 2, data))
    assert not np.array_equal(a["Origin"], c["Origin"])
    # the rehearsal's cap: rows // 200 levels at most, none empty
    small = GEN.make(7, 0, data)
    assert len(small.vec("Origin").domain) == 40
    assert np.bincount(a["Origin"], minlength=40).min() > 0
    # a held-out frame takes the training frame's levels, and may be written
    # with its domains reversed: the same rows under other codes
    held = GEN.make(7, 2, dict(data, rows=2048, levels_for_rows=8192))
    rev = GEN.make(7, 2, dict(data, rows=2048, levels_for_rows=8192,
                              domain_order="reversed"))
    assert len(held.vec("Dest").domain) == 40
    assert rev.vec("Dest").domain == held.vec("Dest").domain[::-1]
    h, r = host(held), host(rev)
    assert np.array_equal(h["Dest"], 39 - r["Dest"])
    assert np.array_equal(h["DepTime"], r["DepTime"])


def test_the_reference_against_a_fit_worked_by_hand():
    """One two-level predictor and the intercept: the model is saturated, so
    the maximum-likelihood fit is the two cells' own logits."""
    codes = np.array([0] * 10 + [1] * 20)
    y = np.array([1] * 3 + [0] * 7 + [1] * 12 + [0] * 8, float)
    d = ref.design([codes], [("a", "b")], ["g"], [], [])
    assert d.names == ["g.b"] and d.X.shape == (30, 2)
    fit = ref.fit(d, y, beta_epsilon=1e-12, objective_epsilon=0.0)
    logit = lambda p: np.log(p / (1 - p))
    assert fit.coef[1] == pytest.approx(logit(0.3), abs=1e-10)
    assert fit.coef[0] == pytest.approx(logit(0.6) - logit(0.3), abs=1e-10)
    by_hand = -2 * (3 * np.log(.3) + 7 * np.log(.7) + 12 * np.log(.6) + 8 * np.log(.4))
    assert fit.deviance == pytest.approx(by_hand, rel=1e-12)
    # the program's stopping rule ends earlier and within its tolerance
    early = ref.fit(d, y)
    assert early.iterations < fit.iterations
    assert np.max(np.abs(early.beta - fit.beta)) < 1e-4


def test_the_reference_standardises_as_datainfo_does():
    rng = np.random.default_rng(0)
    x = rng.normal(50.0, 9.0, 400)
    x[::50] = np.nan
    y = (rng.random(400) < 0.4).astype(float)
    d = ref.design([], [], [], [x], ["x"])
    col = np.asarray(d.X[:, 0].todense()).ravel()
    ok = ~np.isnan(x)
    assert d.sd[0] == pytest.approx(x[ok].std(ddof=1))        # n - 1
    assert np.all(col[~ok] == 0.0)                            # the mean, standardised
    fit = ref.fit(d, y)
    back = ref.standardized(fit.coef, d)
    assert np.allclose(back, fit.beta, rtol=0, atol=1e-12)
    raw = np.where(ok, x, x[ok].mean())
    assert np.allclose(fit.coef[0] * raw + fit.coef[1], ref.eta_of(d, fit.beta))


def test_the_grams_floor_at_the_cells_shape():
    with open(os.path.join(plugins.HERE, "configs", "glm-airlines-onehot.json")) as f:
        cfg = json.load(f)
    rows, predictors = cfg["data"]["rows"], cfg["data"]["features"]
    assert rows % 1_000_000 == 0 and predictors == 8
    cards = GEN.cardinalities(rows)
    assert sum(c - 1 for c in cards) + 2 == cfg["data"]["expanded_columns"] == 668
    peak = roofline.peak_row("TPU v5 lite")
    ops, nbytes = roofline_glm.gram_iteration(rows, predictors)
    # eight predictors, the response and the weight, four bytes each; the
    # outer product of a row's nine non-zeros
    assert nbytes == rows * 40 and ops == 2 * rows * 81
    s, bound = roofline_glm.gram_floor(rows, predictors, 5, peak)
    assert bound == "memory" and s == pytest.approx(5 * rows * 40 / 819e9)
    # the dense formulation the program runs does 2 x rows x 668^2 in six
    # bf16 passes: over twenty thousand times the operations the floor counts
    assert 6 * 2 * rows * 668 ** 2 / ops > 20_000
