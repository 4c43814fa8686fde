"""GLM on an airline-shaped frame against the benchmark's plain reference
(``benchmark/reference/glm_irls_numpy.py``: float64 IRLS, no jitter), at a
few thousand rows on the CPU; the spans, scopes and counters the GLM cell's
per-layer metrics read; ``DataInfo.expand`` against a numpy one-hot at the
cell's 300 levels. The same comparison decides ``correct`` on the chip at
2M rows (``benchmark/checks/glm_*.py``)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from benchmark.reference import glm_irls_numpy as ref
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.types import VecType
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.models import glm as glm_mod
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.models.glm import GLM
from h2o3_tpu.utils.telemetry import METRICS
from h2o3_tpu.utils.timeline import TIMELINE

RESPONSE = "dep_delayed_15min"
ROWS = 8192
PARAMS = dict(family="binomial", alpha=1.0, lambda_=0.0, standardize=True,
              max_iterations=50)
GEN = plugins.load("generators", "airline_delay")


def airline(seed=7, fold=0, **more):
    return GEN.make(seed, fold, dict(rows=ROWS, response=RESPONSE, **more))


@pytest.fixture(scope="module")
def fitted():
    """(frame, model, reference design, domains, y, reference fit)."""
    frame = airline()
    model = GLM(**PARAMS).train(y=RESPONSE, training_frame=frame)
    design, domains, y = ref.from_frame(frame, RESPONSE)
    return frame, model, design, domains, y, ref.fit(design, y)


def test_the_frame_has_the_sources_schema():
    frame = airline()
    cards = [len(frame.vec(n).domain) for n in GEN.NAMES[:6]]
    assert cards == [12, 31, 7, 22, 40, 40]      # 300 airports, capped
    assert GEN.cardinalities(1_000_000) == (12, 31, 7, 22, 300, 300)
    assert frame.vec(RESPONSE).domain == ("N", "Y")
    assert [frame.vec(n).is_categorical for n in GEN.NAMES] == [True] * 6 + [False] * 2


@pytest.mark.parametrize("what", ["coefficients", "deviance", "iterations"])
def test_glm_agrees_with_the_float64_reference(fitted, what):
    _frame, model, design, _domains, y, fit = fitted
    out = model.output
    assert list(out["coef_names"]) == design.names
    if what == "coefficients":
        beta = ref.standardized(np.asarray(out["coef"], np.float64), design)
        gram, _, _ = ref.normal_equations(design.X, y, fit.beta)
        se = np.sqrt(np.diag(np.linalg.inv(gram)))
        # the program's ridge jitter (1e-5 x the mean diagonal, on every
        # coefficient) is its departure from plain IRLS: 1.6e-3 standard
        # errors here, 7e-6 of excess deviance; float32 adds 1e-4 and 2e-8
        assert np.max(np.abs(beta - fit.beta) / se) < 5e-3
        excess = ref.deviance(y, ref.eta_of(design, beta)) - fit.deviance
        assert -1e-9 < excess < 5e-5
        # ... and against the same ridge worked in float64 nothing is left
        ridge = with_the_programs_ridge(design, y, fit.beta)
        assert np.max(np.abs(beta - ridge) / se) < 5e-4
    elif what == "deviance":
        assert out["residual_deviance"] == pytest.approx(fit.deviance, rel=2e-6)
    else:
        assert out["iterations"] == fit.iterations


def test_predict_on_a_frame_with_reversed_domains_agrees(fitted):
    _frame, model, design, domains, _y, fit = fitted
    held = airline(fold=2, levels_for_rows=ROWS, domain_order="reversed")
    assert held.vec("Origin").domain == domains[4][::-1]
    p1 = np.asarray(model.predict(held).vecs[-1].to_numpy()[:ROWS], np.float64)
    held_design, _d, _yh = ref.from_frame(held, RESPONSE, like=(design, domains))
    diff = np.log(p1) - np.log1p(-p1) - ref.eta_of(held_design, fit.beta)
    # the ridge jitter's departure shows here too: 1.1e-3 at most, 6e-5 RMS
    assert np.max(np.abs(diff)) < 4e-3 and np.sqrt(np.mean(diff ** 2)) < 3e-4


def with_a_rare_level(frame, column="Origin", keep=3):
    """``frame`` with all but ``keep`` rows of the column's last level moved
    to its commonest one."""
    cols = {n: np.asarray(frame.vec(n).data)[: frame.nrows] for n in frame.names}
    codes = cols[column].copy()
    last = len(frame.vec(column).domain) - 1
    rows = np.flatnonzero(codes == last)
    codes[rows[keep:]] = np.bincount(codes[codes >= 0]).argmax()
    vecs = [Vec.from_numpy(codes if n == column else cols[n],
                           VecType.CAT if frame.vec(n).is_categorical else VecType.NUM,
                           domain=frame.vec(n).domain) for n in frame.names]
    return Frame(list(frame.names), vecs)


def with_the_programs_ridge(design, y, start):
    """The program's ridge worked in float64: the chip check's own helper."""
    return plugins.load("checks", "_glm").programs_ridge(ref, design, y, start,
                                                        steps=8)[0]


def test_a_level_of_three_rows_and_what_the_jitter_costs():
    """At a level with three rows the ridge jitter shows: the coefficient is
    pulled towards 0 by what the same ridge gives in float64, a few parts in
    a thousand of itself, and the program is that ridge to float32."""
    frame = with_a_rare_level(airline())
    model = GLM(**PARAMS).train(y=RESPONSE, training_frame=frame)
    design, _domains, y = ref.from_frame(frame, RESPONSE)
    fit = ref.fit(design, y)
    rare = design.names.index("Origin.A039")
    assert design.X[:, rare].sum() == 3
    beta = ref.standardized(np.asarray(model.output["coef"], np.float64), design)
    ridge = with_the_programs_ridge(design, y, fit.beta)
    cost = abs(ridge[rare] - fit.beta[rare])
    assert 1e-4 * abs(fit.beta[rare]) < cost < 2e-2 * abs(fit.beta[rare])
    assert abs(beta[rare] - ridge[rare]) < 0.05 * cost + 2e-5


def counter(name):
    return sum(r["value"] for r in METRICS.snapshot() if r["name"] == name)


def test_spans_and_counters_count_once_an_iteration_and_a_megastep(monkeypatch):
    monkeypatch.delenv("H2O3TPU_MEGASTEP_K", raising=False)
    frame = airline()
    its0, mega0 = counter("h2o3_glm_iterations_total"), counter("h2o3_glm_megasteps_total")
    TIMELINE.clear()
    builder = GLM(**PARAMS)
    model = builder.train(y=RESPONSE, training_frame=frame)
    iterations = model.output["iterations"]
    megasteps = -(-iterations // 4)
    assert counter("h2o3_glm_iterations_total") - its0 == iterations
    assert counter("h2o3_glm_megasteps_total") - mega0 == megasteps
    assert builder._dispatch_audit["glm_irls"]["host_syncs"] == megasteps
    assert counter("h2o3_glm_expanded_width") == 11 + 30 + 6 + 21 + 39 + 39 + 2
    spans = [e["what"] for e in TIMELINE.snapshot()]
    # the training expansion and the training metrics' second one
    assert spans.count("glm:expand") == 2
    assert spans.count("glm:irls") == 1 and spans.count("glm:metrics") == 1
    assert spans.count("glm:megastep") == megasteps
    assert "glm_irls" not in spans        # the event took the <algo>:<phase> form
    # recorded as each closes: the metrics come after the fit's own span
    assert spans.index("glm:irls") < spans.index("glm:fit") < spans.index("glm:metrics")


def test_every_builder_opens_the_metrics_span():
    from h2o3_tpu.models.gbm import GBM
    TIMELINE.clear()
    GBM(ntrees=2, max_depth=2, seed=1).train(y=RESPONSE, training_frame=airline())
    assert [e["what"] for e in TIMELINE.snapshot()].count("gbm:metrics") == 1


@pytest.mark.parametrize("scope", ["eta", "weights", "gram", "solve", "deviance"])
def test_the_irls_step_names_its_parts(fitted, scope):
    text = glm_mod._irls_megastep.executables()[-1].as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(f"/{scope}/" in n and "_irls_step" in n for n in names), scope


@pytest.mark.parametrize("program", ["_irls_step", "_glm_score", "_deviance_at"])
def test_no_product_over_the_rows_runs_at_default_precision(program):
    """On a TPU a default-precision product rounds its inputs to bf16: every
    product with the design matrix states HIGHEST."""
    X, y, w = jnp.ones((16, 5)), jnp.ones(16), jnp.ones(16)
    beta = jnp.zeros(6)
    args = {"_irls_step": ("binomial", 1.5, X, y, w, beta, 0.0),
            "_glm_score": ("binomial", 2, 1.5, X, beta),
            "_deviance_at": ("binomial", 1.5, X, y, w, beta)}[program]
    fn = getattr(glm_mod, program)
    statics = tuple(i for i, a in enumerate(args) if not hasattr(a, "shape"))
    jaxpr = str(jax.make_jaxpr(fn, static_argnums=statics)(*args))
    precisions = re.findall(r"dot_general\[.*?precision=(\S+)", jaxpr, flags=re.S)
    assert precisions and all("HIGHEST" in p for p in precisions), precisions


def test_expand_at_300_levels_against_a_numpy_one_hot():
    rng = np.random.default_rng(3)
    rows, card = 5000, 300
    codes = rng.integers(-1, card, rows)            # -1: a missing level
    num = rng.normal(40.0, 7.0, rows)
    num[rng.random(rows) < 0.01] = np.nan
    domain = tuple(f"A{j:03d}" for j in range(card))
    frame = Frame(["Origin", "Distance"],
                  [Vec.from_numpy(codes, VecType.CAT, domain=domain),
                   Vec.from_numpy(num)])
    di = DataInfo.make(frame, ["Origin", "Distance"], standardize=True)
    X = np.asarray(di.expand(frame))[:rows]
    assert X.shape == (rows, card - 1 + 1) and di.ncols_expanded == card
    want = np.zeros((rows, card), np.float32)
    seen = codes >= 1                               # level 0 is dropped
    want[np.flatnonzero(seen), codes[seen] - 1] = 1.0
    mean, sd = np.nanmean(num), np.nanstd(num, ddof=1)
    want[:, -1] = (np.where(np.isnan(num), mean, num) - mean) / sd
    np.testing.assert_array_equal(X[:, :-1], want[:, :-1])
    np.testing.assert_allclose(X[:, -1], want[:, -1], rtol=2e-5, atol=2e-6)
    assert di.coef_names[:2] == ["Origin.A001", "Origin.A002"]
    # a scoring frame whose domain is a reordered subset adapts by name
    sub = domain[::-1][:200]
    lut = {lvl: k for k, lvl in enumerate(sub)}
    sub_codes = np.array([lut.get(domain[c], -1) if c >= 0 else -1 for c in codes])
    scored = Frame(["Origin", "Distance"],
                   [Vec.from_numpy(sub_codes, VecType.CAT, domain=sub),
                    Vec.from_numpy(num)])
    Xs = np.asarray(di.expand(scored))[:rows]
    known = np.array([c >= 0 and domain[c] in lut for c in codes])
    np.testing.assert_array_equal(Xs[known], X[known])
    assert not Xs[~known, :-1].any()


@pytest.mark.parametrize("cards,nnum,use_all", [
    ((12, 31, 7, 22, 300, 300), 2, False),     # the airline design: 668
    ((3,) * 100, 10, False),                   # many narrow sources a strip
    ((1, 2, 129, 1, 128, 127), 1, False),      # sources without a column; a strip's edge
    ((7, 40), 0, True),                        # every level kept, no numerics
    ((), 3, False),                            # numerics alone
    ((300,), 130, True),                       # numerics over more than one strip
    ((1, 1), 0, False),                        # no design column at all
])
def test_expand_lays_every_layout_out_as_a_numpy_one_hot(cards, nnum, use_all):
    from h2o3_tpu.models.data_info import _expand
    rng = np.random.default_rng(len(cards) + nnum)
    rows, lo = 64, 0 if use_all else 1
    codes = (np.stack([rng.integers(-1, c, rows) for c in cards], 1).astype(np.int32)
             if cards else np.zeros((rows, 0), np.int32))
    nums = rng.normal(size=(rows, nnum)).astype(np.float32)
    nums[rng.random(nums.shape) < 0.1] = np.nan
    sub, mul, fill = (rng.normal(size=nnum).astype(np.float32) for _ in range(3))
    blocks = [(codes[:, j][:, None] == np.arange(lo, c)[None, :]).astype(np.float32)
              for j, c in enumerate(cards)]
    blocks.append((np.where(np.isnan(nums), fill[None, :], nums) - sub) * mul)
    want = np.concatenate(blocks, axis=1)
    got = np.asarray(_expand(tuple(jnp.asarray(c) for c in codes.T),
                             tuple(jnp.asarray(x) for x in nums.T), tuple(cards),
                             use_all, sub, mul, fill))
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
