"""DeepLearning against the benchmark's plain reference
(``benchmark/reference/dl_mlp_jnp.py``: float32-highest, a backward pass
written by hand, ADADELTA, ``l1``) at a toy size on the CPU: widths
12-16-16-32-4, minibatches of 8, dropout and ``l1`` on, seeded random
weights. The same comparison decides ``correct`` on the chip at the
published widths (``benchmark/checks/dl_*.py``). Also: what H2O's own call
needs of the builder (``train_samples_per_iteration``,
``classification_stop``, ``ignore_const_cols``, fractional ``epochs``), and
the spans, scopes and counters the cell's per-layer metrics read."""

import logging
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from benchmark.reference import dl_mlp_jnp as ref
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.types import VecType
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.models import deeplearning as dl
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.models.deeplearning import DeepLearning
from h2o3_tpu.utils.telemetry import METRICS
from h2o3_tpu.utils.timeline import TIMELINE

CHK = plugins.load("checks", "_dl")
ROWS, COLS, CLASSES, B = 520, 14, 4, 8
CONSTANT = (3, 9)                       # columns that hold one value
NAMES = [f"C{j + 1}" for j in range(COLS)]
RESPONSE = "label"
PARAMS = dict(activation="RectifierWithDropout", hidden=[16, 16, 32],
              l1=1e-5, input_dropout_ratio=0.2, epochs=1, mini_batch_size=B,
              train_samples_per_iteration=-1, classification_stop=-1, seed=42)


def toy(seed=5, rows=ROWS, reverse=False):
    """(frame, pixels uint8 [rows, 14], labels): whole numbers 0-255, two
    constant columns, four classes that the pixels tell apart."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, CLASSES, rows)
    centres = np.random.default_rng(99).uniform(40, 200, (CLASSES, COLS))
    px = np.clip(np.round(centres[labels] + rng.normal(0, 12, (rows, COLS))),
                 0, 255)
    px[rng.random((rows, COLS)) < 0.3] = 0          # many pixels are 0
    px[:, CONSTANT[0]], px[:, CONSTANT[1]] = 0, 7
    domain = tuple(str(k) for k in range(CLASSES))
    codes = labels
    if reverse:
        domain, codes = domain[::-1], CLASSES - 1 - labels
    vecs = [Vec.from_numpy(px[:, j].astype(np.float32)) for j in range(COLS)]
    vecs.append(Vec.from_numpy(codes, VecType.CAT, domain=domain))
    return Frame(NAMES + [RESPONSE], vecs), px.astype(np.uint8), labels


def reference_rows(px, labels):
    kept, mean, sd = ref.standardize(px)
    return types.SimpleNamespace(pixels=jnp.asarray(px), kept=kept, mean=mean,
                                 sd=sd, labels=jnp.asarray(labels, jnp.int32))


@pytest.fixture(scope="module")
def prepared():
    """(frame, the build's state before its first update, the reference's
    view of the same rows)."""
    frame, px, labels = toy()
    builder = DeepLearning(**PARAMS)
    prep = builder._prepare(frame, NAMES, RESPONSE,
                            frame.row_mask().astype(jnp.float32))
    return frame, prep, reference_rows(px, labels)


def test_the_reference_finds_the_columns_and_moments_itself(prepared):
    _frame, prep, rows = prepared
    assert list(rows.kept) == [j for j in range(COLS) if j not in CONSTANT]
    assert prep.di.ignored_const_cols == tuple(NAMES[j] for j in CONSTANT)
    assert prep.sizes == [12, 16, 16, 32, 4]
    # float32 roll-ups against float64 moments
    np.testing.assert_allclose(prep.di.num_sub, rows.mean, rtol=1e-6)
    np.testing.assert_allclose(1.0 / prep.di.num_mul, rows.sd, rtol=1e-6)
    x = np.asarray(ref.design(rows.pixels, rows.kept, rows.mean, rows.sd))
    np.testing.assert_allclose(np.asarray(prep.X)[:ROWS], x, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("updates", [1, 8, 64])
@pytest.mark.parametrize("what", ["theta", "E_g", "E_delta"])
def test_updates_agree_with_the_reference(prepared, updates, what):
    """The timed program (``_train_epochs``: autodiff, one scan) against the
    reference's hand-written backward pass and ADADELTA under the SAME
    permutation and dropout masks. Tolerance: both sides are float32 on the
    CPU (the program's products at DEFAULT are float32 here, the reference's
    at HIGHEST), so they differ by rounding alone: summation order in the
    products, the float32 roll-ups against float64 moments (1e-7 of a
    standardised value). Measured 2e-7 to 1.5e-5 of the reference's own
    change (1.5e-5 after ONE update, where the change is smallest; 2e-6 after
    64); 2e-4 leaves ten times of room and is a hundred times under the
    nearest fault below (``l1`` left out: 2e-2)."""
    frame, prep, rows = prepared
    hp = CHK.hyper(DeepLearning(**PARAMS).params)
    got_p, got_o = CHK.program_updates(prep, updates, B)
    want_p, want_s, _key = CHK.reference_updates(
        ref, prep.params, rows, prep.key, frame.plen, updates, hp)
    if what == "theta":
        errs = CHK.scaled_errors(got_p, want_p, prep.params)
    elif what == "E_g":
        errs = CHK.scaled_errors(got_o["Eg"], want_s["Eg"])
    else:
        errs = CHK.scaled_errors(got_o["Edx"], want_s["Ed"])
    assert len(errs) == 8 and max(errs) < 2e-4, errs


@pytest.mark.parametrize("fault", ["no_l1", "no_rescale", "rho_0.9"])
def test_the_comparison_tells_a_fault(prepared, fault):
    """The reference run with one thing wrong is far outside the tolerance
    above: ``l1`` left out reads 2.1e-2 after 64 updates, ``rho`` 0.9 reads
    3.5, no dropout rescale 18."""
    frame, prep, rows = prepared
    hp = CHK.hyper(DeepLearning(**PARAMS).params)
    if fault == "no_l1":
        hp.l1 = 0.0
    elif fault == "no_rescale":
        hp.keep = (1.0,) * 4
    else:
        hp.rho = 0.9
    got_p, _ = CHK.program_updates(prep, 64, B)
    want_p, _state, _key = CHK.reference_updates(
        ref, prep.params, rows, prep.key, frame.plen, 64, hp)
    assert max(CHK.scaled_errors(got_p, want_p, prep.params)) > 5e-3


@pytest.fixture(scope="module")
def model():
    frame, px, labels = toy()
    m = DeepLearning(**dict(PARAMS, epochs=40)).train(y=RESPONSE,
                                                      training_frame=frame)
    return frame, m, reference_rows(px, labels)


def test_predict_agrees_with_the_references_forward_pass(model):
    """``model.predict`` on a frame that still has its constant columns and
    writes its response domain REVERSED, against the reference's forward pass
    from the fetched weights and its own moments. Both float32: 1e-5."""
    _frame, m, rows = model
    held, px, labels = toy(seed=6, rows=300, reverse=True)
    pred = m.predict(held)
    assert pred.names == ["predict", "p0", "p1", "p2", "p3"]
    got = np.stack([pred.vec(f"p{k}").to_numpy()[:300] for k in range(4)], 1)
    theta = jax.device_get(m.output["params"])
    want = np.asarray(ref.predict_proba(
        theta, ref.design(px, rows.kept, rows.mean, rows.sd)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the prediction is a class NAME: the frame's reversed codes adapt
    named = np.asarray(pred.vec("predict").labels()[:300]).astype(int)
    assert np.array_equal(named, want.argmax(axis=1))
    assert (named == labels).mean() > 0.45        # chance is 0.25


def test_constant_columns_leave_the_network_and_stay_in_the_frame(model):
    frame, m, _rows = model
    di = m.data_info
    assert di.ignored_const_cols == ("C4", "C10")
    assert len(di.num_cols) == 12 and "C4" not in di.num_cols
    assert m.output["sizes"][0] == 12
    assert np.asarray(m.output["params"]["W"][0]).shape == (12, 16)
    wide = DeepLearning(**dict(PARAMS, ignore_const_cols=False)).train(
        y=RESPONSE, training_frame=frame)
    assert wide.output["sizes"][0] == 14
    assert wide.data_info.ignored_const_cols == ()
    # every other caller of DataInfo.make keeps what it kept
    assert DataInfo.make(frame, NAMES).num_cols == NAMES
    # a scoring frame without the dropped columns scores the same
    narrow = Frame([n for n in frame.names if n not in ("C4", "C10")],
                   [frame.vec(n) for n in frame.names
                    if n not in ("C4", "C10")])
    a, b = (m.predict(f).vec("p1").to_numpy() for f in (frame, narrow))
    assert np.array_equal(a, b)


def test_the_generated_scorers_read_the_same_data_info(model, tmp_path):
    """MOJO round trip and the serving schema: the dropped columns are in
    neither, and the reloaded model predicts bit for bit."""
    from h2o3_tpu.genmodel.mojo import MojoModel, write_mojo
    from h2o3_tpu.serving import serving_schema
    frame, m, _rows = model
    assert "C4" not in serving_schema(m).names
    assert len(serving_schema(m).names) == 12
    back = MojoModel.load(write_mojo(m, str(tmp_path / "dl.mojo")))
    assert back._inner.data_info.ignored_const_cols == ("C4", "C10")
    assert np.array_equal(back.predict(frame).vec("p2").to_numpy(),
                          m.predict(frame).vec("p2").to_numpy())


def counter(name):
    return sum(r["value"] for r in METRICS.snapshot() if r["name"] == name)


@pytest.mark.parametrize("epochs,updates", [
    (1, 72), (0.5, 36), (0.25, 18), (2.25, 162), (0.01, 1), (3, 216)])
def test_fractional_epochs_stop_at_the_stated_update(epochs, updates):
    """576 padded rows in minibatches of 8: an epoch is 72 updates; training
    stops after ceil(epochs x rows / B) updates."""
    frame, _px, _labels = toy()
    assert frame.plen == 576
    before = counter("h2o3_dl_updates_total"), counter("h2o3_dl_samples_total")
    m = DeepLearning(**dict(PARAMS, epochs=epochs)).train(
        y=RESPONSE, training_frame=frame)
    assert counter("h2o3_dl_updates_total") - before[0] == updates
    assert counter("h2o3_dl_samples_total") - before[1] == updates * B
    assert len(m.output["score_history"]) == int(np.ceil(epochs))
    assert counter("h2o3_dl_parameters") == 12 * 16 + 16 * 16 + 16 * 32 + 32 * 4 + 68


@pytest.mark.parametrize("epochs,rows,batch,plan", [
    (1.0, 1_000_000, 32, (31250, 1, 0)), (0.5, 1_000_000, 32, (31250, 0, 15625)),
    (0.25, 1_000_000, 32, (31250, 0, 7813)), (10.0, 1000, 32, (31, 10, 0)),
    (2.1, 1000, 32, (31, 2, 4)), (0.0, 1000, 32, (31, 1, 0)),
    (0.999, 1000, 32, (31, 0, 31))])
def test_the_epoch_plan(epochs, rows, batch, plan):
    assert dl._epoch_plan(epochs, rows, batch) == plan


def weights_of(**more):
    frame, _px, _labels = toy()
    m = DeepLearning(**dict(PARAMS, **more)).train(y=RESPONSE,
                                                   training_frame=frame)
    return b"".join(np.asarray(a).tobytes()
                    for a in jax.tree.leaves(m.output["params"]))


@pytest.mark.parametrize("more", [
    {}, {"train_samples_per_iteration": -2}, {"train_samples_per_iteration": 0},
    {"train_samples_per_iteration": 4096}, {"classification_stop": 0},
    {"classification_stop": 0.05}], ids=str)
def test_builds_from_one_seed_are_bit_equal_whatever_the_iteration_says(more):
    """Two builds from one seed are bit-equal in every parameter array, and
    the reference's iteration parameters cannot change the weights: every
    update already averages exactly."""
    assert weights_of(**more) == weights_of()


def test_the_three_parameters_have_the_references_defaults_and_say_so(caplog):
    d = DeepLearning.defaults()
    assert (d["train_samples_per_iteration"], d["classification_stop"],
            d["ignore_const_cols"]) == (-2, 0.0, True)
    with pytest.raises(ValueError, match="train_samples_per_iteration"):
        DeepLearning(train_samples_per_iteration=-3).validate_request()
    frame, _px, _labels = toy()
    with pytest.raises(ValueError, match="train_samples_per_iteration"):
        DeepLearning(**dict(PARAMS, train_samples_per_iteration=-5)).train(
            y=RESPONSE, training_frame=frame)
    dl._LOGGED.clear()
    with caplog.at_level(logging.INFO, logger="h2o3_tpu"):
        for _ in range(2):
            DeepLearning(**dict(PARAMS, classification_stop=0.125,
                                train_samples_per_iteration=777)).train(
                y=RESPONSE, training_frame=frame)
    said = [r.getMessage() for r in caplog.records]
    assert sum("classification_stop=0.125 is not honoured" in s for s in said) == 1
    assert sum("train_samples_per_iteration=777" in s for s in said) == 1
    # -1 and -1 are what the loop does: nothing to say
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="h2o3_tpu"):
        DeepLearning(**PARAMS).train(y=RESPONSE, training_frame=frame)
    assert not [r for r in caplog.records if "deeplearning:" in r.getMessage()]


def test_spans_and_counters_of_a_build():
    frame, _px, _labels = toy()
    TIMELINE.clear()
    builder = DeepLearning(**dict(PARAMS, epochs=2.5))
    builder.train(y=RESPONSE, training_frame=frame)
    spans = [e["what"] for e in TIMELINE.snapshot()]
    assert spans.count("deeplearning:prepare") == 1
    assert spans.count("deeplearning:epochs") == 1
    assert spans.count("deeplearning:metrics") == 1
    # two whole epochs in one dispatch, the partial one in another
    assert spans.count("dl_epoch") == 2
    assert builder._dispatch_audit["dl_epoch"]["device_dispatches"] == 2
    assert builder._dispatch_audit["dl_epoch"]["host_syncs"] == 1
    assert (spans.index("deeplearning:prepare") < spans.index("deeplearning:epochs")
            < spans.index("deeplearning:fit") < spans.index("deeplearning:metrics"))


@pytest.mark.parametrize("scope", ["shuffle", "dropout", "forward", "loss",
                                   "regularize", "optimizer", "constrain"])
def test_the_training_program_names_its_parts(scope):
    params = dict(PARAMS, max_w2=10.0)          # so that constrain has work
    builder = DeepLearning(**params)
    frame, _px, _labels = toy()
    p = builder._prepare(frame, NAMES, RESPONSE,
                         frame.row_mask().astype(jnp.float32))
    text = dl._train_epochs.lower(
        p.params, p.opt, p.X, p.yy, p.w, p.key, jnp.float32(0.0), p.act,
        p.loss, p.nclasses, p.cfg, 1, 4, B, False).compile().as_text()
    names = set(re.findall(r'op_name="(jit\(_train_epochs\)[^"]*)"', text))
    inner = scope if scope not in ("forward", "loss") else f"jvp({scope})"
    assert any(f"/{inner}/" in n for n in names), scope
    if scope == "forward":
        # the backward pass carries forward's name under autodiff's wrappers
        assert any("/transpose(jvp(forward))/" in n for n in names)


def test_every_product_states_its_precision(prepared):
    """DEFAULT with a float32 result, written out: one bf16 pass on a TPU, a
    float32 product here; a changed default cannot move it."""
    _frame, p, _rows = prepared
    with jax.default_matmul_precision("highest"):
        jaxpr = str(jax.make_jaxpr(
            lambda *a: dl._train_epochs._fun(
                *a, p.act, p.loss, p.nclasses, p.cfg, 1, 2, B, False))(
            p.params, p.opt, p.X, p.yy, p.w, p.key, jnp.float32(0.0)))
    found = re.findall(
        r"dot_general\[.*?precision=(\S+).*?preferred_element_type=(\w+)",
        jaxpr, flags=re.S)
    assert len(found) == 11          # 4 forward, 4 by W, 3 by h
    assert all("DEFAULT" in prec and typ == "float32" for prec, typ in found), found


def test_scoring_in_blocks_gives_what_scoring_whole_gives(prepared):
    """One device, more rows than a block: the last block overlaps the one
    before it."""
    _frame, p, _rows = prepared
    X = jax.device_put(np.random.default_rng(0).normal(
        size=(1000, 12)).astype(np.float32), jax.devices()[0])
    whole = dl._dl_forward_score(p.params, X, p.act)
    blocks = dl._dl_forward_score(p.params, X, p.act, 384)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)
    assert dl._score_block(X) == 0                   # shorter than a block
    long = jax.device_put(np.zeros((dl._SCORE_BLOCK + 8, 12), np.float32),
                          jax.devices()[0])
    assert dl._score_block(long) == dl._SCORE_BLOCK
    assert dl._score_block(p.X) == 0                 # on the test mesh: whole
