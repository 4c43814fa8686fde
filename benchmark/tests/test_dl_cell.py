"""The DeepLearning cell's parts, by hand on the CPU: the generator (constant
pixels, share of zeros, the ceiling), ``roofline_dl``'s arithmetic at the
cell's shape, the new readers on a synthetic trace, the configuration's
arithmetic."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import plugins, roofline, roofline_dl

GEN = plugins.load("generators", "mnist_like")
DL = plugins.load("layer_metrics", "_dl_scopes")
RESPONSE = "C785"
ROWS = 8192


def host(frame, names):
    return np.stack([frame.vec(n).to_numpy()[: frame.nrows] for n in names], 1)


@pytest.fixture(scope="module")
def frame():
    return GEN.make(2147483999, 0, {"rows": ROWS, "response": RESPONSE})


def test_pixels_are_whole_numbers_mostly_zero_and_67_are_constant(frame):
    X = host(frame, GEN.NAMES)
    assert X.shape == (ROWS, 784) and X.min() == 0 and X.max() == 255
    assert np.array_equal(X, np.round(X))
    assert 0.78 < (X == 0).mean() < 0.86            # MNIST: four fifths
    constant = np.flatnonzero(X.min(axis=0) == X.max(axis=0))
    assert len(GEN.CONSTANT_PIXELS) == 67
    assert tuple(constant) == GEN.CONSTANT_PIXELS   # and no other, at 8,192 rows
    assert not X[:, constant].any()
    y = frame.vec(RESPONSE)
    assert y.domain == GEN.DOMAIN
    counts = np.bincount(y.to_numpy()[:ROWS].astype(int), minlength=10)
    assert counts.min() > 0.8 * ROWS / 10 and counts.max() < 1.2 * ROWS / 10


def test_prototypes_are_constants_and_classes_share_their_bright_strokes():
    a, b = GEN.prototypes(), GEN.prototypes()
    assert a is b and a.shape == (10, 28, 28)
    bright = a >= 128
    assert all(np.array_equal(bright[0], bright[k]) for k in range(10))
    assert all((a[j] != a[k]).any() for j in range(10) for k in range(j))
    assert GEN.shifted_table().shape == (250, 784)
    # the unshifted prototype sits at shift (2, 2) = index 12
    assert np.array_equal(GEN.shifted_table()[3 * 25 + 12], a[3].reshape(-1))


def test_seed_fold_matrix_and_reversed_domain(frame):
    data = {"rows": 2048, "response": RESPONSE}
    a = GEN.make(7, 1, data)
    assert np.array_equal(host(a, GEN.NAMES), host(GEN.make(7, 1, data), GEN.NAMES))
    assert not np.array_equal(host(a, GEN.NAMES[200:210]),
                              host(GEN.make(8, 1, data), GEN.NAMES[200:210]))
    # the training frame is kept and handed out again; other folds are not
    assert GEN.make(2147483999, 0, {"rows": ROWS, "response": RESPONSE}) is frame
    assert GEN.make(7, 1, data) is not a
    px, labels, true = GEN.pixels(7, 1, 2048)
    assert px.dtype == np.uint8 and px.shape == (2048, 784)
    assert np.array_equal(np.asarray(px).astype(np.float32), host(a, GEN.NAMES))
    codes = a.vec(RESPONSE).to_numpy()[:2048].astype(int)
    assert np.array_equal(codes, np.asarray(labels))
    rev = GEN.make(7, 1, dict(data, domain_order="reversed"))
    assert rev.vec(RESPONSE).domain == GEN.DOMAIN[::-1]
    assert np.array_equal(rev.vec(RESPONSE).to_numpy()[:2048].astype(int),
                          9 - codes)
    assert np.array_equal(np.asarray(rev.vec(RESPONSE).labels()[:2048]).astype(int),
                          codes)


def test_the_ceiling_is_the_share_of_labels_redrawn():
    _px, labels, true = GEN.pixels(5, 2, 200_000)
    err, ll = GEN.ceiling(labels, true)
    assert GEN.least_error() == pytest.approx(0.018)
    assert GEN.least_logloss() == pytest.approx(0.1297, abs=1e-4)
    # four binomial standard deviations at 200,000 rows
    assert abs(err - 0.018) < 4 * np.sqrt(0.018 * 0.982 / 200_000)
    assert abs(ll - GEN.least_logloss()) < 0.01


def test_an_updates_floor_at_the_cells_shape():
    peak = roofline.peak_row("TPU v5 lite")
    P = roofline_dl.parameters([717, 1024, 1024, 2048, 10])
    assert P == 717 * 1024 + 1024 * 1024 + 1024 * 2048 + 2048 * 10 + 4106 == 3_904_522
    ops, nbytes = roofline_dl.update(P, 32, 717)
    assert ops == 6 * 32 * P and nbytes == 4 * 32 * 717
    s, bound = roofline_dl.update_floor(P, 32, 717, peak)
    # the state stays on the chip: the products set the floor, 3.8 us
    assert bound == "compute" and s == pytest.approx(3.805e-6, rel=1e-2)
    assert s == roofline_dl.mfu_seconds(P, 32, peak)
    # a network of one narrow layer is bound by reading its rows
    assert roofline_dl.update_floor(717 * 2 + 2, 32, 717, peak)[1] == "memory"


@pytest.mark.parametrize("op_name,want", [
    ("jit(_train_epochs)/while/body/closed_call/shuffle/jit(_take)/gather",
     ("shuffle", False)),
    ("jit(_train_epochs)/while/body/closed_call/while/body/closed_call/"
     "jvp(forward)/dot_general", ("forward", False)),
    ("jit(_train_epochs)/while/body/closed_call/while/body/closed_call/"
     "transpose(jvp(forward))/dot_general", ("forward", True)),
    ("jit(_train_epochs)/while/body/closed_call/while/body/closed_call/"
     "transpose(jvp(dropout))/jit(_where)/select_n", ("dropout", True)),
    ("jit(_train_epochs)/while/body/closed_call/while/body/closed_call/"
     "dropout/jit(_bernoulli)/jit(_uniform)/shift_right_logical",
     ("dropout", False)),
    ("jit(_train_epochs)/while/body/closed_call/while/body/closed_call/"
     "optimizer/sqrt", ("optimizer", False)),
    ("jit(_train_epochs)/while/body/closed_call/while/body/closed_call/"
     "jvp(loss)/jit(log_softmax)/reduce_sum", ("loss", False)),
    ("jit(_train_epochs)/while/body/dynamic_slice", None),
    ("jit(_boost_scan_jit)/while/body/closed_call/level3/route/gather", None),
])
def test_scope_of_strips_autodiffs_wrappers(op_name, want):
    assert DL.scope_of(op_name) == want


class FakeTrace:
    """What a reader touches of a ``trace_reduce.Reduction``."""

    def __init__(self, ops, module_s, spans, busy_s, t0=0.0, t1=100.0):
        self._ops, self.module_s, self._spans = ops, module_s, spans
        self.busy_s, self.t0, self.t1 = busy_s, t0, t1

    def op_seconds(self, match):
        return sum(s for name, s, stats in self._ops if match(name, stats))

    def spans(self, name):
        return self._spans.get(name, [])


def reading(ops, updates=31250.0, parameters=3_904_522.0, spans=None,
            program="h2o3_tpu.models.deeplearning:_train_epochs"):
    module = "jit_" + program.rpartition(":")[2]
    named = [(f"{module}/{n}", s, {"op_name": f"jit(_train_epochs)/while/body/"
                                   f"closed_call/{path}", "opcode": opcode})
             for n, s, path, opcode in ops]
    total = sum(s for _n, s, _st in named)
    trace = FakeTrace(named, {module: total}, spans or {}, busy_s=total / 0.8)
    before = {"metrics": [("h2o3_dl_updates_total", {}, 1000.0)]}
    after = {"metrics": [("h2o3_dl_updates_total", {}, 1000.0 + updates),
                         ("h2o3_dl_parameters", {}, parameters)]}
    config = {"program": program, "params": {"mini_batch_size": 32},
              "data": {"expanded_columns": 717}}
    return types.SimpleNamespace(
        trace=trace, before=before, after=after, facts={"algo": "deeplearning"},
        cell=types.SimpleNamespace(config=config),
        peak=roofline.peak_row("TPU v5 lite"))


OPS = [("fusion.1", 4.0, "while/body/closed_call/optimizer/add", "fusion"),
       ("fusion.2", 0.5, "while/body/closed_call/regularize/sign", "fusion"),
       ("fusion.3", 1.0, "while/body/closed_call/jvp(forward)/dot_general", "fusion"),
       ("fusion.4", 1.5, "while/body/closed_call/transpose(jvp(forward))/dot_general", "fusion"),
       ("fusion.5", 0.25, "while/body/closed_call/jvp(loss)/neg", "fusion"),
       ("fusion.6", 0.75, "while/body/closed_call/dropout/jit(_bernoulli)/lt", "fusion"),
       ("fusion.7", 1.0, "shuffle/jit(_take)/gather", "fusion"),
       ("while.1", 0.5, "while", "while"),
       ("copy.1", 0.5, "while/body/dynamic_slice", "copy")]


def metric(name, r):
    return plugins.load("layer_metrics", name).read(r)


def test_the_readers_on_a_synthetic_trace():
    r = reading(OPS)
    busy = 10.0 / 0.8
    assert metric("dl.optimizer_share", r) == pytest.approx(100 * 4.5 / busy)
    assert metric("dl.matmul_share", r) == pytest.approx(100 * 2.75 / busy)
    assert metric("dl.shuffle_share", r) == pytest.approx(100 * 1.0 / busy)
    assert metric("dl.dropout_share", r) == pytest.approx(100 * 0.75 / busy)
    by = DL.seconds_by_scope(r)
    assert by["(loops)"] == 0.5 and by["(unscoped)"] == 0.5
    assert by["forward"] == 1.0 and by["forward'"] == 1.5
    us = 1e6 * 10.0 / 31250
    assert metric("dl.update_us", r) == pytest.approx(us)
    assert metric("dl.step_roofline", r) == pytest.approx(100 * 3.805 / us, rel=1e-2)
    assert metric("dl.step_mfu", r) == pytest.approx(100 * 3.805 / us, rel=1e-2)
    assert metric("dl.step_roofline", r) < 100 and metric("dl.step_mfu", r) < 100


def test_the_outside_share_from_the_programs_spans():
    spans = {"deeplearning:epochs": [(10.0, 18.0), (30.0, 38.0), (200.0, 208.0)],
             "bench:train": [(9.0, 19.0), (29.0, 39.0), (199.0, 209.0)]}
    r = reading(OPS, spans=spans)
    assert metric("dl.outside_epochs_share", r) == pytest.approx(20.0)


@pytest.mark.parametrize("name", [
    "dl.optimizer_share", "dl.matmul_share", "dl.shuffle_share",
    "dl.dropout_share", "dl.update_us", "dl.step_roofline", "dl.step_mfu",
    "dl.outside_epochs_share"])
def test_a_program_without_the_names_leaves_the_metric_out(name):
    """The parent of PR 32, another builder's cell, an untraced run: nothing
    to read is None, never 0 and never an exception."""
    tree_ops = [("fusion.9", 3.0, "level3/route/gather", "fusion")]
    other = reading(tree_ops, updates=0.0, parameters=0.0,
                    program="h2o3_tpu.models.gbm:_boost_scan_jit")
    other.facts = {"algo": "gbm"}
    assert metric(name, other) is None
    untraced = reading(OPS)
    untraced.trace = None
    assert metric(name, untraced) is None


def test_the_configuration_states_what_the_cell_runs():
    cfg = plugins.load_json("configs", "dl-mnist-1024x1024x2048")
    with open(os.path.join(plugins.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    p = cfg["params"]
    assert (p["hidden"], p["activation"], p["l1"], p["input_dropout_ratio"]) == (
        [1024, 1024, 2048], "RectifierWithDropout", 1e-5, 0.2)
    assert (p["train_samples_per_iteration"], p["classification_stop"]) == (-1, -1)
    assert p["mini_batch_size"] == 32 and set(cfg["reduced"]) == {"epochs", "rows"}
    d = cfg["data"]
    assert d["features"] == 784 and d["expanded_columns"] == 784 - 67
    widths = [d["expanded_columns"], *p["hidden"], 10]
    assert roofline_dl.parameters(widths) == 3_904_522
    from h2o3_tpu.models.deeplearning import DeepLearning, _epoch_plan
    DeepLearning(**p)                                   # the call is accepted
    assert _epoch_plan(p["epochs"], d["rows"], 32)[1:] == (
        int(p["epochs"]), 0) or p["epochs"] < 1
    cell = next(w for w in manifest["workloads"] if w["name"] == "dl-mnist-build")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "build-repeat", 1)
    dl_metrics = [m for m in manifest["per_layer"] if m["name"].startswith("dl.")]
    assert len(dl_metrics) == 8
    assert all(m["workloads"] == ["dl-mnist-build"] for m in dl_metrics)
