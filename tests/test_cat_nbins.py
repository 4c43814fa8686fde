"""``nbins_cats`` honoured, and the engine at the shapes of the categorical
airline cell (``gbm100-airline-cat-build``, ISSUE 30): a categorical column
gets a bin a level up to ``nbins_cats`` whatever ``nbins`` is, the engine
runs ONE bin count (``max(nbins, largest categorical bin count)``), and the
group-split search, the packed-mask route, the kernel's ``passes`` contraction
and every scorer agree with the benchmark's plain float64 reference
(``benchmark/reference/gbm_cat_numpy.py``) at a small size on the CPU. The
same comparisons decide ``correct`` on the chip at the cell's size
(``benchmark/checks/``)."""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gbm_cat_numpy as ref
from benchmark.reference import tree_traverse_masked as walk
from benchmark.reference.hist_segment_sum import level_histograms
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.types import VecType
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.models import tree
from h2o3_tpu.models.gbm import DRF, GBM, tree_matrix
from h2o3_tpu.ops import pallas_hist
from h2o3_tpu.ops.quantile import bin_dtype
from h2o3_tpu.utils.telemetry import (HIST_ONEHOT_ROWS, ROUTE_LEVELS,
                                      SPLIT_LEVELS)

LEVELS = 40
DOMAIN = tuple(f"L{j:02d}" for j in range(LEVELS))
PARAMS = dict(ntrees=3, max_depth=4, nbins=16, learn_rate=0.3, min_rows=10.0,
              distribution="bernoulli", seed=1)


def _columns(rows=4000, seed=0, na=0.0):
    """A 40-level column whose levels carry the signal, a 5-level one, a
    numeric one; optionally missing values in all three."""
    rng = np.random.default_rng(seed)
    c = rng.integers(0, LEVELS, rows)
    d = rng.integers(0, 5, rows)
    x = rng.normal(size=rows).astype(np.float32)
    eff = np.random.default_rng(99).normal(0.0, 1.0, LEVELS)
    p = 1.0 / (1.0 + np.exp(-(eff[c] + 0.3 * (d == 2) + 0.5 * x)))
    y = (rng.random(rows) < p).astype(np.int32)
    c, d = c.astype(np.float64), d.astype(np.float64)
    x = x.astype(np.float64)
    for col in (c, d, x):
        col[rng.random(rows) < na] = np.nan
    return c, d, x, y


def _frame(c, d, x, y, reverse=False):
    def cat(codes, dom):
        codes = np.where(np.isnan(codes), -1, codes).astype(np.int32)
        if reverse:
            dom = dom[::-1]
            codes = np.where(codes < 0, -1, len(dom) - 1 - codes)
        return Vec.from_numpy(codes.astype(np.int32), type=VecType.CAT,
                              domain=dom)
    return Frame(["c", "d", "x", "y"],
                 [cat(c, DOMAIN), cat(d, tuple("abcde")),
                  Vec.from_numpy(x.astype(np.float32)),
                  Vec.from_numpy(y, type=VecType.CAT, domain=("N", "Y"))])


@pytest.fixture(scope="module")
def built():
    cols = _columns()
    frame = _frame(*cols)
    model = GBM(**PARAMS).train(y="y", training_frame=frame)
    return cols, frame, model


def test_a_column_of_more_levels_than_nbins_gets_a_bin_a_level(built):
    _cols, _frame_, model = built
    out = model.output
    assert int(out["cat_bins"]) == 1024            # nbins_cats, not nbins
    assert out["edges"].shape == (3, LEVELS - 1)   # the engine's 40 bins
    assert out["trees"][0].left_mask.shape == (2 ** 5 - 1, LEVELS)
    # the numeric column keeps nbins quantile bins: the rest is inf padding
    assert np.isinf(np.asarray(out["edges"])[2, PARAMS["nbins"] - 1:]).all()
    # some split sends level codes left that no 16 range-grouped bins could
    # hold apart (three codes of one group on different sides)
    masks = np.concatenate([np.asarray(t.left_mask)[np.asarray(t.feat) == 0]
                            for t in out["trees"]])
    groups = masks[:, :39].reshape(len(masks), 13, 3)
    assert (groups.any(axis=2) & ~groups.all(axis=2)).any()


def _builders():
    from h2o3_tpu.models.decision_tree import DecisionTree
    from h2o3_tpu.models.xgboost import XGBoost
    return {"gbm": (GBM, {}), "drf": (DRF, {}), "xgboost": (XGBoost, {}),
            "xgboost_dart": (XGBoost, {"booster": "dart"}),
            "decision_tree": (DecisionTree, {})}


@pytest.mark.parametrize("name", ["gbm", "drf", "xgboost", "xgboost_dart",
                                  "decision_tree"])
def test_builders_inherit_the_bin_count(name):
    """Every builder on ``SharedTreeBuilder`` runs the engine at 40 bins
    here, and its model scores through masks of 40 columns."""
    builder, more = _builders()[name]
    frame = _frame(*_columns(rows=1500, seed=3))
    b = builder(max_depth=3, nbins=16, seed=2, **more)
    if name != "decision_tree":
        b.params["ntrees"] = 3
    model = b.train(y="y", training_frame=frame)
    assert b._n_bins == LEVELS
    assert b._bins_used == (LEVELS, 5, 16)   # the kernel's part of the schema
    assert model.output["trees"][0].left_mask.shape[1] == LEVELS
    assert model.training_metrics.auc > 0.6
    p = model.predict(frame).vecs[-1].to_numpy()[: frame.nrows]
    assert np.isfinite(p).all() and p.std() > 0


def test_nbins_cats_below_the_cardinality_range_groups():
    frame = _frame(*_columns(rows=1500, seed=4))
    b = GBM(ntrees=1, max_depth=2, nbins=8, nbins_cats=10, seed=2)
    _, edges, binned, *_ = b._prepare(frame, ["c", "d", "x"], "y")
    assert b._n_bins == 10 and edges.shape == (3, 9)
    got = np.asarray(binned)[: frame.nrows]
    codes = np.asarray(frame.vec("c").data)[: frame.nrows]
    np.testing.assert_array_equal(got[:, 0], codes * 10 // LEVELS)
    assert got[:, 2].max() == 7                    # numeric: nbins 8 stands


def _reference_for(model, c, d, x, y):
    edges = np.asarray(model.output["edges"], np.float64)
    edges = [None, None, edges[2][np.isfinite(edges[2])]]
    X = np.stack([c, d, x], axis=1)
    return ref.fit(X, y, cat_cards=[LEVELS, 5, 0], edges=edges,
                   **{k: PARAMS[k] for k in ("ntrees", "max_depth", "nbins",
                                             "learn_rate", "min_rows")})


def _same_tree(node, t, i=0):
    """The reference's ``node`` against heap slot ``i`` of the model's tree."""
    feat, is_split = np.asarray(t.feat), np.asarray(t.is_split)
    if node.feature < 0:
        assert not is_split[i], f"node {i}: the reference has a leaf"
        np.testing.assert_allclose(np.asarray(t.leaf)[i], node.value,
                                   rtol=1e-5, atol=1e-5)
        return 1
    assert is_split[i] and feat[i] == node.feature, f"node {i}"
    np.testing.assert_array_equal(np.asarray(t.left_mask)[i], node.left_bins)
    np.testing.assert_allclose(np.asarray(t.gain)[i], node.gain, rtol=2e-3,
                               atol=1e-4)
    return (1 + _same_tree(node.left, t, 2 * i + 1)
            + _same_tree(node.right, t, 2 * i + 2))


def test_whole_gbm_equals_the_plain_reference(built):
    """Same splits, leaf values to 1e-5; at the parent's
    ``min(nbins, nbins_cats)`` the 40 levels shared 16 bins and no mask had
    40 columns."""
    (c, d, x, y), _frame_, model = built
    want = _reference_for(model, c, d, x, y)
    assert want.n_bins == LEVELS
    nodes = [_same_tree(r, t) for r, t in zip(want.trees,
                                              model.output["trees"])]
    assert min(nodes) > 7                          # trees, not stumps
    np.testing.assert_allclose(model.output["f0"], want.f0, rtol=1e-6)


def test_predict_on_a_reversed_domain_equals_the_traversal(built):
    (c, d, x, y), frame, model = built
    held = _columns(rows=1000, seed=8, na=0.05)
    flipped = _frame(*held, reverse=True)
    p1 = model.predict(flipped).vecs[-1].to_numpy()[:1000]
    X = np.stack(held[:3], axis=1)                 # codes in TRAINING order
    want = walk.bernoulli_p1(model, X)
    np.testing.assert_allclose(p1, want, rtol=2e-6, atol=2e-7)
    # and the reference model, trained on the same bins, scores the same
    np.testing.assert_allclose(
        _reference_for(model, c, d, x, y).predict_proba(X), want,
        rtol=1e-4, atol=1e-5)
    # the adapted matrix is what the traversal read
    got = np.asarray(tree_matrix(flipped, ["c", "d", "x"],
                                 model.output["feat_domains"]))[:1000]
    np.testing.assert_array_equal(np.isnan(got), np.isnan(X))
    np.testing.assert_array_equal(got[~np.isnan(X)],
                                  X.astype(np.float32)[~np.isnan(X)])


def test_generated_scorer_equals_predict(built, tmp_path):
    _cols, _f, model = built
    held = _columns(rows=300, seed=9, na=0.05)
    frame = _frame(*held)
    path = model.download_pojo(str(tmp_path / "scorer.py"))
    spec = importlib.util.spec_from_file_location("scorer", path)
    scorer = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(scorer)
    assert scorer.NBINS_CAT == 1024 and scorer.MASK.shape[2] == LEVELS
    X = np.stack(held[:3], axis=1)
    want = model.predict(frame).vecs[-1].to_numpy()[:300]
    np.testing.assert_allclose(scorer.score_batch(X)[:, 1], want, rtol=1e-5,
                               atol=1e-6)


# --- the split search against the float64 sorted-prefix optimum ------------

def _histograms(case: str, n_nodes=6, F=4, B=24, seed=0):
    """Seeded node histograms [F, n_nodes * (B + 1), 3]: features 0 and 1
    categorical with signal carried by scattered bins, 2 and 3 numeric."""
    rng = np.random.default_rng(seed)
    rows = 600
    hist = np.zeros((F, n_nodes, B + 1, 3))
    for n in range(n_nodes):
        bins = rng.integers(0, B - 4, size=(rows, F))     # upper bins empty
        if case in ("na_left", "na_right"):
            bins[rng.random((rows, F)) < 0.2] = B
        scattered = np.isin(bins[:, 0], [1, 4, 5, 9, 16])
        signal = {"numeric": bins[:, 2] < 7,
                  "categorical": scattered,
                  "na_left": scattered | (bins[:, 0] == B),
                  "na_right": scattered & (bins[:, 0] != B),
                  "min_rows": bins[:, 1] == 3}[case]
        g = -(signal + 0.3 * rng.normal(size=rows))
        h = 0.5 + rng.random(rows)
        for f in range(F):
            for k, v in enumerate((g, h, np.ones(rows))):
                hist[f, n, :, k] = np.bincount(bins[:, f], v, B + 1)
    return hist.astype(np.float32)


@pytest.mark.parametrize("case, min_rows", [
    ("numeric", 10.0), ("categorical", 10.0), ("na_left", 10.0),
    ("na_right", 10.0), ("min_rows", 60.0)])
def test_find_splits_equals_the_plain_search(case, min_rows):
    hist = _histograms(case)
    F, N, Bt, _ = hist.shape
    cat = np.array([True, True, False, False])
    before = SPLIT_LEVELS.labels(kind="group").value
    (gain, feat, t, na_left, *_mid, member) = tree._find_splits(
        jnp.asarray(hist.reshape(F, N * Bt, 3)), Bt - 1, min_rows, 0.0, 0.0,
        0.0, jnp.ones(F, bool), cat_feats=jnp.asarray(cat))
    assert SPLIT_LEVELS.labels(kind="group").value == before + 1
    seen = set()
    for n in range(N):
        want = ref.best_split(hist[:, n], cat, min_rows)
        assert want is not None
        w_gain, w_feat, _w_t, w_na, w_left = want
        assert int(feat[n]) == w_feat, (case, n)
        np.testing.assert_array_equal(np.asarray(member[n]), w_left)
        np.testing.assert_allclose(float(gain[n]), w_gain, rtol=1e-4)
        got_gain, wl, wr = ref.split_gain(hist[:, n], w_feat,
                                          np.asarray(member[n]),
                                          bool(na_left[n]))
        np.testing.assert_allclose(got_gain, w_gain, rtol=1e-9)
        assert min(wl, wr) >= min_rows
        if case.startswith("na_"):
            assert bool(na_left[n]) == w_na == (case == "na_left")
        seen.add(w_feat)
    assert seen == {{"numeric": 2, "min_rows": 0}.get(case, 0)} or \
        case == "min_rows"
    if case == "min_rows":
        # the one-bin split (about 29 rows a side at most) is forbidden
        assert all(np.asarray(member[n]).sum() > 1 or int(feat[n]) != 1
                   for n in range(N))


def test_threshold_levels_are_counted_apart():
    hist = _histograms("numeric")
    F, N, Bt, _ = hist.shape
    before = SPLIT_LEVELS.labels(kind="threshold").value
    tree._find_splits(jnp.asarray(hist.reshape(F, N * Bt, 3)), Bt - 1, 10.0,
                      0.0, 0.0, 0.0, jnp.ones(F, bool))
    assert SPLIT_LEVELS.labels(kind="threshold").value == before + 1


# --- what each column can hold: the histogram kernel's ``bins_used`` ----------

def _wide_frame(rows=2500, seed=5, na=0.03):
    """Categorical columns of 7 and 300 levels and a numeric one, with
    missing values in all three and the numeric one's infinities."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 7, rows).astype(np.float64)
    b = rng.integers(0, 300, rows).astype(np.float64)
    x = rng.normal(size=rows)
    y = (rng.random(rows) < 1 / (1 + np.exp(-(0.4 * (a == 3) + 0.01 * (b % 7)
                                              + x)))).astype(np.int32)
    x[:5], x[5:8] = np.inf, -np.inf
    for col in (a, b, x):
        col[rng.random(rows) < na] = np.nan

    def cat(codes, levels):
        return Vec.from_numpy(np.where(np.isnan(codes), -1, codes).astype(
            np.int32), type=VecType.CAT,
            domain=tuple(f"v{j:03d}" for j in range(levels)))
    return Frame(["a", "b", "x", "y"],
                 [cat(a, 7), cat(b, 300), Vec.from_numpy(x.astype(np.float32)),
                  Vec.from_numpy(y, type=VecType.CAT, domain=("N", "Y"))])


def _inside(binned, frame, used, n_bins):
    got = np.asarray(binned)[: frame.nrows]
    for j, (col, u) in enumerate(zip(("a", "b", "x"), used)):
        missing = np.isnan(np.asarray(frame.vec(col).as_float())[: frame.nrows])
        assert missing.any() and (got[missing, j] == n_bins).all()
        assert 0 <= got[~missing, j].min() and got[~missing, j].max() == u - 1


def test_every_bin_lies_inside_what_the_schema_declares():
    """``bins_used`` from the cardinalities alone, and ``_bin_frame`` and
    ``_apply_cat_bins`` inside it on every row: what the histogram kernel
    is told it may skip. An infinite value of the numeric column would
    count the edges' ``inf`` padding in (bin 299): it bins with the largest
    finite ones (``_binning_edges``), and the model's edges keep their
    ``inf``."""
    frame = _wide_frame()
    b = GBM(ntrees=1, max_depth=2, nbins=16, seed=2)
    _, edges, binned, *_ = b._prepare(frame, ["a", "b", "x"], "y")
    assert b._n_bins == 300 and edges.shape == (3, 299)
    assert b._bins_used == (7, 300, 16)
    _inside(binned, frame, b._bins_used, 300)
    x = np.asarray(frame.vec("x").as_float())[: frame.nrows]
    got = np.asarray(binned)[: frame.nrows, 2]
    assert (got[x == np.inf] == 15).all() and (got[x == -np.inf] == 0).all()
    assert np.isinf(np.asarray(edges)[2, 15:]).all()
    assert np.isnan(np.asarray(b._binning_edges(edges))[:, 15:]).all()
    # a validation frame: bin_features, then _apply_cat_bins
    valid = _wide_frame(rows=1200, seed=6)
    Xv = tree_matrix(valid, ["a", "b", "x"],
                     {c: frame.vec(c).domain for c in "ab"})
    from h2o3_tpu.ops.quantile import bin_features
    _inside(b._apply_cat_bins(Xv, bin_features(Xv, b._binning_edges(edges))),
            valid, b._bins_used, 300)
    # all columns alike, or none categorical: nothing to tell the kernel
    b._setup_cat_info(frame, ["x"])
    assert b._bins_used is None and b._n_bins == 16
    b.params["nbins"] = 300
    b._setup_cat_info(frame, ["b", "x"])
    assert b._bins_used is None and b._n_bins == 300


def test_a_build_with_bins_used_equals_the_dense_build(monkeypatch):
    """The whole boost program on one device, two trees of depth 4: the same
    trees and margins, array for array, whether the kernel skips the one-hot
    rows the schema rules out or streams them all (interpret mode; the row
    tile pinned, so that both sum the same rows in one accumulation)."""
    from h2o3_tpu.models.gbm import _boost_scan
    monkeypatch.setattr(pallas_hist, "_INTERPRET", True)
    monkeypatch.setattr(pallas_hist, "_TILE_MAX", 256)
    frame = _wide_frame()
    b = GBM(ntrees=2, max_depth=4, nbins=16, seed=1)
    _, edges, binned, yy, valid, *_ = b._prepare(frame, ["a", "b", "x"], "y")
    assert b._bins_used == (7, 300, 16)
    # a frame's arrays span the test mesh; the kernel's operands sit on one
    # device, as a one-chip build's do
    one = lambda a: jnp.asarray(np.asarray(a))
    binned, w = one(binned), one(valid).astype(jnp.float32)
    yc = jnp.where(w > 0, one(yy), 0.0)
    keys = jax.random.split(jax.random.PRNGKey(1), 6).reshape(2, 3, 2)
    rows = HIST_ONEHOT_ROWS.labels(kind="streamed")
    whole = HIST_ONEHOT_ROWS.labels(kind="dense")

    def build(bins_used):
        jax.clear_caches()          # the counters move where a call is traced
        tree.HIST_PATHS.clear()
        before = rows.value, whole.value
        Fend, heap, _, _ = _boost_scan(
            binned, one(edges), yc, w, jnp.ones(3, bool),
            jnp.zeros(binned.shape[0], jnp.float32), keys, dist="bernoulli",
            depth=4, n_bins=b._n_bins, col_rate=1.0, sample_rate=1.0,
            col_tree_rate=1.0, min_rows=5.0, reg_lambda=0.0, reg_alpha=0.0,
            gamma=0.0, min_split_improvement=1e-5, lr=0.3, bootstrap=False,
            drf=False, nclass=0, cat_feats=one(b._cat_feats),
            bins_used=bins_used)
        assert tree.HIST_PATHS == {"pallas": 5}
        return ([np.asarray(a) for a in (*heap, Fend)],
                rows.value - before[0], whole.value - before[1])

    # a traced level call streams 16 + 304 + 24 one-hot rows a row of 3 x 304
    # (jit traces the 1-, 2- and 4-slot call once each), the totals' call 16
    sparse, streamed, of = build(b._bins_used)
    assert (streamed, of) == (3 * 344 + 16, 3 * 912 + 16)
    dense, streamed, of = build(None)
    assert (streamed, of) == (3 * 912 + 16, 3 * 912 + 16)
    assert len(sparse) == 10 and sparse[4].sum() > 8        # is_split
    for got, want in zip(sparse, dense, strict=True):
        np.testing.assert_array_equal(got, want)
    jax.clear_caches()


# --- the cell's kernel and route shapes -------------------------------------

@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pallas_hist, "_INTERPRET", True)


@pytest.mark.parametrize("n_nodes, blocks", [(128, 2), (256, 4)])
def test_kernel_passes_contraction_at_the_cells_bins(interpret, n_nodes,
                                                     blocks):
    """8 features x 301 bins (int16) x 128 / 256 parent slots: node blocks
    of 64, a pass a digit, against a float64 segment sum."""
    F, Bt, rows = 8, 301, 1536
    Nb, Fb, _T = pallas_hist._plan(n_nodes, F, Bt)
    assert (Nb, Fb, -(-n_nodes // Nb)) == (64, 8, blocks)
    assert not pallas_hist._packed(Nb)
    rng = np.random.default_rng(n_nodes)
    binned = rng.integers(0, Bt, size=(rows, F)).astype(bin_dtype(Bt - 1))
    assert binned.dtype == np.int16
    node = rng.integers(-1, n_nodes, size=rows).astype(np.int32)
    g = rng.normal(size=rows).astype(np.float32)
    h = (rng.random(rows) + 0.1).astype(np.float32)
    w = np.ones(rows, np.float32)
    got = pallas_hist.hist_pallas(jnp.asarray(binned.T), jnp.asarray(node),
                                  jnp.asarray(g), jnp.asarray(h),
                                  jnp.asarray(w), n_nodes, Bt)
    want = level_histograms(binned, node, g, h, w, n_nodes, Bt)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=5e-4,
                               atol=5e-3)


@pytest.mark.parametrize("n_nodes, path", [(128, "select"), (256, "gather"),
                                           (512, "gather")])
def test_route_by_member_masks_of_ten_words_a_node(n_nodes, path):
    """300 bins are 10 mask words a node: 1,280 table entries at 128 nodes
    (select), 2,560 and 5,120 past ``_SELECT_MAX_ENTRIES`` (gather); both
    equal a plain two-dimensional gather."""
    n_bins, rows, F = 300, 4001, 8
    rng = np.random.default_rng(n_nodes)
    binned = rng.integers(0, n_bins + 1, size=(rows, F)).astype(np.int16)
    node = rng.integers(-1, n_nodes, size=rows).astype(np.int32)
    do = rng.random(n_nodes) < 0.8
    feat = rng.integers(0, F, size=n_nodes).astype(np.int32)
    na_left = rng.random(n_nodes) < 0.5
    member = rng.random((n_nodes, n_bins)) < 0.5
    leaf = np.where(do, 0.0, rng.normal(size=n_nodes)).astype(np.float32)
    before = ROUTE_LEVELS.labels(path=path).value
    got_node, got_leaf = tree._route_rows(
        jnp.asarray(binned.T), jnp.asarray(node), jnp.zeros(rows, jnp.float32),
        jnp.asarray(feat), jnp.ones(n_nodes, jnp.int32), jnp.asarray(na_left),
        jnp.asarray(do), jnp.asarray(leaf), jnp.asarray(member), n_bins)
    assert ROUTE_LEVELS.labels(path=path).value == before + 1
    assert (n_nodes * 10 > tree._SELECT_MAX_ENTRIES) == (path == "gather")
    live = node >= 0
    nl = np.where(live, node, 0)
    b = binned[np.arange(rows), feat[nl]]
    left = np.where(b >= n_bins, na_left[nl],
                    member[nl, np.minimum(b, n_bins - 1)])
    split = live & do[nl]
    np.testing.assert_array_equal(
        np.asarray(got_node), np.where(split, 2 * nl + np.where(left, 0, 1),
                                       -1))
    np.testing.assert_array_equal(
        np.asarray(got_leaf), np.where(live & ~split, leaf[nl], 0.0))


def test_node_totals_at_the_cells_last_level():
    rows, n = 5000, 1024
    rng = np.random.default_rng(5)
    node = rng.integers(-1, n, size=rows).astype(np.int32)
    g, h = rng.normal(size=(2, rows)).astype(np.float32)
    w = np.ones(rows, np.float32)
    got = np.asarray(tree._node_totals(jnp.asarray(node), jnp.asarray(g),
                                       jnp.asarray(h), jnp.asarray(w), n))
    live = node >= 0
    for k, v in enumerate((g, h, w)):
        want = np.bincount(node[live], v[live].astype(np.float64), n)
        np.testing.assert_allclose(got[:, k], want, rtol=1e-4, atol=1e-4)


def _a_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()), ("rows",))


@pytest.mark.parametrize("interpret, mesh", [
    (False, lambda: None),           # off the TPU: pallas_available says no
    (True, lambda: tree.UNFUSED),    # as if on a TPU, the operand spread
    (True, _a_mesh)],                # ... over a mesh with a rows axis
    ids=["off-the-tpu", "unfused", "mesh"])
def test_node_totals_off_the_kernel_are_three_segment_sums(monkeypatch,
                                                           interpret, mesh):
    """The fallback is the parent's code: bit for bit three
    ``jax.ops.segment_sum``s, and counted as ``scatter``."""
    monkeypatch.setattr(pallas_hist, "_INTERPRET", interpret)
    rows, n = 5000, 64
    rng = np.random.default_rng(6)
    node = jnp.asarray(rng.integers(-1, n, size=rows).astype(np.int32))
    g, h, w = (jnp.asarray(v) for v in
               rng.normal(size=(3, rows)).astype(np.float32))
    tree.HIST_PATHS.clear()
    got = tree._node_totals(node, g, h, w, n, mesh=mesh())
    assert tree.HIST_PATHS == {"scatter": 1}
    live = node >= 0
    want = jnp.stack([jax.ops.segment_sum(
        jnp.where(live, v, 0.0), jnp.where(live, node, 0), num_segments=n)
        for v in (g, h, w)], axis=1)
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  np.asarray(want).view(np.int32))


# --- the timed trees against a float64 replay on the training rows -------

def _replay(out, cols):
    from benchmark.checks import trees_vs_replay as rp
    c, d, x, y = cols
    f0, trees = rp.replay(out, np.stack([c, d, x], axis=1),
                          y.astype(np.float64), 1024, 0.0, 0.0,
                          PARAMS["min_rows"])
    return rp.verdict(out, f0, trees)


def _a_row_sent_the_wrong_way(out, _cols):
    # one occupied level of the root's group split changes sides
    import dataclasses
    t = out["trees"][0]
    assert int(t.feat[0]) == 0                 # the 40-level column
    mask = np.array(t.left_mask)
    mask[0, 0] = ~mask[0, 0]
    return dict(out, trees=[dataclasses.replace(t, left_mask=mask),
                            *out["trees"][1:]])


def _margins_that_never_move(out, _cols):
    return dict(out, trees=[out["trees"][0]] * len(out["trees"]))


def _built_on_half_the_rows(_out, cols):
    half = tuple(col[: len(col) // 2] for col in cols)
    return GBM(**PARAMS).train(y="y", training_frame=_frame(*half)).output


@pytest.mark.parametrize("fault, fails_by", [
    (None, None),
    (_a_row_sent_the_wrong_way, "cover_ulps"),
    (_margins_that_never_move, "leaf_err"),
    (_built_on_half_the_rows, "cover_ulps"),
])
def test_trees_equal_their_replay_on_the_training_rows(built, fault, fails_by):
    """``benchmark/checks/trees_vs_replay.py``: the model's ``cover``,
    ``leaf`` and ``gain`` against node sums recomputed in float64 from the
    training rows sent down its own splits; each planted fault fails by the
    limit named, the sound model by none."""
    from benchmark.checks import trees_vs_replay as rp
    cols, _frame_, model = built
    out = model.output if fault is None else fault(model.output, cols)
    got = _replay(out, cols)
    limits = {"cover_ulps": rp.COVER_ULPS, "leaf_err": rp.LEAF_ATOL,
              "gain_err_per_row": rp.GAIN_ERR_PER_ROW}
    if fails_by is None:
        assert got["ok"], got
        assert all(got[k] <= v for k, v in limits.items())
        assert got["by_tree"][0]["splits"] > 3
    else:
        assert not got["ok"]
        assert got[fails_by] > 10 * limits[fails_by], got
