"""Binning by compare-and-count (``ops/quantile.py``, PR 25).

A numeric column's bin is the count of its edges <= x, computed by broadcast
compares reduced over the edge axis. It must equal
``np.searchsorted(e, x, side="right")`` bit for bit (the binary search it
replaced), and must stay free of what made that search slow on the TPU: a
gather from the edge table at every step of a ``while``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.gbm import GBM, tree_matrix
from h2o3_tpu.ops import quantile
from h2o3_tpu.utils.telemetry import BIN_COLUMNS

EDGE_COUNTS = (15, 63, 127, 128, 255, 1023)


def _edges(n: int, pad: int = 0) -> np.ndarray:
    """``n`` sorted float32 edges with 0.0 among them, the last ``pad``
    replaced by the inf padding ``compute_bin_edges`` leaves."""
    e = np.sort(np.random.default_rng(n).normal(size=n).astype(np.float32))
    e[np.searchsorted(e, 0.0)] = 0.0
    e = np.sort(e)
    if pad:
        e[-pad:] = np.inf
    return e


def _case(kind: str, n: int):
    """(edges, values) of one kind of difficulty at ``n`` edges."""
    body = np.random.default_rng(7).normal(size=257).astype(np.float32)
    if kind == "ties":                    # every edge itself, and its neighbours
        e = _edges(n)
        x = np.concatenate([e, np.nextafter(e, np.float32(-np.inf)),
                            np.nextafter(e, np.float32(np.inf)), body])
        # XLA flushes subnormals to zero, in this and in the search it
        # replaced alike; numpy does not. 0.0's two neighbours are out.
        return e, x[(x == 0) | (np.abs(x) >= np.finfo(np.float32).tiny)]
    if kind == "infinities":
        return _edges(n), np.array([np.inf, -np.inf, *body[:8]], np.float32)
    if kind == "negative_zero":           # -0.0 == 0.0: right of the edge at 0.0
        return _edges(n), np.array([-0.0, 0.0, *body[:8]], np.float32)
    if kind == "nan":
        x = body.copy()
        x[::5] = np.nan
        return _edges(n), x
    if kind == "inf_padded_edges":        # a column with few distinct values
        e = _edges(n, pad=n // 3 + 1)
        return e, np.concatenate([body, e, [np.inf, -np.inf, np.nan]]).astype(np.float32)
    if kind == "all_inf_edges":           # an all-NaN column's row of edges
        e = np.full(n, np.inf, np.float32)
        return e, np.array([np.nan, 0.0, -1.5, np.inf, -np.inf, *body[:8]], np.float32)
    raise AssertionError(kind)


@pytest.mark.parametrize("n_edges", EDGE_COUNTS)
@pytest.mark.parametrize("kind", ["ties", "infinities", "negative_zero", "nan",
                                  "inf_padded_edges", "all_inf_edges"])
def test_bin_column_equals_numpy_searchsorted_right(kind, n_edges):
    e, x = _case(kind, n_edges)
    nbins = n_edges + 1
    want = np.searchsorted(e, x, side="right")
    want[np.isnan(x)] = nbins             # the missing bin
    got = quantile.bin_column(jnp.asarray(x), jnp.asarray(e))
    assert got.dtype == quantile.bin_dtype(nbins)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_compiled_binning_has_no_gather_and_no_loop():
    """What a CPU can hold for the chip: at 255 edges the default
    ``jnp.searchsorted`` lowers to a ``while`` of gathers (0.76 s a column
    of 11M rows on the v5e); compare-and-count lowers to neither."""
    col = jax.ShapeDtypeStruct((4096,), jnp.float32)
    e = jax.ShapeDtypeStruct((255,), jnp.float32)
    text = quantile._bin_by_compare.lower(col, e).as_text()
    assert "gather" not in text and "while" not in text
    assert "compare" in text and "reduce" in text
    # the guard guards: the search this replaced has both
    scan = jax.jit(lambda c, e: jnp.searchsorted(e, c, side="right"))
    old = scan.lower(col, e).as_text()
    assert "gather" in old and "while" in old


def _mixed_frame(n: int = 500) -> Frame:
    rng = np.random.default_rng(3)
    x0 = rng.normal(size=n).astype(np.float32)
    x0[::7] = np.nan
    x1 = np.round(rng.normal(size=n), 1).astype(np.float32)   # many ties
    c = np.array(["a", "b", "c", "d"], dtype=object)[rng.integers(0, 4, n)]
    c[::11] = None
    y = (rng.random(n) < 0.5).astype(np.float32)
    return Frame.from_arrays({"x0": x0, "c": c, "x1": x1, "y": y})


@pytest.mark.parametrize("nbins", [16, 64, 256])
def test_bin_frame_and_bin_features_agree(nbins):
    """The training frame's binning (a column at a time, categorical
    columns by level code) and the validation frame's (``bin_features``
    over the raw matrix, then ``_apply_cat_bins``) give one matrix."""
    fr, x = _mixed_frame(), ["x0", "c", "x1"]
    b = GBM(ntrees=1, nbins=nbins)
    _, edges, binned, *_rest, domains = b._prepare(fr, x, "y")
    assert b._cat_info is not None        # the frame has a categorical column
    X = tree_matrix(fr, x, domains)
    via_features = b._apply_cat_bins(X, quantile.bin_features(X, edges))
    assert binned.dtype == via_features.dtype == quantile.bin_dtype(nbins)
    got, want = np.asarray(binned)[:fr.nrows], np.asarray(via_features)[:fr.nrows]
    np.testing.assert_array_equal(got, want)
    # and both are numpy's answer on the numeric columns, NaN → nbins
    for j in (0, 2):
        col = np.asarray(X[:fr.nrows, j])
        ref = np.searchsorted(np.asarray(edges[j]), col, side="right")
        ref[np.isnan(col)] = nbins
        np.testing.assert_array_equal(got[:, j], ref)
    assert (got[np.isnan(np.asarray(X[:fr.nrows, 1])), 1] == nbins).all()


def _compare_count() -> float:
    return BIN_COLUMNS.labels(path="compare").value


def test_counter_counts_the_numeric_columns_a_build_bins():
    fr = _mixed_frame()
    before = _compare_count()
    levels = BIN_COLUMNS.labels(path="levels")
    levels_before = levels.value
    GBM(ntrees=2, max_depth=2, nbins=16, seed=1).train(
        x=["x0", "c", "x1"], y="y", training_frame=fr)
    assert _compare_count() - before == 2   # x0 and x1; c is categorical
    assert levels.value - levels_before == 1    # ... and bins by level code
    paths = {labels["path"] for labels, _ in BIN_COLUMNS.children()}
    assert paths == {"compare", "levels"}   # one path a kind of column


def test_counter_counts_a_validation_frames_columns():
    X = jnp.zeros((64, 5), jnp.float32)
    edges = jnp.asarray(np.tile(_edges(15), (5, 1)))
    before = _compare_count()
    quantile.bin_features(X, edges)
    assert _compare_count() - before == 5
