"""One tree walk (``models/tree.py`` ``_walk``).

How a row walks a heap-laid tree is written once; ``predict_binned``,
``fold_binned``, ``predict_raw`` and the boost scan's
``gbm._traverse_heap_device`` differ only in what a row's value is compared
with, what "missing" means and what the leaf is added to. Each is held here
to a plain numpy loop (the one ``genmodel/codegen.py`` emits is its model)
on the same small stacked ensembles: numeric splits and group splits, with
NaNs / the NA bin. It is integer routing plus float32 additions in a fixed
order, so the comparison is exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.models import gbm, tree

ROWS, F, DEPTH, NTREES, NBINS = 257, 6, 4, 5, 16
NODES = 2 ** (DEPTH + 1) - 1
CAT_CARD = np.array([40, 7, 0, 0, 0, 0], np.int32)   # 40 > NBINS: grouped
#: a power of two: ``lr * leaf`` is then exact, so a compiler that fuses the
#: multiply into the add (XLA's CPU backend does) rounds as numpy does and
#: only the ORDER of the float32 additions is left to differ
LR = np.float32(0.125)


def _ensemble(masked: bool):
    """Random stacked trees as numpy arrays [NTREES, NODES(, NBINS)]."""
    rng = np.random.default_rng(7 + masked)
    sp = rng.random((NTREES, NODES)) < 0.8
    sp[:, :7] = True                            # rows go three levels deep
    sp[:, NODES // 2:] = False                  # the last level holds leaves
    feat = np.where(sp, rng.integers(0, F, (NTREES, NODES)), -1)
    return dict(
        feat=feat.astype(np.int32), is_split=sp,
        thresh_bin=rng.integers(1, NBINS, (NTREES, NODES)).astype(np.int32),
        thresh_val=rng.normal(size=(NTREES, NODES)).astype(np.float32),
        na_left=rng.random((NTREES, NODES)) < 0.5,
        leaf=rng.normal(size=(NTREES, NODES)).astype(np.float32),
        left_mask=(rng.random((NTREES, NODES, NBINS)) < 0.5) if masked
        else None)


def _trees(ens) -> list:
    z = jnp.zeros(NODES)
    return [tree.Tree(gain=z, cover=z, **{
        k: None if v is None else jnp.asarray(v[t]) for k, v in ens.items()})
        for t in range(NTREES)]


def _data(masked: bool):
    rng = np.random.default_rng(3)
    binned = rng.integers(0, NBINS + 1, (ROWS, F)).astype(np.int8)  # NBINS=NA
    X = rng.normal(size=(ROWS, F)).astype(np.float32)
    if masked:
        X[:, :2] = rng.integers(0, CAT_CARD[:2], (ROWS, 2))
    X[rng.random((ROWS, F)) < 0.1] = np.nan
    return binned, X


def _walk_numpy(ens, t, missing, goes_left):
    """The reference: one tree, one row vector, no jax."""
    idx = np.zeros(ROWS, np.int64)
    for _ in range(DEPTH):
        f = np.maximum(ens["feat"][t][idx], 0)
        left = np.where(missing(f), ens["na_left"][t][idx],
                        goes_left(t, idx, f))
        idx = np.where(ens["is_split"][t][idx], idx * 2 + np.where(left, 1, 2),
                       idx)
    return idx


def _leaves_binned(ens, binned):
    """[NTREES, ROWS] leaf values by the bins."""
    r = np.arange(ROWS)

    def goes_left(t, idx, f):
        b = binned[r, f].astype(np.int64)
        if ens["left_mask"] is None:
            return b < ens["thresh_bin"][t][idx]
        return ens["left_mask"][t][idx, np.minimum(b, NBINS - 1)]

    return np.stack([
        ens["leaf"][t][_walk_numpy(ens, t, lambda f: binned[r, f] >= NBINS,
                                   goes_left)]
        for t in range(NTREES)])


def _leaves_raw(ens, X):
    """[NTREES, ROWS] leaf values by the raw values (codegen's loop)."""
    r = np.arange(ROWS)

    def goes_left(t, idx, f):
        x = X[r, f]
        below = x < ens["thresh_val"][t][idx]
        if ens["left_mask"] is None:
            return below
        code = np.nan_to_num(x, nan=0.0).astype(np.int64)
        card = CAT_CARD[f]
        b = np.where(card > NBINS, (code * NBINS) // np.maximum(card, 1), code)
        in_mask = ens["left_mask"][t][idx, np.clip(b, 0, NBINS - 1)]
        return np.where(card > 0, in_mask, below)

    return np.stack([
        ens["leaf"][t][_walk_numpy(ens, t, lambda f: np.isnan(X[r, f]),
                                   goes_left)]
        for t in range(NTREES)])


def _fold(acc, steps, scale=None):
    """float32 additions in tree order, as the scan makes them."""
    for s in steps:
        acc = acc + (s if scale is None else scale * s)
    return acc


@pytest.mark.parametrize("masked", [False, True], ids=["numeric", "groups"])
@pytest.mark.parametrize("entry", ["predict_binned", "fold_binned",
                                   "predict_raw", "_traverse_heap_device"])
def test_traversal_equals_the_plain_loop(entry, masked):
    ens, (binned, X) = _ensemble(masked), _data(masked)
    trees = _trees(ens)
    zero = np.zeros(ROWS, np.float32)
    if entry == "predict_binned":
        got = tree.predict_binned(jnp.asarray(binned), trees, NBINS)
        want = _fold(zero, _leaves_binned(ens, binned))
    elif entry == "fold_binned":
        F0 = np.random.default_rng(5).normal(size=ROWS).astype(np.float32)
        got = tree.fold_binned(jnp.asarray(binned), trees, NBINS, LR,
                               jnp.asarray(F0))
        want = _fold(F0, _leaves_binned(ens, binned), LR)
    elif entry == "predict_raw":
        kw = dict(cat_card=jnp.asarray(CAT_CARD), n_bins=NBINS) if masked \
            else {}
        got = tree.predict_raw(jnp.asarray(X), trees, **kw)
        want = _fold(zero, _leaves_raw(ens, X))
    else:
        t = trees[1]
        heap = [t.feat, t.thresh_bin, t.thresh_val, t.na_left, t.is_split,
                t.leaf, t.gain, t.cover] + ([t.left_mask] if masked else [])
        got = jax.jit(lambda b, h: gbm._traverse_heap_device(
            b, h, NBINS, masked))(jnp.asarray(binned), heap)
        want = _leaves_binned(ens, binned)[1]
    got = np.asarray(got)
    assert got.dtype == np.float32 and want.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_every_row_ends_in_a_leaf_and_both_sides_are_taken():
    """The cases above are not hollow: rows reach the last level, both
    children and the NA direction are taken, and frozen nodes stop rows."""
    ens, (binned, _) = _ensemble(False), _data(False)
    r = np.arange(ROWS)
    idx = _walk_numpy(ens, 0, lambda f: binned[r, f] >= NBINS,
                      lambda t, i, f: binned[r, f] < ens["thresh_bin"][t][i])
    assert not ens["is_split"][0][idx].any()
    assert idx.max() >= NODES // 2 and len(np.unique(idx)) > 4
    assert (binned >= NBINS).any() and ens["na_left"].any() \
        and not ens["na_left"].all()
