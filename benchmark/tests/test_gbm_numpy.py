"""The plain reference GBM against a tree worked by hand.

Eight rows, two binned features, unit hessians, g = -y (a gaussian start at
F = 0), y = 1 1 1 1 5 5 9 9:

    feature 0 bins: 0 0 0 0 1 1 1 1     separates the 1s from the rest
    feature 1 bins: 0 1 0 1 0 0 1 1     separates the 5s from the 9s

Root: G = -32, H = 8. Split on feature 0 at t = 1: GL = -4, HL = 4, GR = -28,
HR = 4, gain = 1/2 (16/4 + 784/4 - 1024/8) = 36. Split on feature 1 at t = 1:
left y = 1 1 5 5, right y = 1 1 9 9, gain = 1/2 (144/4 + 400/4 - 128) = 4.
So the root takes feature 0. Its left child holds four equal rows: every
split gains 0, it is a leaf of value -G/H = 1. Its right child (5 5 9 9,
G = -28, H = 4) splits on feature 1: gain = 1/2 (100/2 + 324/2 - 784/4) = 8,
leaves 5 and 9. With min_rows = 3 that split is not allowed (two rows a
side) and the right child is a leaf of value 7.
"""

import numpy as np

from benchmark.reference import gbm_numpy as ref

BINS = np.array([[0, 0], [0, 1], [0, 0], [0, 1],
                 [1, 0], [1, 0], [1, 1], [1, 1]])
Y = np.array([1., 1., 1., 1., 5., 5., 9., 9.])


def grow(min_rows):
    return ref.grow_tree(BINS, -Y, np.ones(8), max_depth=2, nbins=2,
                         min_rows=min_rows, lam=0.0, gamma=0.0,
                         min_split_improvement=1e-5)


def test_best_split_gains_match_the_hand_calculation():
    hist = np.zeros((2, 2, 3))
    for f in range(2):
        for b in range(2):
            rows = BINS[:, f] == b
            hist[f, b] = [-Y[rows].sum(), rows.sum(), rows.sum()]
    gain, feature, t = ref.best_split(hist, 2, 1.0, 0.0, 0.0)
    assert (gain, feature, t) == (36.0, 0, 1)


def test_two_level_tree():
    root = grow(min_rows=1.0)
    assert (root.feature, root.t) == (0, 1)
    assert root.left.feature == -1 and root.left.value == 1.0
    assert (root.right.feature, root.right.t) == (1, 1)
    assert root.right.left.value == 5.0 and root.right.right.value == 9.0
    np.testing.assert_array_equal(ref._predict_tree(root, BINS), Y)


def test_min_rows_forbids_the_second_split():
    root = grow(min_rows=3.0)
    assert root.feature == 0 and root.right.feature == -1
    assert root.right.value == 7.0


def test_fit_learns_a_separable_response():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(2000, 3)).astype(np.float32)
    y = (X[:, 1] > 0.3).astype(int)
    model = ref.fit(X, y, ntrees=5, max_depth=2, nbins=16, learn_rate=0.5,
                    min_rows=10.0, reg_lambda=0.0)
    ybar = y.mean()
    assert np.isclose(model.f0, np.log(ybar / (1 - ybar)))
    assert all(t.feature == 1 for t in model.trees)      # the only signal
    # 0.3 falls inside one of the 16 bins, so that bin's rows stay mixed
    from benchmark.reference.auc import auc
    assert auc(y, model.predict_proba(X)) > 0.99


def test_auc_by_ranks_with_ties():
    from benchmark.reference.auc import auc
    assert auc([0, 0, 1, 1], [0.1, 0.2, 0.3, 0.4]) == 1.0
    assert auc([0, 1, 0, 1], [0.5, 0.5, 0.5, 0.5]) == 0.5
    # one of four pairs tied, three right: (3 + 0.5) / 4
    assert auc([0, 0, 1, 1], [0.1, 0.3, 0.3, 0.4]) == 0.875
