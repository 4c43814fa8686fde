"""The categorical GBM cell's parts, by hand on the CPU: the plain reference
(``reference/gbm_cat_numpy.py``) against a group split worked by hand, the
masked traversal against a tree written by hand, the configuration's
arithmetic, and the cell's rehearsal (``run.py --selftest``, both modes).

Six rows of ONE node, one categorical feature of four levels (bins a b c d,
then the missing bin), unit hessians:

    level  a   b   c   d   missing
    rows   2   1   2   1   0
    G     -4  +1  +4  -1

G/H by level: a -2, b +1, c +2, d -1: the order is a d b c. Prefixes:
{a} G = -4, H = 2: gain = 1/2 (16/2 + 16/4 - 0) = 6; {a d} G = -5, H = 3:
1/2 (25/3 + 25/3) = 8.33; {a d b} G = -4, H = 4: 1/2 (16/4 + 16/2) = 6. The
best group is {a, d}, which no threshold on the code order a b c d can cut
out: thresholds give {a} 6, {a b} G = -3, H = 3: 3, {a b c} G = 1, H = 5:
1/2 (1/5 + 1) = 0.6.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import plugins
from benchmark.reference import gbm_cat_numpy as ref
from benchmark.reference import tree_traverse_masked as walk

HIST = np.zeros((1, 5, 3))
HIST[0, :4, 0] = [-4, 1, 4, -1]
HIST[0, :4, 1] = HIST[0, :4, 2] = [2, 1, 2, 1]
CELL = "gbm100-airline-cat-build"


def test_group_split_matches_the_hand_calculation():
    gain, feature, t, _na_left, left = ref.best_split(HIST, [True], 1.0)
    assert (feature, t) == (0, 2) and gain == pytest.approx(25 / 3)
    assert left.tolist() == [True, False, False, True]
    gain, _f, t, _na, left = ref.best_split(HIST, [False], 1.0)
    assert (t, gain) == (1, 6.0) and left.tolist() == [True, False, False, False]
    assert ref.split_gain(HIST, 0, [True, True, True, False], False)[0] == \
        pytest.approx(0.6)


def test_min_rows_and_the_missing_bin():
    # {a d} holds 3 rows and so does the rest: min_rows 4 forbids every split
    assert ref.best_split(HIST, [True], 4.0) is None
    # two missing rows with G = -6 join the negative side, whichever it is
    hist = HIST.copy()
    hist[0, 4] = [-6, 2, 2]
    gain, _f, t, na_left, left = ref.best_split(hist, [True], 1.0)
    assert na_left and left.tolist() == [True, False, False, True]
    assert gain == pytest.approx(0.5 * (121 / 5 + 25 / 3 - 36 / 8))
    hist[0, 4] = [6, 2, 2]
    _gain, _f, _t, na_left, left = ref.best_split(hist, [True], 1.0)
    assert not na_left and left.tolist() == [True, False, False, True]


def test_a_level_has_its_own_bin_up_to_nbins_cats():
    X = np.array([[0.0], [299.0], [150.0], [np.nan]])
    assert ref.engine_bins(100, [300], 1024) == 300
    np.testing.assert_array_equal(
        ref.bin_features(X, [None], [300], 1024, 300)[:, 0],
        [0, 299, 150, 300])
    assert ref.engine_bins(100, [300], 64) == 100
    np.testing.assert_array_equal(
        ref.bin_features(X, [None], [300], 64, 100)[:, 0], [0, 63, 32, 100])


def test_fit_finds_the_levels_that_carry_the_response():
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 30, 3000)
    x = rng.normal(size=3000)
    hot = np.isin(codes, [2, 3, 11, 17, 29])
    y = (rng.random(3000) < np.where(hot, 0.9, 0.1)).astype(int)
    model = ref.fit(np.stack([codes, x], 1).astype(float), y,
                    cat_cards=[30, 0], ntrees=1, max_depth=1, nbins=8,
                    learn_rate=1.0, min_rows=10.0)
    root = model.trees[0]
    assert model.n_bins == 30 and root.feature == 0
    sides = {bool(root.left_bins[c]) for c in (2, 3, 11, 17, 29)}
    assert len(sides) == 1                        # the hot levels together
    assert root.left_bins.sum() in (5, 25)
    p = model.predict_proba(np.array([[2.0, 0.0], [4.0, 0.0]]))
    assert p[0] > 0.8 and p[1] < 0.2


def test_masked_traversal_of_a_tree_written_by_hand():
    # root: categorical feature 0, levels {1, 3} left; its right child:
    # numeric feature 1 < 0.5 left; a missing value goes right at both
    tree = types.SimpleNamespace(
        feat=np.array([0, -1, 1, -1, -1, -1, -1]),
        thresh_val=np.array([0, 0, 0.5, 0, 0, 0, 0], np.float32),
        na_left=np.zeros(7, bool),
        is_split=np.array([1, 0, 1, 0, 0, 0, 0], bool),
        leaf=np.array([0, 10, 0, 0, 0, 20, 30], np.float32),
        left_mask=np.zeros((7, 4), bool))
    tree.left_mask[0, [1, 3]] = True
    X = np.array([[1, 9.0], [3, np.nan], [0, 0.1], [2, 0.9], [np.nan, 0.1],
                  [0, np.nan]])
    idx = walk.heap_index(tree, X, np.array([4, 0]), 1024)
    np.testing.assert_array_equal(idx, [1, 1, 5, 6, 5, 6])
    np.testing.assert_array_equal(
        walk.heap_index(tree, X, np.array([4, 0]), 1024, levels=1),
        [1, 1, 2, 2, 2, 2])
    model = types.SimpleNamespace(output=dict(
        trees=[tree], cat_card=np.array([4, 0]), cat_bins=1024, f0=0.0,
        learn_rate=0.1))
    np.testing.assert_allclose(walk.leaf_sum(model, X),
                               [10, 10, 20, 30, 20, 30])


def test_replay_of_a_tree_written_by_hand():
    """checks/trees_vs_replay.py on a depth-2 tree whose sums are worked by
    hand: the sound tree by every limit, a wrong count, leaf and gain each by
    its own."""
    import dataclasses
    from benchmark.checks import trees_vs_replay as rp
    # root splits on the 4-level column, levels {1, 3} left; the right child
    # on x < 0.5; six rows, margins 0: g = 0.5 - y, h = 0.25
    X = np.array([[1, 9.0], [3, 0.0], [0, 0.1], [2, 0.9], [0, 0.2], [2, 0.7]])
    y = np.array([1, 0, 1, 1, 0, 1.0])
    tree = types.SimpleNamespace(
        feat=np.array([0, -1, 1, -1, -1, -1, -1]),
        thresh_val=np.array([0, 0, 0.5, 0, 0, 0, 0], np.float32),
        na_left=np.zeros(7, bool),
        is_split=np.array([1, 0, 1, 0, 0, 0, 0], bool),
        left_mask=np.zeros((7, 4), bool))
    tree.left_mask[0, [1, 3]] = True
    idx = walk.heap_index(tree, X, np.array([4, 0]), 1024)
    np.testing.assert_array_equal(idx, [1, 1, 5, 6, 5, 6])
    tot = rp.heap_totals(tree, rp.node_sums(tree, idx, 0.5 - y,
                                            np.full(6, 0.25)))
    np.testing.assert_allclose(tot[:, 2], [6, 2, 4, 0, 0, 2, 2])
    np.testing.assert_allclose(tot[:, 0], [-1, 0, -1, 0, 0, 0, -1])
    half = lambda g, h: g * g / h          # noqa: E731
    tree.cover = np.array([6, 2, 4, 0, 0, 2, 2], np.float32)
    tree.leaf = np.array([0, 0, 0, 0, 0, 0, 2.0], np.float32)
    tree.gain = np.array(
        [0.5 * (half(0, .5) + half(-1, 1.0) - half(-1, 1.5)), 0,
         0.5 * (half(0, .5) + half(-1, .5) - half(-1, 1.0)), 0, 0, 0, 0],
        np.float32)
    tree = dataclasses.make_dataclass("T", list(vars(tree)))(**vars(tree))
    sound = rp.compare(tree, tot, 0.0, 0.0, 2.0)
    assert sound["cover_ulps"] == 0 and sound["leaf_err"] < 1e-7
    assert sound["gain_err_per_row"] < 1e-7 and sound["splits"] == 2
    assert sound["children_under_min_rows"] == 0
    assert rp.compare(tree, tot, 0.0, 0.0, 3.0)["children_under_min_rows"] == 2
    off = dataclasses.replace(tree, cover=tree.cover + np.eye(7)[5].astype("f4"))
    assert rp.compare(off, tot, 0.0, 0.0, 2.0)["nodes_cover_off"] == 1
    off = dataclasses.replace(tree, leaf=tree.leaf * np.float32(1.1))
    assert rp.compare(off, tot, 0.0, 0.0, 2.0)["leaves_off"] == 1
    off = dataclasses.replace(tree, gain=tree.gain + np.float32(1e-3))
    assert rp.compare(off, tot, 0.0, 0.0, 2.0)["gain_err_per_row"] > 1e-4


def test_the_configurations_arithmetic():
    cfg = plugins.load_json("configs", "gbm-airline-cat-100")
    gen = plugins.load("generators", cfg["data"]["generator"])
    rows, p = cfg["data"]["rows"], cfg["params"]
    cards = gen.cardinalities(rows)
    assert cards == (12, 31, 7, 22, 300, 300)
    assert cfg["data"]["categorical"] == [c[0] for c in gen.CATEGORICALS]
    assert cfg["data"]["features"] == len(gen.NAMES) == 8
    bins = ref.engine_bins(p["nbins"], cards, p["nbins_cats"])
    assert bins == 300
    # the builder's chunk rule (gbm.py), trees a dispatch: the 3 trees are
    # one chunk at the source's rows, three dispatches at the raised size
    def per(n):
        return max(1, min(int(1.5e8 // (n * max(bins, 64) // 64)), 25))
    assert per(cfg["source_values"]["rows"]) == p["ntrees"] == 3
    assert rows == 2 * cfg["source_values"]["rows"] and per(rows) == 1
    # levels whose mask table passes the router's select limit (2,048)
    words = -(-bins // 32)
    gather = [d for d in range(p["max_depth"]) if 2 ** d * words > 2048]
    assert words == 10 and gather == [8, 9]
    manifest = json.load(open(os.path.join(plugins.ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == ["ntrees", "rows"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "build-repeat", 1)
    for m in manifest["per_layer"]:
        if m["name"] in ("program.cat_rank_share",
                         "program.group_levels_share"):
            assert m["workloads"] == [CELL]
        if m["name"] == "kernel.hist_passes_share":
            assert m["workloads"][-1] == CELL and len(m["workloads"]) == 3


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu(tmp_path, trace):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))
    proc = subprocess.run(
        [sys.executable, os.path.join(plugins.HERE, "run.py"), "--selftest",
         "--workload", CELL, "--seed", "3000000019", "--trace", str(trace)],
        cwd=plugins.ROOT, env=env, capture_output=True, text=True,
        timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert set(line["checks"]) >= {
        "hist_vs_segment_sum_engine", "cat_split_vs_reference",
        "predict_vs_traversal", "trees_vs_replay", "auc_vs_cat_reference",
        "builds_identical"}
    assert "not_a_result" in line
    if trace:
        got = line["metrics"]
        assert got["program.group_levels_share"]["value"] == 100.0
        assert got["kernel.hist_passes_share"]["value"] == 40.0
        assert got["program.cat_rank_share"]["value"] > 0
        assert got["builder.bin_compare_share"]["value"] == 25.0
    else:
        assert set(line["metrics"]) == {"train_work_per_s_chip", "setup_s"}
