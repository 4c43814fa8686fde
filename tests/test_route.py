"""Row routing by compare-and-select (``models/tree.py`` ``_route_rows``,
PR 27).

After a level's splits every row moves from its node to a child. The parent
read a row's split out of its node's tables, and the split feature's bin out
of ``binned[rows, F]``, by per-row gathers (10-15 ns a row on the v5e,
whatever the table's size); the engine now reads them by broadcast
compare-and-select over the small axis. It is integer work on the same
bins, thresholds and masks: the result must equal the gathers' bit for bit,
and the numeric route must stay free of ``gather``.

The sibling subtraction reads each row's parent's chosen child the same way
(``_sibling_slots`` through ``_lookup``), where ``chosen[par]`` was a per-row
index that ``vmap`` turns into a batched gather: the same bits, and no gather
under the class axis up to the crossover.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.models import tree
from h2o3_tpu.models.tree import TreeParams, grow_trees_batched
from h2o3_tpu.ops.quantile import bin_dtype
from h2o3_tpu.utils.telemetry import ROUTE_LEVELS, SIBLING_LEVELS

PAST_CROSSOVER = 2 * tree._SELECT_MAX_ENTRIES


def route_rows_by_gather(binned_T, node_local, row_leaf, feat, t, na_left,
                         do_split, leaf, member, n_bins):
    """The plain reference: what the engine did before PR 27, a gather for
    every per-row read (``table[node]``, ``take_along_axis`` on the
    row-major bins, the two-dimensional ``member[node, bin]``)."""
    active = node_local >= 0
    nl = jnp.where(active, node_local, 0)
    row_leaf = jnp.where(active & ~do_split[nl], leaf[nl], row_leaf)
    if member is None:
        member = jnp.arange(n_bins)[None, :] < t[:, None]
    f = jnp.maximum(feat, 0)[nl]
    split = do_split[nl] & active
    b = jnp.take_along_axis(binned_T.T, f[:, None], axis=1)[:, 0]
    left = jnp.where(b >= n_bins, na_left[nl],
                     member[nl, jnp.minimum(b, n_bins - 1)])
    child = nl * 2 + jnp.where(left, 0, 1)
    return jnp.where(split, child, -1), row_leaf


def _level(n_nodes: int, n_bins: int, seed: int, rows: int = 3001, F: int = 7,
           cat: bool = False):
    """One level's inputs with every difficulty in them: frozen rows, the
    missing bin under both ``na_left``, nodes that do not split, leaf values
    of either zero."""
    rng = np.random.default_rng(seed)
    binned = rng.integers(0, n_bins + 1, size=(rows, F))
    binned[rng.random((rows, F)) < 0.1] = n_bins          # missing
    node = rng.integers(0, n_nodes, size=rows).astype(np.int32)
    node[rng.random(rows) < 0.15] = -1                    # frozen rows
    do = rng.random(n_nodes) < 0.7
    do[0], do[-1] = True, n_nodes == 1                    # both kinds of node
    feat = rng.integers(0, F, size=n_nodes).astype(np.int32)
    t = rng.integers(1, n_bins, size=n_nodes).astype(np.int32)
    leaf = rng.normal(size=n_nodes).astype(np.float32)
    leaf[::3] = -0.0
    leaf = np.where(do, np.float32(0.0), leaf)
    member = (rng.random((n_nodes, n_bins)) < 0.5) if cat else None
    return dict(
        binned_T=jnp.asarray(binned.T.astype(bin_dtype(n_bins))),
        node_local=jnp.asarray(node),
        row_leaf=jnp.asarray(rng.normal(size=rows).astype(np.float32)),
        feat=jnp.asarray(feat), t=jnp.asarray(t),
        na_left=jnp.asarray(rng.random(n_nodes) < 0.5),
        do_split=jnp.asarray(do), leaf=jnp.asarray(leaf),
        member=None if member is None else jnp.asarray(member))


def _assert_same(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        # floats by their bits: -0.0 and 0.0 are different leaves
        np.testing.assert_array_equal(g.view(np.int32), w.view(np.int32))


@pytest.mark.parametrize("n_bins", [64, 256])              # int8, int16 bins
@pytest.mark.parametrize("n_nodes", [1, 2, 32, 64, PAST_CROSSOVER])
def test_route_rows_equals_the_gathers(n_nodes, n_bins):
    a = _level(n_nodes, n_bins, seed=n_nodes + n_bins)
    assert a["binned_T"].dtype == (jnp.int8 if n_bins == 64 else jnp.int16)
    node, _ = got = tree._route_rows(**a, n_bins=n_bins)
    _assert_same(got, route_rows_by_gather(**a, n_bins=n_bins))
    node = np.asarray(node)
    assert (node[np.asarray(a["node_local"]) < 0] == -1).all()
    assert (node >= 0).any() and (node < 2 * n_nodes).all()


@pytest.mark.parametrize("n_bins", [16, 64, 256])
@pytest.mark.parametrize("n_nodes", [1, 32, PAST_CROSSOVER])
def test_route_rows_by_member_masks_equals_the_gathers(n_nodes, n_bins):
    a = _level(n_nodes, n_bins, seed=3 * n_nodes + n_bins, cat=True)
    _assert_same(tree._route_rows(**a, n_bins=n_bins),
                 route_rows_by_gather(**a, n_bins=n_bins))


def test_a_frame_too_wide_for_one_word_takes_two(monkeypatch):
    """Two flags, the threshold and the feature share an int32 while they
    fit (the cells: 2 + 9 + 5 bits); past that the feature rides in a word
    of its own, and the answer is the same."""
    lookups = []
    real = tree._lookup
    monkeypatch.setattr(tree, "_lookup",
                        lambda tb, ix: lookups.append(tb.shape) or real(tb, ix))
    a = _level(8, 256, seed=4, rows=64, F=28)
    tree._route_rows(**a, n_bins=256)
    assert len(lookups) == 2                # the packed word, the leaf
    n_bins, F = 16384, 20_000               # 2 + 15 bits, then 15 for 19,999
    a = _level(8, n_bins, seed=5, rows=64, F=F)
    del lookups[:]
    got = tree._route_rows(**a, n_bins=n_bins)
    assert len(lookups) == 3                # and the feature's own word
    _assert_same(got, route_rows_by_gather(**a, n_bins=n_bins))


def test_route_rows_under_vmap():
    """The multinomial round grows its K class trees under one ``vmap``:
    node ids and tables batched, the bins shared."""
    n_bins, K = 64, 3
    levels = [_level(16, n_bins, seed=k) for k in range(K)]
    shared = levels[0]["binned_T"]
    keys = [k for k in levels[0] if k not in ("binned_T", "member")]
    stacked = {k: jnp.stack([lv[k] for lv in levels]) for k in keys}
    got = jax.vmap(lambda kw: tree._route_rows(
        shared, member=None, n_bins=n_bins, **kw))(stacked)
    for k in range(K):
        want = route_rows_by_gather(**dict(levels[k], binned_T=shared),
                                    n_bins=n_bins)
        _assert_same([g[k] for g in got], want)


@pytest.mark.parametrize("n", [1, 64, PAST_CROSSOVER])
def test_leaf_lookup_equals_the_gather(n):
    """The last level's ``row_leaf = leaf[node]``: float32 selected as
    bits."""
    rng = np.random.default_rng(n)
    leaf = rng.normal(size=n).astype(np.float32)
    leaf[::2] = -0.0
    node = rng.integers(-1, n, size=999).astype(np.int32)
    got = np.asarray(tree._lookup_f32(jnp.asarray(leaf), jnp.asarray(node)))
    want = np.where(node >= 0, leaf[np.maximum(node, 0)], np.float32(0.0))
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


# -- whole trees: the engine with its routing swapped for the reference ------

def _frame(kind: str, rows: int = 1500, F: int = 6, n_bins: int = 32):
    rng = np.random.default_rng(11)
    binned = rng.integers(0, n_bins, size=(rows, F))
    binned[rng.random((rows, F)) < 0.05] = n_bins
    x = binned[:, :3].sum(1) + 4.0 * (binned[:, 3] % 3 == 1)
    K = 3 if kind == "multinomial" else 1
    g = rng.normal(size=(K, rows)).astype(np.float32) - (x / x.max())[None, :]
    h = np.ones((K, rows), np.float32)
    w = np.ones((K, rows), np.float32)
    w[:, ::17] = 0.0
    kw = {}
    if kind == "categorical":
        kw["cat_feats"] = jnp.asarray(np.arange(F) == 3)
    if kind == "mono":
        kw["mono"] = jnp.asarray([1, -1, 0, 0, 0, 0], jnp.int32)
    if kind == "reach":
        r = np.eye(F, dtype=bool)
        r[:2, :2] = True
        kw["reach"] = jnp.asarray(r)
    edges = jnp.asarray(np.tile(np.arange(1, n_bins, dtype=np.float32), (F, 1)))
    return (jnp.asarray(binned.astype(bin_dtype(n_bins))), edges,
            jnp.asarray(g), jnp.asarray(h), jnp.asarray(w)), kw


@pytest.fixture
def fresh_programs():
    """``_grow_batched`` keeps its compiled programs by argument signature,
    which a swapped global does not change: drop them around the test."""
    def drop():
        tree._grow_batched.clear_executables()
        tree._grow_batched._jit.clear_cache()
    drop()
    yield drop
    drop()


TREE_KINDS = [("numeric", 3), ("numeric", 6), ("categorical", 4), ("mono", 4),
              ("reach", 4), ("multinomial", 3)]


def _assert_trees_bit_equal_with(name, reference, kind, depth, monkeypatch,
                                 fresh_programs):
    """Grow with the engine, then with ``tree.<name>`` swapped for its plain
    ``reference``: every tree array and the predictions bit for bit."""
    args, kw = _frame(kind)
    params = TreeParams(max_depth=depth, nbins=32, min_rows=2.0)
    fmask = jnp.ones(args[0].shape[1], bool)

    def grow():
        trees, preds = grow_trees_batched(*args, params, fmask, **kw)
        return [[np.asarray(getattr(t, f.name)) for f in
                 tree.dataclasses.fields(t) if getattr(t, f.name) is not None]
                for t in trees], np.asarray(preds)

    new_trees, new_preds = grow()
    monkeypatch.setattr(tree, name, reference)
    fresh_programs()
    old_trees, old_preds = grow()

    assert len(new_trees) == (3 if kind == "multinomial" else 1)
    assert any(tr[4].sum() >= depth for tr in new_trees)   # is_split: it grew
    for new, old in zip(new_trees, old_trees):
        assert len(new) == len(old) == (9 if kind == "categorical" else 8)
        for a, b in zip(new, old):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))
    np.testing.assert_array_equal(new_preds.view(np.int32),
                                  old_preds.view(np.int32))


@pytest.mark.parametrize("kind,depth", TREE_KINDS)
def test_trees_bit_equal_with_routing_by_gather(kind, depth, monkeypatch,
                                                fresh_programs):
    _assert_trees_bit_equal_with("_route_rows", route_rows_by_gather, kind,
                                 depth, monkeypatch, fresh_programs)


def sibling_slots_by_gather(node_local, chosen_left):
    """The plain reference: each row's parent's chosen child read by a
    per-row index into the table (``chosen[par]``), which ``vmap`` makes a
    batched gather."""
    P = chosen_left.shape[0]
    chosen = jnp.arange(P) * 2 + jnp.where(chosen_left, 0, 1).astype(jnp.int32)
    act = node_local >= 0
    par = jnp.where(act, node_local // 2, 0)
    at_chosen = act & (node_local == chosen[par])
    return jnp.where(at_chosen, par, -1)


@pytest.mark.parametrize("kind,depth", TREE_KINDS)
def test_trees_bit_equal_with_sibling_by_gather(kind, depth, monkeypatch,
                                                fresh_programs):
    _assert_trees_bit_equal_with("_sibling_slots", sibling_slots_by_gather,
                                 kind, depth, monkeypatch, fresh_programs)


@pytest.mark.parametrize("n_parents", [1, 8, 256, PAST_CROSSOVER])
def test_sibling_slots_equal_the_gather_under_vmap(n_parents):
    """Seven class trees' levels at once: frozen rows, both children of
    every parent, either child chosen."""
    K, rows = 7, 2999
    rng = np.random.default_rng(n_parents)
    node = rng.integers(0, 2 * n_parents, size=(K, rows)).astype(np.int32)
    node[rng.random((K, rows)) < 0.15] = -1
    chosen_left = jnp.asarray(rng.random((K, n_parents)) < 0.5)
    node = jnp.asarray(node)
    got = np.asarray(jax.vmap(tree._sibling_slots)(node, chosen_left))
    want = np.asarray(jax.vmap(sibling_slots_by_gather)(node, chosen_left))
    np.testing.assert_array_equal(got, want)
    assert (got >= 0).any() and (got < 0).any()


# -- what a CPU can hold for the chip ----------------------------------------

def _lowered(fn, n_nodes: int, n_bins: int = 64, rows: int = 4096, F: int = 28):
    s = jax.ShapeDtypeStruct
    return jax.jit(partial(fn, member=None, n_bins=n_bins)).lower(
        s((F, rows), bin_dtype(n_bins)), s((rows,), jnp.int32),
        s((rows,), jnp.float32), s((n_nodes,), jnp.int32),
        s((n_nodes,), jnp.int32), s((n_nodes,), jnp.bool_),
        s((n_nodes,), jnp.bool_), s((n_nodes,), jnp.float32)).as_text()


@pytest.mark.parametrize("n_bins", [64, 256])
@pytest.mark.parametrize("n_nodes", [1, 32])
def test_numeric_route_lowers_to_no_gather(n_nodes, n_bins):
    """A gather costs the v5e 10-15 ns a row (17.5 s of a 28 s GBM-64
    build); broadcast compares reduced over the nodes and the features cost
    single milliseconds a level. The cells' levels (N <= 32) hold none."""
    text = _lowered(tree._route_rows, n_nodes, n_bins)
    assert "gather" not in text and "while" not in text
    assert "compare" in text and "select" in text and "reduce" in text
    # the guard guards: the routing this replaced gathers
    assert "gather" in _lowered(route_rows_by_gather, n_nodes, n_bins)


def test_leaf_lookup_lowers_to_no_gather_up_to_the_crossover():
    s = jax.ShapeDtypeStruct
    rows = s((4096,), jnp.int32)
    lower = lambda n: jax.jit(tree._lookup_f32).lower(
        s((n,), jnp.float32), rows).as_text()
    assert "gather" not in lower(64)
    assert "gather" not in lower(tree._SELECT_MAX_ENTRIES)
    assert "gather" in lower(PAST_CROSSOVER)


def _lowered_vmapped_slots(fn, n_parents: int, classes: int = 7,
                           rows: int = 4096):
    s = jax.ShapeDtypeStruct
    return jax.jit(jax.vmap(fn)).lower(
        s((classes, rows), jnp.int32),
        s((classes, n_parents), jnp.bool_)).as_text()


@pytest.mark.parametrize("n_parents", [1, 8, 256, tree._SELECT_MAX_ENTRIES])
def test_sibling_slots_lower_to_no_gather_under_vmap(n_parents):
    """A multinomial round's class trees under ``vmap``: the parents' table
    read by compare-and-select up to the crossover, no batched gather (about
    9 ns a row and class on the v5e, 81% of the covtype build)."""
    text = _lowered_vmapped_slots(tree._sibling_slots, n_parents)
    assert "gather" not in text
    assert "compare" in text and "select" in text and "reduce" in text


def test_sibling_slots_gather_past_the_crossover():
    assert "gather" in _lowered_vmapped_slots(tree._sibling_slots,
                                              PAST_CROSSOVER)
    # the guard guards: the form this replaced gathers at 8 parents
    assert "gather" in _lowered_vmapped_slots(sibling_slots_by_gather, 8)


# -- the counter --------------------------------------------------------------

def _levels(path: str) -> float:
    return ROUTE_LEVELS.labels(path=path).value


def test_counter_rises_by_depth_a_traced_tree(fresh_programs):
    args, kw = _frame("numeric")
    fmask = jnp.ones(args[0].shape[1], bool)
    select, gather = _levels("select"), _levels("gather")
    grow_trees_batched(*args, TreeParams(max_depth=5, nbins=32), fmask)
    assert _levels("select") - select == 5
    assert _levels("gather") - gather == 0
    # counted where the program is TRACED: a cached program adds nothing
    grow_trees_batched(*args, TreeParams(max_depth=5, nbins=32), fmask)
    assert _levels("select") - select == 5


def test_counter_names_the_path_past_the_crossover(monkeypatch, fresh_programs):
    args, kw = _frame("numeric")
    fmask = jnp.ones(args[0].shape[1], bool)
    monkeypatch.setattr(tree, "_SELECT_MAX_ENTRIES", 8)
    select, gather = _levels("select"), _levels("gather")
    grow_trees_batched(*args, TreeParams(max_depth=5, nbins=32), fmask)
    assert _levels("select") - select == 4      # N = 1, 2, 4, 8
    assert _levels("gather") - gather == 1      # N = 16


def _sibling(path: str) -> float:
    return SIBLING_LEVELS.labels(path=path).value


def test_sibling_counter_rises_by_depth_less_one_a_traced_tree(fresh_programs):
    args, kw = _frame("numeric")
    fmask = jnp.ones(args[0].shape[1], bool)
    select, gather = _sibling("select"), _sibling("gather")
    grow_trees_batched(*args, TreeParams(max_depth=5, nbins=32), fmask)
    assert _sibling("select") - select == 4     # levels 1-4; level 0 has none
    assert _sibling("gather") - gather == 0
    grow_trees_batched(*args, TreeParams(max_depth=5, nbins=32), fmask)
    assert _sibling("select") - select == 4     # a cached program adds nothing


def test_sibling_counter_names_the_gather_past_the_crossover(monkeypatch,
                                                             fresh_programs):
    args, kw = _frame("multinomial")
    fmask = jnp.ones(args[0].shape[1], bool)
    monkeypatch.setattr(tree, "_SELECT_MAX_ENTRIES", 4)
    select, gather = _sibling("select"), _sibling("gather")
    grow_trees_batched(*args, TreeParams(max_depth=5, nbins=32), fmask)
    # one trace for the three class trees under vmap, as the route counts
    assert _sibling("select") - select == 3     # P = 1, 2, 4
    assert _sibling("gather") - gather == 1     # P = 8


def test_sibling_select_share_reads_the_counter_at_the_windows_end():
    from benchmark.plugins import load
    metric = load("layer_metrics", "program.sibling_select_share")
    assert (metric.LAYER, metric.UNIT, metric.MOVES) == (
        "program", "%", "train_work_per_s_chip")
    name = "h2o3_sibling_levels_total"

    class Reading:
        after = {"metrics": [(name, {"path": "select"}, 8.0),
                             (name, {"path": "gather"}, 1.0)]}

    assert metric.read(Reading) == pytest.approx(100 * 8 / 9)

    class Parent:                                # no such counter: left out
        after = {"metrics": [
            ("h2o3_route_levels_total", {"path": "select"}, 6.0)]}

    assert metric.read(Parent) is None
