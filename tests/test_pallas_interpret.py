"""Pallas histogram kernel semantics, validated OFF-TPU via interpret mode
(the kernel itself only dispatches on real TPU — ``pallas_available`` gates
on backend — but its math must be checkable in CI; VERDICT r3 next #3).

Covers the MXU precision modes: "hilo" (2 bf16 digits, default), "hilo3"
(3 digits, f32-exact), "highest" (6-pass reference mode) — all against the
XLA segment-sum ground truth — and every case the body distinguishes: the
digits side by side in one product or a pass each, int8 and int16 bins,
feature blocks narrower than the frame, rows short of a tile, one node,
several node blocks, a call under ``vmap``; ``_plan``'s VMEM account; the
last level's per-node totals as the kernel's one-feature call
(``tree._node_totals``); and ``bins_used``: the one-hot rows a column's bins
cannot match are skipped, the histograms are the dense call's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.models.tree import _level_histograms
from h2o3_tpu.ops import pallas_hist
from h2o3_tpu.utils import telemetry


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(pallas_hist, "_INTERPRET", True)
    pallas_hist.hist_pallas._clear_cache()
    yield
    pallas_hist.hist_pallas._clear_cache()


def _data(rng, R, F, B, N, dtype=np.int16):
    binned = rng.integers(0, B + 1, size=(R, F)).astype(dtype)
    node = rng.integers(-1, N, size=R).astype(np.int32)
    g = rng.normal(size=R).astype(np.float32)
    h = rng.random(R).astype(np.float32) + 0.1
    w = np.ones(R, np.float32)
    return (jnp.asarray(binned), jnp.asarray(node), jnp.asarray(g),
            jnp.asarray(h), jnp.asarray(w))


def _check(rng, R, F, B, N, rtol, atol, dtype=np.int16):
    binned, node, g, h, w = _data(rng, R, F, B, N, dtype)
    want = _level_histograms(binned, node, g, h, w, N, B + 1)
    got = pallas_hist.hist_pallas(binned.T, node, g, h, w, N, B + 1)
    assert got.shape == want.shape == (F, N * (B + 1), 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


def _set_mode(monkeypatch, mode):
    monkeypatch.setattr(pallas_hist, "_MXU_MODE", mode)
    pallas_hist.hist_pallas._clear_cache()


def _drop_grow_traces():
    """The counters move where a program is TRACED: forget the tree
    program's traces, so that the next grow traces again."""
    from h2o3_tpu.models import tree
    tree._grow_batched.clear_executables()
    tree._grow_batched._jit.clear_cache()


@pytest.mark.parametrize("mode,rtol", [("hilo", 5e-4), ("hilo3", 1e-5),
                                       ("highest", 1e-5)])
def test_kernel_matches_segment_sum(monkeypatch, mode, rtol, rng):
    _set_mode(monkeypatch, mode)
    _check(rng, 4096, 7, 16, 8, rtol, rtol * 10)


# node blocks on either side of what the MXU's 128 lanes hold side by side:
# hilo packs up to 21 slots (2 x 21 x 3 columns), hilo3 up to 14
@pytest.mark.parametrize("mode,rtol,n_nodes,packed", [
    ("hilo", 5e-4, 16, True), ("hilo", 5e-4, 21, True),
    ("hilo", 5e-4, 22, False), ("hilo", 5e-4, 64, False),
    ("hilo3", 1e-5, 8, True), ("hilo3", 1e-5, 14, True),
    ("hilo3", 1e-5, 16, False), ("highest", 1e-5, 16, False)])
def test_packed_and_separate_digits(monkeypatch, mode, rtol, n_nodes, packed,
                                    rng):
    _set_mode(monkeypatch, mode)
    assert pallas_hist._packed(min(n_nodes, pallas_hist._NODE_BLOCK)) is packed
    _check(rng, 1024, 3, 16, n_nodes, rtol, rtol * 10)


@pytest.mark.parametrize("mode", ["hilo", "hilo3"])
def test_packed_equals_separate_bit_for_bit(monkeypatch, mode, rng):
    """Side by side in the lanes or a pass a digit: the same products, the
    same float32 sums in the same order — the same array."""
    _set_mode(monkeypatch, mode)
    binned, node, g, h, w = _data(rng, 2048, 5, 64, 4, np.int8)
    assert pallas_hist._packed(4)
    packed = np.asarray(pallas_hist.hist_pallas(binned.T, node, g, h, w, 4, 65))
    monkeypatch.setattr(pallas_hist, "_packed", lambda Nb: False)
    pallas_hist.hist_pallas._clear_cache()
    assert not pallas_hist._packed(4)
    passes = np.asarray(pallas_hist.hist_pallas(binned.T, node, g, h, w, 4, 65))
    assert np.abs(packed).sum() > 0
    np.testing.assert_array_equal(packed.view(np.int32), passes.view(np.int32))


@pytest.mark.parametrize("bins,dtype,n_nodes", [
    (64, np.int8, 16),      # the GBM cell's storage and deepest level
    (256, np.int16, 16),    # the XGBoost cell's
    (256, np.int16, 128),   # several node blocks
    (2, np.int8, 1)])       # one node, the fewest bins
def test_kernel_256_bins_and_multiblock(monkeypatch, bins, dtype, n_nodes,
                                        rng):
    """int8 and int16 storage, the 256-bin (XGBoost config) layout, one
    node, and a node count spanning multiple node blocks all reduce to the
    same histograms."""
    _set_mode(monkeypatch, "hilo")
    _check(rng, 2048, 3, bins, n_nodes, 5e-4, 5e-3, dtype)


@pytest.mark.parametrize("rows", [1, 100, 1000, 4097, 9000])
def test_rows_short_of_a_tile_and_past_one(monkeypatch, rows, rng):
    """Fewer rows than one tile (the tile shrinks to them), rows that are
    not a multiple of it, and more than one tile's."""
    _set_mode(monkeypatch, "hilo")
    assert pallas_hist._plan(4, 3, 17)[2] == 4096
    _check(rng, rows, 3, 16, 4, 5e-4, 5e-3, np.int8)


@pytest.mark.parametrize("dtype,feats", [(np.int8, 70), (np.int16, 100)])
def test_feature_blocks_narrower_than_the_frame(monkeypatch, dtype, feats,
                                                rng):
    """A frame whose slab does not fit VMEM whole: blocks of 32 features (a
    sublane tile of every storage), the surplus of the last sliced off."""
    _set_mode(monkeypatch, "hilo")
    monkeypatch.setattr(pallas_hist, "_VMEM_BUDGET",
                        pallas_hist._vmem_bytes(8, 32, 128, 24, out_blocks=2))
    assert pallas_hist._plan(8, feats, 17) == (8, 32, 128) and feats % 32
    _check(rng, 700, feats, 16, 8, 5e-4, 5e-3, dtype)


def test_kernel_under_vmap(monkeypatch, rng):
    """The multinomial round: K class trees' statistics and node ids batched
    over one shared frame."""
    _set_mode(monkeypatch, "hilo")
    K, R, F, B, N = 3, 1500, 4, 16, 8
    binned = _data(rng, R, F, B, N, np.int8)[0]
    per_class = [_data(rng, R, F, B, N)[1:] for _ in range(K)]
    stacked = [jnp.stack(v) for v in zip(*per_class)]
    got = jax.vmap(lambda n, g, h, w: pallas_hist.hist_pallas(
        binned.T, n, g, h, w, N, B + 1))(*stacked)
    for k in range(K):
        want = _level_histograms(binned, *per_class[k], N, B + 1)
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want),
                                   rtol=5e-4, atol=5e-3)


@pytest.mark.parametrize("mode", ["hilo", "hilo3", "highest"])
@pytest.mark.parametrize("n_nodes,feats,n_bins_tot", [
    (1, 28, 65), (16, 28, 65), (16, 28, 257), (64, 28, 257), (64, 500, 65),
    (2048, 28, 65)])
def test_plan_stays_inside_vmem(monkeypatch, mode, n_nodes, feats,
                                n_bins_tot):
    monkeypatch.setattr(pallas_hist, "_MXU_MODE", mode)
    Nb, Fb, T = pallas_hist._plan(n_nodes, feats, n_bins_tot)
    S = -(-n_bins_tot // 8) * 8
    blocks = -(-n_nodes // Nb) * -(-feats // Fb)
    assert (pallas_hist._vmem_bytes(Nb, Fb, T, S, blocks)
            <= pallas_hist._VMEM_BUDGET < pallas_hist._VMEM_LIMIT)
    assert T % 128 == 0 and 128 <= T <= pallas_hist._TILE_MAX
    assert Nb == min(n_nodes, 64) and (Fb == feats or Fb % 32 == 0)
    assert -(-n_nodes // Nb) <= pallas_hist._MAX_NODE_BLOCKS
    # a 16-slot level at 72 rows of one-hot affords a longer tile than a
    # 64-node block at 264
    assert T <= pallas_hist._plan(1, 28, 65)[2]


def test_plan_refuses_past_the_node_block_cap():
    assert pallas_hist._plan(64 * 32, 28, 65) is not None
    assert pallas_hist._plan(64 * 32 + 1, 28, 65) is None


def test_hilo_split_exactness():
    """hi+lo bf16 digits reconstruct f32 stats to 16-bit mantissa: the
    one-hot side contributes no error, so a single-row 'histogram' must
    reproduce each stat to ~1.5e-5 relative."""
    vals = np.float32([1.0, 1e-3, 123.456, -0.9999, 3.14159e4])
    for v in vals:
        hi = np.float32(jnp.bfloat16(v))
        lo = np.float32(jnp.bfloat16(np.float32(v) - hi))
        assert abs((hi + lo) - v) <= abs(v) * 2 ** -15


# -- the last level's per-node totals: a one-feature call ----------------------

def _totals_f64(node, g, h, w, n):
    live = node >= 0
    return np.stack([np.bincount(node[live], v[live].astype(np.float64), n)
                     for v in (g, h, w)], axis=1)


@pytest.mark.parametrize("n_nodes,rows", [
    (64, 9000),        # GBM-64's and XGBoost-256's last level; 2.2 tiles
    (1024, 5000),      # the categorical cell's: most segments hold 0-8 rows
    (1024, 1),         # a frame shorter than a lane
    (4, 4097)])        # fewer segments than a sublane tile; a row past a tile
def test_node_totals_through_the_kernel(monkeypatch, n_nodes, rows, rng):
    """Frozen rows (-1) count nowhere, empty segments read 0, rows need be
    no multiple of the tile; counts are exact and sums hold the kernel's
    tolerance against float64."""
    from h2o3_tpu.models import tree
    _set_mode(monkeypatch, "hilo")
    assert pallas_hist._plan(1, 1, n_nodes)[1:] == (1, 4096)
    node, g, h, w = (np.array(v) for v in _data(rng, rows, 1, 1, n_nodes)[1:])
    node[node == n_nodes // 2] = -1            # an empty segment for certain
    tree.HIST_PATHS.clear()
    before = _counters()
    got = np.asarray(tree._node_totals(
        *(jnp.asarray(v) for v in (node, g, h, w)), n_nodes))
    assert tree.HIST_PATHS == {"pallas": 1}
    assert tuple(a - b for a, b in zip(_counters(), before)) == (
        1, 0, -(-rows // 4096))
    want = _totals_f64(node, g, h, w, n_nodes)
    assert got.shape == (n_nodes, 3) and (want[n_nodes // 2] == 0).all()
    np.testing.assert_array_equal(got[:, 2], want[:, 2])
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-3)


def test_node_totals_of_long_two_valued_segments_are_sound(monkeypatch):
    """What the chip showed at 20M rows (PERF.md, PR 30), in the small: a
    first tree's g and h take two values, and a scatter-add that adds a
    segment's rows one by one in float32 rounds every addend the same way
    (a leaf of 300,000 rows read 3e-3 off). The kernel sums a 4,096-row
    tile on the MXU and adds tiles: a leaf within 1e-4 of float64."""
    from h2o3_tpu.models import tree
    _set_mode(monkeypatch, "hilo")
    rows, n = 1_000_000, 64
    rng = np.random.default_rng(11)
    zipf = 1.0 / np.arange(1, n + 1)
    node = rng.choice(n, size=rows, p=zipf / zipf.sum()).astype(np.int32)
    y = rng.random(rows) < 0.47
    p = np.float32(0.53)
    g = np.where(y, p - 1, p).astype(np.float32)
    h = np.full(rows, p * (1 - p), np.float32)
    w = np.ones(rows, np.float32)
    got = np.asarray(tree._node_totals(
        *(jnp.asarray(v) for v in (node, g, h, w)), n), np.float64)
    want = _totals_f64(node, g, h, w, n)
    assert want[:, 2].max() > 200_000
    np.testing.assert_array_equal(got[:, 2], want[:, 2])
    np.testing.assert_allclose(got[:, 1], want[:, 1], rtol=5e-5)
    np.testing.assert_allclose(-got[:, 0] / got[:, 1],
                               -want[:, 0] / want[:, 1], rtol=0, atol=1e-4)


def test_node_totals_past_the_measured_crossover_stay_scatter_adds():
    from h2o3_tpu.models import tree
    n = 2 * tree._TOTALS_KERNEL_MAX_NODES
    assert pallas_hist.pallas_available(1, 1, tree._TOTALS_KERNEL_MAX_NODES)
    assert pallas_hist._plan(1, 1, n) is not None       # a plan, not a win
    node = jnp.asarray(np.arange(-1, 499, dtype=np.int32))
    ones = jnp.ones(500, jnp.float32)
    tree.HIST_PATHS.clear()
    got = tree._node_totals(node, ones, ones, ones, n)
    assert tree.HIST_PATHS == {"scatter": 1}
    assert float(got.sum()) == 3 * 499.0


@pytest.mark.parametrize("K", [1, 3])
def test_a_depth_6_tree_calls_the_kernel_seven_times(monkeypatch, K):
    """Six levels and the last level's totals, under the multinomial
    round's ``vmap`` too; the totals are sums over the rows the last route
    left in a node, so with unit weights a split node of level 5 has exactly
    its two children's rows."""
    from h2o3_tpu.models import tree
    from h2o3_tpu.models.tree import TreeParams, grow_trees_batched
    _set_mode(monkeypatch, "hilo")
    rng = np.random.default_rng(K)
    rows, F, nbins, depth = 6000, 4, 16, 6
    binned = jnp.asarray(rng.integers(0, nbins, size=(rows, F)).astype(np.int8))
    edges = jnp.asarray(np.tile(np.arange(1, nbins, dtype=np.float32), (F, 1)))
    g = jnp.asarray(rng.normal(size=(K, rows)).astype(np.float32))
    ones = jnp.ones((K, rows), jnp.float32)
    _drop_grow_traces()
    tree.HIST_PATHS.clear()
    trees, preds = grow_trees_batched(
        binned, edges, g, ones, ones,
        TreeParams(max_depth=depth, nbins=nbins, min_rows=1.0),
        jnp.ones(F, bool))
    assert tree.HIST_PATHS == {"pallas": depth + 1}
    assert len(trees) == K and preds.shape == (K, rows)
    for t in trees:
        cover, is_split = np.asarray(t.cover), np.asarray(t.is_split)
        parents = np.arange(2 ** (depth - 1) - 1, 2 ** depth - 1)
        assert is_split[parents].sum() > 8
        kids = cover[2 * parents + 1] + cover[2 * parents + 2]
        np.testing.assert_array_equal(kids[is_split[parents]],
                                      cover[parents][is_split[parents]])
        assert (kids[~is_split[parents]] == 0).all()
    _drop_grow_traces()


# -- the counters --------------------------------------------------------------

def _counters():
    levels = telemetry.HIST_KERNEL_LEVELS
    return (levels.labels(contraction="packed").value,
            levels.labels(contraction="passes").value,
            telemetry.HIST_GRID_STEPS.labels().value)


def test_counters_rise_where_a_build_is_traced(monkeypatch):
    """One increment a TRACED call and its grid's size: a depth-3 tree calls
    the kernel three times for its levels (1 node, then 1 and 2 slots under
    sibling subtraction) and once for the last level's totals (one feature
    of 8 bins), and traces three signatures, since jit traces a shape
    once."""
    from h2o3_tpu.models import tree
    from h2o3_tpu.models.tree import TreeParams, grow_trees_batched
    _set_mode(monkeypatch, "hilo")
    rng = np.random.default_rng(3)
    rows, F, nbins = 9000, 5, 16
    binned = jnp.asarray(rng.integers(0, nbins, size=(rows, F)).astype(np.int8))
    edges = jnp.asarray(np.tile(np.arange(1, nbins, dtype=np.float32), (F, 1)))
    g = jnp.asarray(rng.normal(size=(1, rows)).astype(np.float32))
    ones = jnp.ones((1, rows), jnp.float32)

    _drop_grow_traces()
    tree.HIST_PATHS.clear()
    before = _counters()
    grow = lambda: grow_trees_batched(binned, edges, g, ones, ones,
                                      TreeParams(max_depth=3, nbins=nbins),
                                      jnp.ones(F, bool))
    grow()
    assert tree.HIST_PATHS == {"pallas": 4}
    Nb, Fb, T = pallas_hist._plan(1, F, nbins + 1)
    assert (Fb, T) == (F, 4096) == pallas_hist._plan(2, F, nbins + 1)[1:]
    assert pallas_hist._plan(1, 1, 8) == (1, 1, T)      # the totals' call
    steps = -(-rows // T)                      # one node and feature block
    packed, passes, total = (a - b for a, b in zip(_counters(), before))
    assert (packed, passes, total) == (3, 0, 3 * steps)
    grow()                                     # a cached program adds nothing
    assert _counters() == tuple(b + d for b, d in
                                zip(before, (3, 0, 3 * steps)))
    _drop_grow_traces()


def test_counter_names_the_contraction(monkeypatch, rng):
    _set_mode(monkeypatch, "hilo")
    before = _counters()
    _check(rng, 1000, 3, 16, 128, 5e-4, 5e-3)   # two node blocks of 64
    assert tuple(a - b for a, b in zip(_counters(), before)) == (0, 1, 2)


def test_steps_per_call_reads_the_quotient():
    from benchmark.plugins import load
    metric = load("layer_metrics", "kernel.hist_steps_per_call")
    assert (metric.LAYER, metric.UNIT, metric.MOVES) == (
        "kernel", "count", "train_work_per_s_chip")

    class Reading:
        after = {"metrics": [
            ("h2o3_hist_kernel_levels_total", {"contraction": "packed"}, 5.0),
            ("h2o3_hist_kernel_levels_total", {"contraction": "passes"}, 1.0),
            ("h2o3_hist_grid_steps_total", {}, 5 * 5372.0 + 2 * 977.0),
            ("h2o3_route_levels_total", {"path": "select"}, 6.0)]}

    assert metric.read(Reading) == (5 * 5372 + 2 * 977) / 6

    class Parent:                                # no such counter: left out
        after = {"metrics": [
            ("h2o3_route_levels_total", {"path": "select"}, 6.0)]}

    assert metric.read(Parent) is None


# -- bins_used: only the one-hot rows a column's bins can match ----------------

#: the categorical airline cell's columns (Month, DayofMonth, DayOfWeek,
#: UniqueCarrier, Origin, Dest, DepTime, Distance) at 301 engine bins
CELL_USED, CELL_BT = (12, 31, 7, 22, 300, 300, 100, 100), 301


def _data_inside(rng, R, used, Bt, N, dtype):
    """Bins drawn inside what each column declares, 3% of them missing."""
    cols = [np.where(rng.random(R) < 0.03, Bt - 1, rng.integers(0, u, R))
            for u in used]
    _b, node, g, h, w = _data(rng, R, 1, 1, N)
    return jnp.asarray(np.stack(cols).astype(dtype)), node, g, h, w


def _pin_tile(monkeypatch, rows):
    """Dense and ragged calls plan different row tiles; the same tile sums
    the same rows in one MXU accumulation, so the sums agree bit for bit."""
    monkeypatch.setattr(pallas_hist, "_TILE_MAX", rows)
    jax.clear_caches()


@pytest.mark.parametrize("mode", ["hilo", "hilo3"])
@pytest.mark.parametrize("n_nodes", [1, 16, 64, 256])
@pytest.mark.parametrize("dtype,used,Bt", [
    (np.int16, CELL_USED, CELL_BT),
    (np.int8, (3, 40, 9, 101, 17), 102)])
def test_bins_used_equals_the_dense_call(monkeypatch, mode, n_nodes, dtype,
                                         used, Bt, rng):
    _set_mode(monkeypatch, mode)
    F, S = len(used), -(-Bt // 8) * 8
    data = _data_inside(rng, 1300, used, Bt, n_nodes, dtype)
    call = lambda **kw: np.asarray(
        pallas_hist.hist_pallas(*data, n_nodes, Bt, **kw))
    assert (pallas_hist._plan(n_nodes, F, Bt, used)[2]
            >= pallas_hist._plan(n_nodes, F, Bt)[2])
    dense, ragged = call(), call(bins_used=used)     # each at its own tile
    np.testing.assert_allclose(ragged, dense, rtol=5e-4, atol=5e-3)
    _pin_tile(monkeypatch, 256)
    assert pallas_hist._plan(n_nodes, F, Bt, used)[2] == 256
    dense, ragged = call(), call(bins_used=used)
    assert np.abs(dense).sum() > 0
    np.testing.assert_array_equal(ragged.view(np.int32), dense.view(np.int32))
    # what the call skipped reads exactly 0.0: past a column's 8-row groups,
    # short of the group that holds the missing bin
    by_bin = ragged.reshape(F, n_nodes, Bt, 3)
    skipped = [(f, -(-u // 8) * 8, S - 8) for f, u in enumerate(used)
               if -(-u // 8) * 8 < S - 8]
    assert skipped
    for f, lo, hi in skipped:
        assert not by_bin[f, :, lo:hi].any()
    jax.clear_caches()


def test_a_bin_outside_what_was_declared_is_dropped(monkeypatch, rng):
    """The contract's other side, pinned so that nobody leans on it: a bin
    id past a column's declared range and short of the missing bin's group
    is in no histogram (the dense call counts it)."""
    _set_mode(monkeypatch, "hilo")
    binned_T = jnp.full((2, 512), 50, jnp.int16)
    ones = jnp.ones(512, jnp.float32)
    node = jnp.zeros(512, jnp.int32)
    call = lambda **kw: np.asarray(pallas_hist.hist_pallas(
        binned_T, node, ones, ones, ones, 1, 101, **kw)).reshape(2, 101, 3)
    assert call()[:, 50, 2].tolist() == [512.0, 512.0]
    assert call(bins_used=(10, 100))[:, 50, 2].tolist() == [0.0, 512.0]


def _jaxpr(F, R, Bt, N, dtype, **kw):
    shapes = (jax.ShapeDtypeStruct((F, R), dtype),
              jax.ShapeDtypeStruct((R,), jnp.int32)) + (
                  jax.ShapeDtypeStruct((R,), jnp.float32),) * 3
    return str(jax.make_jaxpr(
        lambda *a: pallas_hist.hist_pallas(*a, N, Bt, **kw))(*shapes))


@pytest.mark.parametrize("F,Bt,dtype,used", [
    (28, 65, jnp.int8, (64,) * 28),            # the HIGGS cells: every
    (28, 257, jnp.int16, (256,) * 28),         # column holds nbins
    (3, 65, jnp.int8, (64, 60, 57))])          # 64 rows + the missing group
def test_a_tuple_that_skips_nothing_is_the_dense_call(F, Bt, dtype, used):
    """All a call knows of ``bins_used`` is each position's first range, and
    here that is the dense one: the same plan and, to the last equation, the
    same program (the guard of the cells whose columns all hold ``nbins``)."""
    S = -(-Bt // 8) * 8
    assert pallas_hist._block_first_rows(S, F, F, used) == (S,) * F
    assert pallas_hist._plan(16, F, Bt, used) == pallas_hist._plan(16, F, Bt)
    assert (_jaxpr(F, 9000, Bt, 16, dtype, bins_used=used)
            == _jaxpr(F, 9000, Bt, 16, dtype))
    assert (_jaxpr(F, 9000, Bt, 16, dtype, bins_used=(8,) + used[1:])
            != _jaxpr(F, 9000, Bt, 16, dtype))
    with pytest.raises(ValueError, match="bins_used names"):
        pallas_hist._plan(16, F, Bt, used[1:])


@pytest.mark.parametrize("n_nodes,dense_tile,tile", [
    (1, 3328, 4096), (16, 3328, 4096), (32, 1664, 4096), (64, 768, 2176),
    (256, 768, 2176)])
def test_plan_at_the_cells_columns(monkeypatch, n_nodes, dense_tile, tile):
    """944 one-hot rows a row where dense streams 2,432: the row tile grows
    with what ``_STEP_MATMULS`` then allows, inside the VMEM account."""
    monkeypatch.setattr(pallas_hist, "_MXU_MODE", "hilo")
    Nb, Fb, T = pallas_hist._plan(n_nodes, 8, CELL_BT, CELL_USED)
    assert (Fb, T) == (8, tile)
    assert pallas_hist._plan(n_nodes, 8, CELL_BT) == (Nb, 8, dense_tile)
    S = 304
    rows = pallas_hist._streamed(
        S, pallas_hist._block_first_rows(S, Fb, 8, CELL_USED))
    assert rows == [24, 40, 16, 32, 304, 304, 112, 112] and sum(rows) == 944
    # stacked to left-hand sides of at least _GROUP_ROWS rows, but the tail
    assert pallas_hist._groups(rows) == [(0, 6), (6, 8)]
    blocks = -(-n_nodes // Nb)
    assert (pallas_hist._vmem_bytes(Nb, Fb, T, S, blocks, rows)
            <= pallas_hist._VMEM_BUDGET)


def test_feature_blocks_stream_the_most_a_position_needs(monkeypatch, rng):
    """The body is one code for every feature block: position ``j`` of a
    block streams what the largest of features ``j, j + Fb, ...`` needs."""
    _set_mode(monkeypatch, "hilo")
    F, Bt = 70, 102
    used = tuple(int(u) for u in rng.integers(2, 100, F))
    first = pallas_hist._block_first_rows(104, 32, F, used)
    monkeypatch.setattr(pallas_hist, "_VMEM_BUDGET", pallas_hist._vmem_bytes(
        8, 32, 128, 104, 3, pallas_hist._streamed(104, first)))
    # three blocks of 32, 26 features padded onto the last
    assert pallas_hist._plan(8, F, Bt, used) == (8, 32, 128)
    assert first[0] == pallas_hist._first_rows(
        104, max(used[0], used[32], used[64]))
    assert first[31] == pallas_hist._first_rows(
        104, max(used[31], used[63], used[69]))
    assert pallas_hist._first_rows(104, 88) == 88       # 88 + 8 rows of 104
    assert pallas_hist._first_rows(104, 89) == 104      # nothing to skip
    data = _data_inside(rng, 600, used, Bt, 8, np.int8)
    want = _level_histograms(data[0].T, *data[1:], 8, Bt)
    got = pallas_hist.hist_pallas(*data, 8, Bt, bins_used=used)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4,
                               atol=5e-3)


def _onehot_rows():
    rows = telemetry.HIST_ONEHOT_ROWS
    return (rows.labels(kind="streamed").value,
            rows.labels(kind="dense").value)


def test_onehot_rows_are_counted_where_a_call_is_traced(monkeypatch, rng):
    """One traced call at the cell's shape: 944 of 2,432; the same call again
    is a cached trace and adds nothing; a dense call streams what it has."""
    _set_mode(monkeypatch, "hilo")
    jax.clear_caches()
    data = _data_inside(rng, 300, CELL_USED, CELL_BT, 4, np.int16)
    before = _onehot_rows()
    pallas_hist.hist_pallas(*data, 4, CELL_BT, bins_used=CELL_USED)
    assert tuple(a - b for a, b in zip(_onehot_rows(), before)) == (944, 2432)
    pallas_hist.hist_pallas(*data, 4, CELL_BT, bins_used=CELL_USED)
    assert tuple(a - b for a, b in zip(_onehot_rows(), before)) == (944, 2432)
    pallas_hist.hist_pallas(*data, 4, CELL_BT)
    assert tuple(a - b for a, b in zip(_onehot_rows(), before)) == (
        944 + 2432, 2 * 2432)


def test_onehot_rows_share_reads_the_quotient():
    from benchmark.plugins import load
    metric = load("layer_metrics", "kernel.hist_onehot_rows_share")
    assert (metric.LAYER, metric.UNIT, metric.MOVES) == (
        "kernel", "%", "train_work_per_s_chip")

    class Reading:                 # ten level calls and the totals' call
        after = {"metrics": [
            ("h2o3_hist_onehot_rows_total", {"kind": "streamed"},
             10 * 944.0 + 1024.0),
            ("h2o3_hist_onehot_rows_total", {"kind": "dense"},
             10 * 2432.0 + 1024.0),
            ("h2o3_hist_grid_steps_total", {}, 7.0)]}

    assert metric.read(Reading) == pytest.approx(41.288, abs=1e-3)

    class Parent:                                # no such counter: left out
        after = {"metrics": [("h2o3_hist_grid_steps_total", {}, 7.0)]}

    assert metric.read(Parent) is None
