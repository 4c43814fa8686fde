"""Shared histogram-tree grower — the engine under GBM/DRF/IsolationForest.

Reference: ``hex/tree/`` — per level, ``ScoreBuildHistogram2``
(``ScoreBuildHistogram2.java:62,119-236``) accumulates per-bin (w, wY, wYY)
into ``DHistogram._vals`` (``DHistogram.java:48-94``) with a two-stage
node-local pass, histograms reduce across the cloud, and
``DTree.findBestSplitPoint`` (``DTree.java:984``) scans bins for the best
split. The XGBoost extension does the same with (grad, hess) stats and
gain = 0.5*(GL²/(HL+λ)+GR²/(HR+λ)−G²/(H+λ))−γ.

TPU-native redesign (the "hard part #1" of SURVEY.md §7): growth is
**level-synchronous with static shapes**, and — unlike the reference's
per-level driver round-trips — the ENTIRE tree grows inside one compiled XLA
program: the level loop is unrolled at trace time (depth is static), each
level being a histogram build (the Pallas MXU kernel on one chip, a
feature-scanned ``segment_sum`` elsewhere; XLA reduces per-chip partials
over ICI), a vectorized cumsum+argmax split search over
[F, nodes, bins, dir], and a re-route of rows by broadcast compare-and-select
over the level's nodes and the features (:func:`_route_rows`: no per-row
gather, which costs the TPU 7-14 ns a row). One tree = one device
dispatch; a whole K-class round = one ``vmap``-ed dispatch
(:func:`grow_trees_batched`). Trees are stored as dense heaps (arrays
indexed 2i+1/2i+2), so prediction is D gather steps.

Uses (g, h) gradient-pair stats — the XGBoost formulation — for GBM too;
with h = w this reduces exactly to H2O GBM's (w, wY) mean-leaf semantics.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from h2o3_tpu.ops.quantile import cat_bins_for_codes
from h2o3_tpu.utils.costs import accounted_jit
from h2o3_tpu.utils.telemetry import ROUTE_LEVELS, SPLIT_LEVELS


@dataclasses.dataclass
class TreeParams:
    max_depth: int = 5
    nbins: int = 64              # regular bins; bin index nbins = missing
    min_rows: float = 10.0       # min sum of instance weights per child
    reg_lambda: float = 1.0      # L2 on leaf values (XGBoost lambda; H2O GBM ~0)
    reg_alpha: float = 0.0       # L1 on leaf values (XGBoost alpha)
    gamma: float = 0.0           # min split gain (XGBoost gamma)
    min_split_improvement: float = 1e-8


@dataclasses.dataclass
class Tree:
    """Dense heap arrays, length 2^(max_depth+1)-1."""
    feat: jax.Array         # int32, split feature (or -1)
    thresh_bin: jax.Array   # int32, go left if bin < thresh_bin
    thresh_val: jax.Array   # f32, go left if x < thresh_val (raw traversal)
    na_left: jax.Array      # bool, direction for missing values
    is_split: jax.Array     # bool
    leaf: jax.Array         # f32 leaf values (valid where !is_split)
    gain: jax.Array | None = None    # f32 split gain (0 at leaves) — varimp
    cover: jax.Array | None = None   # f32 sum of row weights through the node
    # [heap, B] bool — bins routed LEFT at each node. Present only when the
    # model has categorical features (group splits, reference DHistogram enum
    # subsets); numeric-only trees route by thresh_bin/thresh_val alone.
    left_mask: jax.Array | None = None


def _level_histograms(binned, node_local, g, h, w, n_nodes: int, n_bins_tot: int):
    """All node histograms for one level: [F, n_nodes*n_bins_tot, 3] of (G,H,W).

    The MRTask analog: per-shard masked segment-sums, psum-reduced by XLA.
    """
    active = node_local >= 0
    base = jnp.where(active, node_local * n_bins_tot, 0)
    stats = [jnp.where(active, v, 0.0) for v in (g, h, w)]

    def per_feature(_, binf):
        ids = base + jnp.minimum(binf, n_bins_tot - 1)
        # per 1-D stat (a [rows, 3] stack pads minor dim to 128 lanes in HBM)
        outs = [jax.ops.segment_sum(v, ids, num_segments=n_nodes * n_bins_tot)
                for v in stats]
        return None, jnp.stack(outs, axis=1)

    _, hists = lax.scan(per_feature, None, binned.T)
    return hists


#: :func:`hist_mesh`'s answer for an operand that spans several devices but
#: cannot be fused (no named ``rows`` axis, or rows not divisible by it).
UNFUSED = "unfused"

#: which histogram path each level, and the last level's per-node totals
#: (:func:`_node_totals`), took, counted where the branch is taken
#: — at TRACE time, so a cached program adds nothing. The caller resets it
#: (``HIST_PATHS.clear()``) before the build it wants to read.
HIST_PATHS: collections.Counter = collections.Counter()


def hist_mesh(arr):
    """Where one level's histogram reduction runs, from an input array's
    sharding: the mesh to fuse it over; ``None`` when the array lives on
    one device (the Pallas kernel's case); :data:`UNFUSED` when it spans
    several devices but has no named ``rows`` axis that divides its rows.
    Called OUTSIDE jit by the dispatch wrappers; the answer then rides into
    the compiled program as a STATIC argument, so a trace can never reuse a
    stale mesh after the global mesh changes (shard_map bakes its mesh in
    at trace time)."""
    from h2o3_tpu.parallel.mesh import ROWS
    sharding = getattr(arr, "sharding", None)
    if sharding is None or len(sharding.device_set) <= 1:
        return None
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or getattr(mesh, "axis_names", None) is None:
        return UNFUSED
    if ROWS not in mesh.axis_names or mesh.shape[ROWS] <= 1:
        return UNFUSED
    if arr.shape[0] % mesh.shape[ROWS] != 0:
        return UNFUSED
    return mesh


def _level_histograms_fused(binned, node_local, g, h, w, n_nodes: int,
                            n_bins_tot: int, mesh):
    """One-collective level histograms on a multi-device mesh: shard-local
    segment-sums inside ``shard_map``, then ONE ``lax.psum`` of the whole
    stacked ``[F, n_nodes*n_bins_tot, 3]`` payload over the row axis — the
    FireCaffe shape: few, large, tree-reduced collectives. The implicit-SPMD
    path instead lowers one small all-reduce per feature-scan step, which is
    exactly the 4-tiny-collectives-per-level pattern the multi-chip dry run
    (``__graft_entry__.dryrun_multichip``) flagged."""
    from h2o3_tpu.parallel.mesh import ROWS
    rows = P(ROWS)

    def local(b, nl, gg, hh, ww):
        return lax.psum(
            _level_histograms(b, nl, gg, hh, ww, n_nodes, n_bins_tot), ROWS)

    fused = _shard_map(local, mesh=mesh,
                       in_specs=(P(ROWS, None), rows, rows, rows, rows),
                       out_specs=P())
    return fused(binned, node_local, g, h, w)


def _histograms(binned, binned_T, node_local, g, h, w, n_nodes: int,
                n_bins_tot: int, mesh=None, bins_used=None):
    """Dispatch on :func:`hist_mesh`'s answer. A mesh: one fused-collective
    shard_map reduction over it (``fused_scatter``). ``None`` — the operand
    is on one device: the Pallas MXU kernel inside its envelope
    (``pallas``), segment_sum beyond it or off-TPU (``scatter``).
    :data:`UNFUSED` — segment_sum under implicit SPMD, whose collectives
    XLA inserts: the kernel is single-device, and over a sharded global
    array it would skip the per-level ``psum`` (each shard's partial
    histogram would be treated as the total). ``bins_used`` (static: the
    bins each column can hold, ``hist_pallas``'s contract) spares the kernel
    the one-hot rows no bin id can match; the segment sums cost the same
    whatever the ids and take no notice."""
    from h2o3_tpu.ops.pallas_hist import hist_pallas, pallas_available
    if mesh is not None and mesh is not UNFUSED:
        HIST_PATHS["fused_scatter"] += 1
        return _level_histograms_fused(binned, node_local, g, h, w, n_nodes,
                                       n_bins_tot, mesh)
    if pallas_available(n_nodes, binned.shape[1], n_bins_tot,
                        one_device=mesh is None):
        HIST_PATHS["pallas"] += 1
        return hist_pallas(binned_T, node_local, g, h, w, n_nodes, n_bins_tot,
                           bins_used=bins_used)
    HIST_PATHS["scatter"] += 1
    return _level_histograms(binned, node_local, g, h, w, n_nodes, n_bins_tot)


#: the most segments :func:`_node_totals` sums through the histogram kernel;
#: past it the three scatter-adds are the cheaper ones. The kernel's call
#: streams rows x N one-hot rows through the MXU and ``_plan``'s row tile
#: shrinks past 2,048 segments (4,096 rows, then 2,048 / 896 / 128 at 4,096
#: / 8,192 / 16,384); the scatter-adds cost the same whatever N. On the v5e
#: (PERF.md section 6, PR 31; ms, the whole function timed alone, kernel /
#: scatter-adds, node ids uniform or Zipf, whichever is nearer): 22M rows
#: 7.2 / 580 at 64, 35 / 580 at 1,024, 64 / 452 at 2,048, 124 / 445 at
#: 4,096, 247 / 445 at 8,192, 669 / 444 at 16,384; 11M rows 3.8 / 290,
#: 17.5 / 290, 32 / 226, 62 / 222, 123 / 222, 338 / 222. 8,192 is the
#: largest size measured at which the kernel wins at both.
_TOTALS_KERNEL_MAX_NODES = 8192


def _node_totals(node_local, g, h, w, n_nodes: int, mesh=None):
    """Per-node (G, H, W) sums [n_nodes, 3] over the rows the last ``route``
    left in each node: what the final level's leaves and covers are made of.
    Rows of node -1 (frozen) count nowhere.

    On one TPU device, up to :data:`_TOTALS_KERNEL_MAX_NODES` nodes, they
    are ONE call of the histogram kernel with one feature, whose bin is the
    row's node id, and one node slot: ``sum_rows [node == n] * (g, h, w)`` is
    that feature's histogram. It is the cheaper one there (the constant's
    readings) and the nearer one: a 4,096-row tile is summed on the MXU in
    the kernel's two bf16 digits, where the chip's scatter-add accumulates
    a segment's rows one by one in float32 (three digits short on a
    300,000-row leaf). Only ``[1, rows]`` operands exist on that path: a
    ``[rows, 1]`` array pads its minor dimension to 128 lanes in HBM.

    Elsewhere (off the TPU, an operand that spans a mesh, more nodes than
    the kernel wins at) three ``segment_sum``s under implicit SPMD, summed
    per 1-D stat column: a [rows, 3] stack would pad its minor dim to 128
    lanes (42x memory at 11M rows). Counted in :data:`HIST_PATHS` like a
    level (``pallas`` / ``scatter``)."""
    from h2o3_tpu.ops.pallas_hist import hist_pallas, pallas_available
    active = node_local >= 0
    if (n_nodes <= _TOTALS_KERNEL_MAX_NODES
            and pallas_available(1, 1, n_nodes, one_device=mesh is None)):
        HIST_PATHS["pallas"] += 1
        # slot -1 drops a frozen row, as sibling subtraction's masked rows
        return hist_pallas(node_local[None, :], jnp.where(active, 0, -1),
                           g, h, w, 1, n_nodes)[0]
    HIST_PATHS["scatter"] += 1
    ids = jnp.where(active, node_local, 0)
    outs = [jax.ops.segment_sum(jnp.where(active, v, 0.0), ids,
                                num_segments=n_nodes) for v in (g, h, w)]
    return jnp.stack(outs, axis=1)


def _find_splits(hists, n_bins: int, min_rows, reg_lambda, reg_alpha, gamma,
                 feat_mask, mono=None, allowed=None, cat_feats=None):
    """Vectorized split search (reference: DTree.findBestSplitPoint).

    hists: [F, N*(n_bins+1), 3]. Returns per-node best (gain, feat, t,
    na_left, child values) and node totals (G, H, W). Candidate split t in
    [1, n_bins-1]: bins < t go left; the missing bin (index n_bins) is
    assigned to the better direction.

    ``mono`` [F] in {-1,0,1} rejects splits whose child leaf values violate
    the feature's monotone direction (reference ``hex/tree/Constraints.java``;
    LightGBM "basic" mode — violating candidates get -inf gain; the CALLER
    propagates [lo,hi] bounds down the heap and clamps leaf values).
    ``allowed`` [N,F] masks features an interaction-constrained branch may
    split on (reference ``BranchInteractionConstraints.java``).
    ``cat_feats`` [F] marks categorical features: their candidate splits are
    GROUP splits — bins re-ranked per node by gradient ratio G/H and scanned
    as sorted prefixes (reference ``DHistogram`` enum handling /
    ``DTree.findBestSplitPoint`` Fisher-optimal subset search) — instead of
    ordinal thresholds.
    """
    F = hists.shape[0]
    Bt = n_bins + 1
    N = hists.shape[1] // Bt
    hist4 = hists.reshape(F, N, Bt, 3)
    reg = hist4[:, :, :n_bins, :]                 # [F,N,B,3]
    na = hist4[:, :, n_bins, :]                   # [F,N,3]
    cum = jnp.cumsum(reg, axis=2)                 # [F,N,B,3]
    rank = None
    # counted where a level is TRACED (a cached program adds nothing)
    SPLIT_LEVELS.labels(
        kind="threshold" if cat_feats is None else "group").inc()
    if cat_feats is not None:
        # rank bins by mean gradient; empty bins sort to the end so prefix
        # candidates enumerate only occupied categories first
        with jax.named_scope("rank"):
            ratio = reg[..., 0] / jnp.maximum(reg[..., 1], 1e-12)
            ratio = jnp.where(reg[..., 2] > 0, ratio, jnp.inf)
            order = jnp.argsort(ratio, axis=2)                  # [F,N,B]
            reg_sorted = jnp.take_along_axis(reg, order[..., None], axis=2)
            cum_sorted = jnp.cumsum(reg_sorted, axis=2)
            rank = jnp.argsort(order, axis=2)                   # bin → rank
        cum = jnp.where(cat_feats[:, None, None, None], cum_sorted, cum)
    tot = cum[:, :, -1, :] + na                   # [F,N,3] (same for all f)
    G, H, W = tot[0, :, 0], tot[0, :, 1], tot[0, :, 2]

    GL = cum[:, :, : n_bins - 1, :]               # split t=b+1 → left = bins<=b
    # direction choice for missing values: [2, F, N, B-1, 3]
    GLd = jnp.stack([GL + na[:, :, None, :], GL], axis=0)
    gl, hl, wl = GLd[..., 0], GLd[..., 1], GLd[..., 2]
    gr = G[None, None, :, None] - gl
    hr = H[None, None, :, None] - hl
    wr = W[None, None, :, None] - wl

    def half(gs, hs):
        # XGBoost leaf objective with L1: soft-threshold G by alpha
        gt = jnp.sign(gs) * jnp.maximum(jnp.abs(gs) - reg_alpha, 0.0)
        return gt * gt / (hs + reg_lambda)

    parent = half(G, H)[None, None, :, None]
    gain = 0.5 * (half(gl, hl) + half(gr, hr) - parent) - gamma
    ok = (wl >= min_rows) & (wr >= min_rows) & feat_mask[None, :, None, None]
    if allowed is not None:
        ok = ok & allowed.T[None, :, :, None]
    vl = _leaf_value(gl, hl, wl, reg_lambda, reg_alpha)
    vr = _leaf_value(gr, hr, wr, reg_lambda, reg_alpha)
    if mono is not None:
        m = mono[None, :, None, None]
        viol = ((m > 0) & (vl > vr)) | ((m < 0) & (vl < vr))
        ok = ok & ~viol
    gain = jnp.where(ok, gain, -jnp.inf)

    flat = gain.transpose(2, 0, 1, 3).reshape(N, -1)   # [N, 2*F*(B-1)]
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    na_left = best < F * (n_bins - 1)
    rem = best % (F * (n_bins - 1))
    best_feat = (rem // (n_bins - 1)).astype(jnp.int32)
    best_t = (rem % (n_bins - 1) + 1).astype(jnp.int32)
    nn = jnp.arange(N)
    dirs = jnp.where(na_left, 0, 1)
    vl_b = vl[dirs, best_feat, nn, best_t - 1]
    vr_b = vr[dirs, best_feat, nn, best_t - 1]
    wl_b = wl[dirs, best_feat, nn, best_t - 1]
    wr_b = wr[dirs, best_feat, nn, best_t - 1]
    # left-membership mask over bins for the chosen split: numeric = bins
    # below the threshold; categorical = bins whose per-node rank is in the
    # sorted prefix (the group going left)
    member = jnp.arange(n_bins)[None, :] < best_t[:, None]       # [N,B]
    if cat_feats is not None:
        rank_best = rank[best_feat, nn, :]                       # [N,B]
        member = jnp.where(cat_feats[best_feat][:, None],
                           rank_best < best_t[:, None], member)
    return (best_gain, best_feat, best_t, na_left, G, H, W, vl_b, vr_b,
            wl_b, wr_b, member)


#: the largest table :func:`_lookup` reads by compare-and-select; a larger
#: one it gathers from. Select costs entries x rows, the gather rows. On the
#: v5e (PERF.md section 6, PR 27; ms a level's whole route, select / gather):
#: 22M rows of int8 bins 38 / 267 at 1,024 entries, 111 / 308 at 2,048,
#: 403 / 308 at 4,096; 11M rows of int16 bins 25 / 134, 46 / 155, 85 / 155,
#: and 202 / 155 at 8,192. 2,048 is the largest size measured at which the
#: select wins at both.
_SELECT_MAX_ENTRIES = 2048


def _select(values, idx):
    """``values[idx[r], r]`` for every row r, ``values`` [n, rows] (or
    [n, 1], broadcast) int32, as a broadcast compare-and-select reduced over
    the small axis, rows on the minor axis: XLA fuses compare, select and
    reduce into one loop over the rows (the [n, rows] predicate is never
    stored) and the program holds no ``gather``. Exactly one term of a
    row's sum is not 0 (none where ``idx`` is -1, which reads 0), so bits
    pass through unchanged."""
    n = values.shape[0]
    hit = idx[None, :] == jnp.arange(n, dtype=idx.dtype)[:, None]
    return jnp.where(hit, values, 0).sum(0, dtype=values.dtype)


def _lookup(table, idx):
    """``table[idx]`` for every row: ``table`` [n] int32, ``idx`` [rows] in
    [-1, n); a row whose ``idx`` is -1 reads 0. A :func:`_select` up to
    :data:`_SELECT_MAX_ENTRIES` entries; past the crossover a gather, which
    costs this chip 7-14 ns a row from any table of more than 64 entries,
    is the cheaper one."""
    if table.shape[0] <= _SELECT_MAX_ENTRIES:
        return _select(table[:, None], idx)
    return jnp.where(idx >= 0, table[jnp.maximum(idx, 0)], 0)


def _lookup_f32(table, idx):
    """:func:`_lookup` of a float32 table, selected as bits (so ``-0.0``
    and every other value come back exact)."""
    bits = _lookup(lax.bitcast_convert_type(table, jnp.int32), idx)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _row_splits(node_local, feat, t, na_left, do_split, n_features: int,
                n_bins: int):
    """Each row's split, read from its node's: ``(split, na_left, t,
    feat)`` per row, ``split`` False for a frozen row (node -1) and for a
    row of a node that does not split. A node's four values pack into one
    int32 — bit 0 ``do_split``, bit 1 ``na_left``, then ``t`` in
    ``n_bins.bit_length()`` bits and the feature above it — so ONE
    :func:`_lookup` by node gives a row all four; where the frame is too
    wide for the bits left, the feature rides in a word of its own."""
    t_bits = n_bins.bit_length()
    one_word = max(n_features - 1, 0).bit_length() + t_bits + 2 <= 31
    f = jnp.maximum(feat, 0).astype(jnp.int32)
    words = (do_split.astype(jnp.int32) | (na_left.astype(jnp.int32) << 1)
             | (t.astype(jnp.int32) << 2))
    if one_word:
        words = words | (f << (t_bits + 2))
    word = _lookup(jnp.where(do_split, words, 0), node_local)
    f_row = word >> (t_bits + 2) if one_word else _lookup(f, node_local)
    return ((word & 1) == 1, (word & 2) == 2,
            (word >> 2) & ((1 << t_bits) - 1), f_row)


def _pack_member(member):
    """``member`` [N, B] bool -> [N * W] int32, W = ceil(B / 32) words a
    node, bit ``b % 32`` of word ``b // 32`` set where bin b goes left."""
    N, B = member.shape
    W = -(-B // 32)
    m = jnp.pad(member, ((0, 0), (0, W * 32 - B))).reshape(N, W, 32)
    bit = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    words = jnp.sum(jnp.where(m, bit, jnp.uint32(0)), axis=2, dtype=jnp.uint32)
    return lax.bitcast_convert_type(words, jnp.int32).reshape(N * W)


def _route_rows(binned_T, node_local, row_leaf, feat, t, na_left, do_split,
                leaf, member, n_bins: int):
    """One level's routing: rows of a node that split advance to a child
    (``2 * node + (0 left | 1 right)``), rows of a node that froze take its
    ``leaf`` value into ``row_leaf`` and get node -1, as frozen rows keep.
    Returns ``(next node_local, row_leaf)``.

    Every per-row read is a :func:`_lookup` by the row's node (its split:
    :func:`_row_splits`; ``leaf`` [N] float32 as bits) or a :func:`_select`
    over the feature axis of ``binned_T`` [F, rows], the layout the
    histogram kernel already reads (one streaming read of it, F x rows
    compares: a few percent of the level's histogram) — no gather up to
    :data:`_SELECT_MAX_ENTRIES` table entries, no ``take_along_axis`` at
    all. ``member`` is ``None`` for a numeric-only model, where a bin goes
    left iff ``bin < t`` (``_find_splits``' own definition of ``member``);
    with categorical features it is the [N, B] left-membership of each bin
    at each node, packed to bit masks and tested by bit."""
    N, F = feat.shape[0], binned_T.shape[0]
    width = 1 if member is None else -(-n_bins // 32)
    if N * width <= _SELECT_MAX_ENTRIES:     # the largest table of the level
        ROUTE_LEVELS.labels(path="select").inc()
    else:
        ROUTE_LEVELS.labels(path="gather").inc()

    split, row_na_left, row_t, f_row = _row_splits(
        node_local, feat, t, na_left, do_split, F, n_bins)
    # rows whose node froze at this level take its leaf value
    row_leaf = jnp.where((node_local >= 0) & ~split,
                         _lookup_f32(leaf, node_local), row_leaf)
    b = _select(binned_T.astype(jnp.int32), f_row)    # the row's bin of ITS feature
    if member is None:
        in_left = b < row_t
    else:
        bc = jnp.minimum(b, n_bins - 1)
        slot = jnp.where(node_local >= 0, node_local * width + (bc >> 5), -1)
        in_left = ((_lookup(_pack_member(member), slot) >> (bc & 31)) & 1) == 1
    left = jnp.where(b >= n_bins, row_na_left, in_left)
    child = node_local * 2 + jnp.where(left, 0, 1)
    return jnp.where(split, child, -1), row_leaf


def _leaf_value(G, H, W, reg_lambda, reg_alpha):
    Gt = jnp.sign(G) * jnp.maximum(jnp.abs(G) - reg_alpha, 0.0)
    return jnp.where(W > 0, -Gt / jnp.maximum(H + reg_lambda, 1e-30), 0.0)


def _grow_tree_device(binned, binned_T, edges, g, h, w, feat_mask, key,
                      depth: int, n_bins: int, min_rows, reg_lambda, reg_alpha,
                      gamma, min_split_improvement, col_rate: float,
                      do_col_sample: bool | None = None,
                      mono=None, reach=None, cat_feats=None, mesh=None,
                      bins_used=None):
    """Grow one whole tree on device; the level loop unrolls at trace time.

    Returns heap arrays + per-row training predictions (leaf of each row).

    ``mono`` [F]: monotone directions per feature; child leaf bounds
    propagate down the heap and leaves clamp into them.
    ``reach`` [F, F]: interaction reachability — ``reach[f]`` is the set of
    features allowed below a split on ``f`` (union of the constraint sets
    containing ``f``; unlisted features are singletons, XGBoost semantics).
    """
    B = n_bins
    Bt = B + 1
    F = binned.shape[1]
    node_local = jnp.zeros(binned.shape[0], jnp.int32)

    lv_feat, lv_t, lv_tv, lv_na, lv_sp, lv_leaf = [], [], [], [], [], []
    lv_gain, lv_cover, lv_mask = [], [], []
    row_leaf = jnp.zeros(binned.shape[0], jnp.float32)
    bounds = jnp.array([[-jnp.inf, jnp.inf]], jnp.float32) if mono is not None else None
    allowed = jnp.ones((1, F), bool) if reach is not None else None

    def clamp(v, bnd):
        return jnp.clip(v, bnd[:, 0], bnd[:, 1]) if bnd is not None else v

    if do_col_sample is None:     # static callers pass a concrete col_rate
        do_col_sample = col_rate < 1.0
    # sibling-subtraction state (reference ScoreBuildHistogram2 /
    # gpu_hist "hist subtraction trick"): at level d >= 1 only the SMALLER
    # child of each split parent is histogrammed — the sibling is the
    # parent's histogram minus the computed child's — halving the one-hot
    # contraction's node dimension (its FLOPs are ∝ N) at every level
    prev_hists = prev_do = chosen_left = None
    # each level, and its three parts, under a jax.named_scope: trace-time
    # metadata only, so a profile reads ``.../level3/route/...`` by name
    for d in range(depth):
        N = 2 ** d
        with jax.named_scope(f"level{d}"):
            with jax.named_scope("hist"):
                if d == 0:
                    hists = _histograms(binned, binned_T, node_local, g, h,
                                        w, N, Bt, mesh=mesh,
                                        bins_used=bins_used)
                else:
                    P = N // 2
                    # chosen child id per parent; rows elsewhere mask to -1
                    chosen = (jnp.arange(P) * 2
                              + jnp.where(chosen_left, 0, 1).astype(jnp.int32))
                    act = node_local >= 0
                    par = jnp.where(act, node_local // 2, 0)
                    at_chosen = act & (node_local == chosen[par])
                    node_slot = jnp.where(at_chosen, par, -1)
                    part = _histograms(binned, binned_T, node_slot, g, h, w,
                                       P, Bt, mesh=mesh, bins_used=bins_used)
                    part4 = part.reshape(F, P, Bt, 3)
                    prev4 = prev_hists.reshape(F, P, Bt, 3)
                    # sibling by subtraction — only where the parent really
                    # split (a frozen parent's children hold no rows; its
                    # stale parent histogram must not leak into phantom
                    # nodes)
                    other4 = jnp.where(prev_do[None, :, None, None],
                                       prev4 - part4, 0.0)
                    cl = chosen_left[None, :, None, None]
                    left4 = jnp.where(cl, part4, other4)
                    right4 = jnp.where(cl, other4, part4)
                    hists = jnp.stack([left4, right4],
                                      axis=2).reshape(F, N * Bt, 3)
            with jax.named_scope("split"):
                lmask = feat_mask
                if do_col_sample:
                    key, kd, kf = jax.random.split(key, 3)
                    sub = jax.random.uniform(kd, (F,)) < col_rate
                    sub = sub.at[jax.random.randint(kf, (), 0, F)].set(True)
                    lmask = feat_mask & sub
                    # the forced index may miss feat_mask; never let the
                    # level go empty
                    lmask = jnp.where(lmask.any(), lmask, feat_mask)
                (gain, feat, t, na_left, G, H, W, vl_b, vr_b, wl_b, wr_b,
                 member) = _find_splits(
                    hists, B, min_rows, reg_lambda, reg_alpha, gamma, lmask,
                    mono=mono, allowed=allowed, cat_feats=cat_feats)
                prev_hists = hists
                chosen_left = wl_b <= wr_b
                do = ((gain > min_split_improvement) & jnp.isfinite(gain)
                      & (W > 0))
                prev_do = do
                leaf = jnp.where(
                    do, 0.0,
                    clamp(_leaf_value(G, H, W, reg_lambda, reg_alpha),
                          bounds))
                lv_feat.append(jnp.where(do, feat, -1))
                lv_t.append(jnp.where(do, t, 0))
                lv_tv.append(jnp.where(
                    do, edges[feat, jnp.maximum(t - 1, 0)], 0.0))
                lv_na.append(do & na_left)
                lv_sp.append(do)
                lv_leaf.append(leaf)
                lv_gain.append(jnp.where(do, gain, 0.0))
                lv_cover.append(W)
                if cat_feats is not None:
                    lv_mask.append(member & do[:, None])
                if bounds is not None:
                    # monotone bound propagation: split midpoint bounds the
                    # children
                    lo, hi = bounds[:, 0], bounds[:, 1]
                    mid = jnp.clip(0.5 * (vl_b + vr_b), lo, hi)
                    c = mono[feat] * do   # 0 where unconstrained or no split
                    l_lo = jnp.where(c < 0, mid, lo)
                    l_hi = jnp.where(c > 0, mid, hi)
                    r_lo = jnp.where(c > 0, mid, lo)
                    r_hi = jnp.where(c < 0, mid, hi)
                    bounds = jnp.stack(
                        [jnp.stack([l_lo, l_hi], 1),
                         jnp.stack([r_lo, r_hi], 1)],
                        axis=1).reshape(2 * N, 2)
                if allowed is not None:
                    child_allowed = jnp.where(do[:, None],
                                              allowed & reach[feat], allowed)
                    allowed = jnp.repeat(child_allowed, 2, axis=0)
            with jax.named_scope("route"):
                node_local, row_leaf = _route_rows(
                    binned_T, node_local, row_leaf, feat, t, na_left, do,
                    leaf, member if cat_feats is not None else None, B)

    # final level: all surviving nodes become leaves; only per-node totals
    # are needed (no split search): a one-feature call of the histogram
    # kernel over the node ids in place of a level's F-feature one
    N = 2 ** depth
    with jax.named_scope("leaves"):
        tot = _node_totals(node_local, g, h, w, N, mesh=mesh)
        leaf = clamp(_leaf_value(tot[:, 0], tot[:, 1], tot[:, 2], reg_lambda,
                                 reg_alpha), bounds)
        lv_feat.append(jnp.full(N, -1, jnp.int32))
        lv_t.append(jnp.zeros(N, jnp.int32))
        lv_tv.append(jnp.zeros(N, jnp.float32))
        lv_na.append(jnp.zeros(N, bool))
        lv_sp.append(jnp.zeros(N, bool))
        lv_leaf.append(leaf)
        lv_gain.append(jnp.zeros(N, jnp.float32))
        lv_cover.append(tot[:, 2])
        if cat_feats is not None:
            lv_mask.append(jnp.zeros((N, B), bool))
        row_leaf = jnp.where(node_local >= 0, _lookup_f32(leaf, node_local),
                             row_leaf)

    out = (jnp.concatenate(lv_feat), jnp.concatenate(lv_t),
           jnp.concatenate(lv_tv), jnp.concatenate(lv_na),
           jnp.concatenate(lv_sp), jnp.concatenate(lv_leaf),
           jnp.concatenate(lv_gain), jnp.concatenate(lv_cover))
    if cat_feats is not None:
        out = out + (jnp.concatenate(lv_mask, axis=0),)
    return out + (row_leaf,)


# the boosting round's host-dispatched program — registered with the
# compute observatory (utils/costs.py) so each (shape, K, depth, mesh)
# signature's compile time and cost_analysis FLOPs are attributable
@accounted_jit("gbm:grow_batched", loop="gbm_chunk",
               static_argnames=("depth", "n_bins", "col_rate", "min_rows",
                                "reg_lambda", "reg_alpha", "gamma",
                                "min_split_improvement", "mesh",
                                "bins_used"))
def _grow_batched(binned, edges, g, h, w, feat_mask, keys,
                  depth: int, n_bins: int, min_rows, reg_lambda, reg_alpha,
                  gamma, min_split_improvement, col_rate: float,
                  mono=None, reach=None, cat_feats=None, mesh=None,
                  bins_used=None):
    """K trees in ONE dispatch: vmap over the stats axis (class trees of a
    multinomial round, or K=1). binned/edges are shared (in_axes=None)."""
    binned_T = binned.T   # once per round; the Pallas kernel wants [F, rows]
    fn = lambda gk, hk, wk, mk, kk: _grow_tree_device(
        binned, binned_T, edges, gk, hk, wk, mk, kk, depth, n_bins, min_rows,
        reg_lambda, reg_alpha, gamma, min_split_improvement, col_rate,
        mono=mono, reach=reach, cat_feats=cat_feats, mesh=mesh,
        bins_used=bins_used)
    return jax.vmap(fn)(g, h, w, feat_mask, keys)


def grow_trees_batched(binned, edges, g, h, w, params: TreeParams, feat_mask,
                       col_rate: float = 1.0, key: jax.Array | None = None,
                       mono=None, reach=None, cat_feats=None, bins_used=None
                       ) -> tuple[list[Tree], jax.Array]:
    """Grow K trees (leading axis of g/h/w) in one compiled program.

    Returns (trees, preds[K, rows]) where preds are each tree's training-row
    leaf values (what the boosting driver adds to F).

    ``col_rate`` < 1 resamples the feature mask every level — the TPU stand-in
    for the reference's per-split mtries/col_sample_rate (per-node sampling
    would break the single-batched-argmax split search; per-level is the
    standard compromise, cf. LightGBM feature_fraction granularity)."""
    K = g.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, K)
    if feat_mask.ndim == 1:
        feat_mask = jnp.broadcast_to(feat_mask[None, :], (K, feat_mask.shape[0]))
    # hyperparams are STATIC (compiled constants): a traced jnp scalar would
    # cost one host→device upload each per call
    out = _grow_batched(
        binned, edges, g, h, w, feat_mask, keys,
        params.max_depth, params.nbins, float(params.min_rows),
        float(params.reg_lambda), float(params.reg_alpha),
        float(params.gamma), float(params.min_split_improvement),
        float(col_rate), mono=mono, reach=reach, cat_feats=cat_feats,
        mesh=hist_mesh(binned), bins_used=bins_used)
    hf, ht, htv, hna, hsp, hlf, hg, hc = out[:8]
    hm = out[8] if cat_feats is not None else None
    preds = out[-1]
    trees = [Tree(feat=hf[k], thresh_bin=ht[k], thresh_val=htv[k],
                  na_left=hna[k], is_split=hsp[k], leaf=hlf[k],
                  gain=hg[k], cover=hc[k],
                  left_mask=None if hm is None else hm[k])
             for k in range(K)]
    return trees, preds


def grow_tree(binned: jax.Array, edges: jax.Array, g: jax.Array, h: jax.Array,
              w: jax.Array, params: TreeParams, feat_mask: jax.Array,
              col_rate: float = 1.0, key: jax.Array | None = None) -> Tree:
    """Grow one tree (K=1 batched growth); see :func:`grow_trees_batched`."""
    trees, _ = grow_trees_batched(binned, edges, g[None], h[None], w[None],
                                  params, feat_mask, col_rate, key)
    return trees[0]


def _walk(feat, na_left, is_split, vals, is_missing, goes_left) -> jax.Array:
    """Each row's final heap index in ONE tree: the level loop every
    traversal shares (the numpy loop ``genmodel/codegen.py`` emits is its
    model). ``vals`` holds the rows' values ``[rows, F]`` — bins or raw
    values; a tuple of such arrays is fetched member by member — and the walk
    fetches the split feature's. ``is_missing(v)`` marks the rows that take
    the node's NA direction; ``goes_left(idx, f, v)`` is the split test for
    the rest. What a row's value is compared with lives in those two."""
    rows = jax.tree.leaves(vals)[0].shape[0]
    depth = int(np.log2(feat.shape[0] + 1)) - 1
    idx = jnp.zeros(rows, jnp.int32)
    for _ in range(depth):
        f = jnp.maximum(feat[idx], 0)
        v = jax.tree.map(
            lambda a: jnp.take_along_axis(a, f[:, None], axis=1)[:, 0], vals)
        left = jnp.where(is_missing(v), na_left[idx], goes_left(idx, f, v))
        nxt = idx * 2 + jnp.where(left, 1, 2)
        idx = jnp.where(is_split[idx], nxt, idx)
    return idx


def _walk_binned(binned, feat, na_left, is_split, n_bins: int,
                 thresh_bin=None, left_mask=None) -> jax.Array:
    """:func:`_walk` over bin indices: the NA bin (``b >= n_bins``) is
    missing; a row goes left below its node's ``thresh_bin`` or, with group
    splits, where the node's ``left_mask`` holds its bin."""
    if left_mask is None:
        def goes_left(idx, f, b):
            return b < thresh_bin[idx]
    else:
        def goes_left(idx, f, b):
            return left_mask[idx, jnp.minimum(b, n_bins - 1)]
    return _walk(feat, na_left, is_split, binned, lambda b: b >= n_bins,
                 goes_left)


def _add_leaves(walk_one, stacked, acc0, lr=None) -> jax.Array:
    """``acc0`` plus every stacked tree's leaf values, in tree order:
    ``((acc0 + l1) + l2) + ...``, or with ``lr`` the boosting scan's own
    ``((acc0 + lr*l1) + lr*l2) + ...``. ``stacked`` ends in the leaf values;
    ``walk_one`` takes one tree's other arrays to its rows' heap indices."""
    def one_tree(acc, tr):
        *tree, leaf = tr
        step = leaf[walk_one(*tree)]
        return acc + (step if lr is None else lr * step), None

    acc, _ = lax.scan(one_tree, acc0, stacked)
    return acc


def _stack(trees: list[Tree], *attrs: str) -> tuple:
    return tuple(jnp.stack([getattr(t, a) for t in trees]) for a in attrs)


def _stacked(trees: list[Tree]):
    """The binned programs' operands over ``trees``: feat, na_left, is_split
    and leaf stacked, then the split test as :func:`_walk_binned`'s keyword:
    the left-membership masks where the trees carry them (group splits),
    else the bin thresholds."""
    test = "thresh_bin" if trees[0].left_mask is None else "left_mask"
    return (*_stack(trees, "feat", "na_left", "is_split", "leaf"),
            {test: _stack(trees, test)[0]})


@partial(jax.jit, static_argnames=("n_bins",))
def _binned_margins(binned, feat_s, na_s, sp_s, leaf_s, split_s, n_bins: int,
                    lr=None, F0=None):
    """Stacked trees over binned features. One compiled program per
    (thresholds or masks, sum or fold): ``split_s``'s key and whether
    ``lr`` / ``F0`` are given are part of what is traced."""
    acc0 = (jnp.zeros(binned.shape[0], jnp.float32) if F0 is None
            else F0.astype(jnp.float32))

    def walk_one(feat, na_l, is_sp, split):
        return _walk_binned(binned, feat, na_l, is_sp, n_bins, **split)

    return _add_leaves(walk_one, (feat_s, na_s, sp_s, split_s, leaf_s),
                       acc0, lr)


def predict_binned(binned, trees: list[Tree], n_bins: int) -> jax.Array:
    """Sum of leaf values over stacked trees, traversing binned features."""
    return _binned_margins(binned, *_stacked(trees), n_bins=n_bins)


def fold_binned(binned, trees: "list[Tree]", n_bins: int, lr, F0) -> jax.Array:
    """Margins folded tree-by-tree: ``F = (((F0 + lr*l1) + lr*l2) + ...)``.

    The boosting scan accumulates margins in exactly this float-addition
    order, so a checkpoint resume seeding from here reproduces the
    uninterrupted run's margins — and therefore its remaining trees —
    BIT-IDENTICALLY. ``predict_binned`` (sum-then-scale) differs by ulps,
    which is fine for scoring but breaks exact-resume guarantees
    (docs/RELIABILITY.md)."""
    if not trees:
        # a zero-tree checkpoint (deadline tripped before the first chunk)
        # legally resumes from the bare f0 margins
        return jnp.asarray(F0, jnp.float32)
    return _binned_margins(binned, *_stacked(trees), n_bins=n_bins,
                           lr=jnp.float32(lr), F0=F0)


@jax.jit
def _predict_raw_impl(X, feat_s, tv_s, na_s, sp_s, leaf_s):
    """Raw-value traversal for scoring new frames (threshold = edge value)."""
    def walk_one(feat, tv, na_l, is_sp):
        return _walk(feat, na_l, is_sp, X, jnp.isnan,
                     lambda idx, f, x: x < tv[idx])

    return _add_leaves(walk_one, (feat_s, tv_s, na_s, sp_s, leaf_s),
                       jnp.zeros(X.shape[0], jnp.float32))


@partial(jax.jit, static_argnames=("n_bins",))
def _predict_raw_masked(X, cat_card, feat_s, tv_s, mask_s, na_s, sp_s, leaf_s,
                        n_bins: int):
    """Raw traversal with group splits: categorical features map raw codes
    to their histogram bin (range-grouped when cardinality > ``n_bins``, the
    model's ``cat_bins``) and test membership in the node's mask, whose
    width is the engine's bin count; numeric features compare against the
    edge threshold."""
    cat_bin = cat_bins_for_codes(X, cat_card, n_bins)   # [rows, F] int32

    def walk_one(feat, tv, mask, na_l, is_sp):
        def goes_left(idx, f, v):
            x, b = v
            return jnp.where(cat_card[f] > 0,
                             mask[idx, jnp.minimum(b, mask.shape[-1] - 1)],
                             x < tv[idx])

        return _walk(feat, na_l, is_sp, (X, cat_bin),
                     lambda v: jnp.isnan(v[0]), goes_left)

    return _add_leaves(walk_one, (feat_s, tv_s, mask_s, na_s, sp_s, leaf_s),
                       jnp.zeros(X.shape[0], jnp.float32))


def predict_raw(X, trees: list[Tree], cat_card=None, n_bins: int = 0) -> jax.Array:
    if trees[0].left_mask is not None:
        return _predict_raw_masked(
            X, cat_card, *_stack(trees, "feat", "thresh_val", "left_mask",
                                 "na_left", "is_split", "leaf"), n_bins)
    return _predict_raw_impl(X, *_stack(trees, "feat", "thresh_val", "na_left",
                                        "is_split", "leaf"))
