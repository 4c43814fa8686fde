"""Pallas TPU kernel for level-synchronous histogram building.

The hot op of tree growth (reference: ``hex/tree/ScoreBuildHistogram2.java``
— per-bin (w, wY, wYY) accumulation, SURVEY.md §2.9's "Pallas histogram-build
kernel"). For every feature f, tree node n, bin b:

    hist[f, n, b, :] = Σ_rows [node==n]·[bin_f==b]·(g, h, w)

XLA's ``segment_sum`` lowering of this inside the fused tree program runs at
~110 ms/level on 500k×28 (scatter-add serialization); this kernel instead
rides the MXU: per row tile it builds the node×stat spread matrix
ns[Nb*3, T] once, and contracts the transposed bin one-hots of EVERY feature
of the block ([S, T] a feature, built on the VPU, a few features stacked to
one left-hand side) against it, accumulating histograms in a resident VMEM
output block.

Tiling: the grid is (node-blocks, feature-blocks, row-tiles); a step takes
one ``[Fb, T]`` block of bins — all the block's features for the row tile in
one DMA. The output block holds one (feature-block × node-block) slab and
stays VMEM-resident across the row sweep; node blocks beyond the first
re-read the inputs, so HBM traffic scales with ``ceil(N / NODE_BLOCK)`` — the
dispatch layer caps how many blocks are worth it (measured crossover vs the
scatter path; see ``_MAX_NODE_BLOCKS`` and ROOFLINE.md). ``_plan`` picks the
blocks and the row tile from the shapes alone: the body is straight-line
code, so the tile is as long as ``_STEP_MATMULS`` MXU instructions and
``_VMEM_BUDGET`` allow (4,096 rows at 28 features × 64 bins, 1,024 at 256
bins), by the one-hot rows the block really streams.

The MXU streams the one-hot's rows (one a cycle and MXU) past the latched
statistics, so a call costs R/128 · (one-hot rows a row) · passes row-cycles
whatever the node count. Three things set that product:

- The one-hot rows a row are the bins a feature CAN hold, not the engine's
  one bin count: a call told so (``bins_used``, static: a frame with a
  300-level column runs 301 bins on its 7-level and its numeric columns too)
  builds, streams and accumulates, feature by feature, only the 8-row groups
  ``[0, ceil8(used_f))`` and the group of the missing bin ``[S − 8, S)``;
  every other row of the feature's stride ``S`` keeps the 0.0 the slab was
  cleared to. Dense, ``F · S``, is the case in which every feature holds
  ``n_bins_tot``. On the v5e, 20M rows × 8 columns of 12 to 300 bins at 301
  engine bins: 944 one-hot rows a row of 2,432, 0.0266–0.0336 s a pass where
  dense takes 0.0661–0.0765 (PERF.md, PR 36); rounding the groups to the
  bf16 tile's 16 rows instead costs 7% (1,024 rows) and buys nothing, the
  stacked one-hot is cast whole.
- The bf16 digits of the statistics (``_MXU_MODE``) need not be passes:
  while ``digits · Nb·3`` columns fit the MXU's 128 lanes they sit side by
  side in ONE right-hand side ``[hi | lo]`` and the halves of the product
  are added afterwards — the same products and float32 sums as a pass a
  digit, at half (a third) of the rows streamed. Wider node blocks keep a
  pass a digit.
- FLOP cost is R·Σrows·2·3·N MACs and doubles per level — the MXU wins while
  the arithmetic stays under the scatter path's serialization, not
  asymptotically.

Layout notes (Mosaic constraints): the bin one-hot is built TRANSPOSED
([S, T], bins on sublanes) because dynamic lane indexing is unsupported;
binned is passed pre-transposed [F, R] so a step's block is ``Fb`` contiguous
row runs; the per-feature output offset uses an 8-aligned padded bin
stride S, and a feature's one-hot pieces are stacked at 8-row offsets (the
tile of the 32-bit compares they are made of) before the one cast.
"""

from __future__ import annotations

import os
from functools import cache, partial

import jax
import jax.numpy as jnp

from h2o3_tpu.utils.telemetry import (HIST_GRID_STEPS, HIST_KERNEL_LEVELS,
                                      HIST_ONEHOT_ROWS)

#: MXU precision mode for the one-hot contraction. The one-hot operand is
#: EXACTLY representable in bf16 (entries 0/1), so only the stats operand
#: needs splitting: "hilo" = 2 bf16 digits (stats to 16-bit mantissa,
#: ~1.5e-5 relative — vs the ~4e-3 of a single bf16 digit that flips
#: near-tie splits), "hilo3" = 3 digits (24-bit mantissa, f32-exact),
#: "highest" = XLA's 6-pass f32 decomposition (the round-3 default).
#: A digit is a pass of the one-hot through the MXU only where the digits do
#: not fit its lanes side by side (``_packed``).
_MXU_MODE = os.environ.get("H2O3TPU_HIST_MXU", "hilo").strip().lower()
if _MXU_MODE not in ("hilo", "hilo3", "highest"):
    raise ValueError(
        f"H2O3TPU_HIST_MXU={_MXU_MODE!r}: expected hilo, hilo3, or highest")
#: tests force interpret mode to validate kernel semantics off-TPU
_INTERPRET = False
_NODE_BLOCK = 64     # nodes per resident output slab
#: node-block count cap: levels needing more blocks fall back to the XLA
#: scatter path. Kernel time grows ~linearly with blocks (input re-reads +
#: MXU FLOPs ∝ N); the scatter path is roughly flat until XLA switches
#: lowering around N≈4096 and speeds up. Measured crossover on v5e at
#: 1M×28×64bins: 3.8× win at N=2048 (32 blocks), loss at N=4096 — so 32
#: blocks ≡ tree depth ≤ 11 stays on the kernel (ROOFLINE.md has the table).
_MAX_NODE_BLOCKS = 32
#: the scoped VMEM the kernel asks the compiler for (a v5e has 128 MiB; the
#: default scope is 16 MiB), and what ``_vmem_bytes`` may add up to under
#: it: the compiler keeps spills and matmul staging of its own beside what
#: the account names
_VMEM_LIMIT = 32 * 1024 * 1024
_VMEM_BUDGET = 24 * 1024 * 1024
_LANES = 128         # the MXU's and a vector register's width
#: longest row tile, and the MXU instructions (16 one-hot rows each) one
#: grid step may unroll to: the body is straight-line code, four bundles an
#: instruction, and a step of this many runs 10 µs against the 0.35 µs a
#: grid step costs by itself
_TILE_MAX = 4096
_STEP_MATMULS = 4096
#: one-hot rows stacked to one left-hand side, at the least: enough to hide
#: the latching of the statistics behind the streaming (one feature's 72
#: rows cost 8% more a call, its 264 rows 3%; all 28 features buy nothing
#: over these), few enough that the stacked one-hot stays small where the
#: compiler materialises it
_GROUP_ROWS = 512
#: one-hot rows are built, streamed and skipped in groups of this many: the
#: sublane tile of the float32 output slab and of the 32-bit compares the
#: one-hot is made of, so a group starts on a tile of both
_ROW_GROUP = 8


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _digits() -> int:
    """bf16 digits the statistics are split into (``highest``: none, the
    float32 operands go to the MXU as they are)."""
    return {"hilo": 2, "hilo3": 3, "highest": 1}[_MXU_MODE]


def _packed(Nb: int) -> bool:
    """Whether every digit of a node block's statistics fits the MXU's lanes
    side by side, so that one pass of the one-hot serves them all."""
    return _MXU_MODE != "highest" and _digits() * Nb * 3 <= _LANES


def _passes(Nb: int) -> int:
    """Times a step streams the one-hot through the MXU: once where the
    digits are packed, else a pass a digit (``highest``: XLA's six) for
    every 128 columns of the node block's statistics."""
    if _packed(Nb):
        return 1
    return (6 if _MXU_MODE == "highest" else _digits()) * -(-Nb * 3 // _LANES)


def _first_rows(S: int, used: int) -> int:
    """One-hot rows of a feature's FIRST range ``[0, ceil8(used))``, of the
    ``S`` its stride holds; ``S`` itself where that range and the group of the
    missing bin, ``[S - 8, S)``, leave nothing between them to skip."""
    u = _ceil_to(used, _ROW_GROUP)
    return S if u + _ROW_GROUP >= S else u


def _block_first_rows(S: int, Fb: int, n_feat: int, bins_used) -> tuple:
    """:func:`_first_rows` of each position of a feature block: all that a
    call's plan, body and counters know of ``bins_used``, so a tuple that
    skips nothing IS the call without one (``(S,) * Fb``), to the last
    instruction. The body is one code for every feature block of a call,
    so a position streams the most that any block's feature needs there;
    the features padded onto the last block read the last feature's bins."""
    if bins_used is None:
        return (S,) * Fb
    if len(bins_used) != n_feat:
        raise ValueError(f"bins_used names {len(bins_used)} features, "
                         f"the frame has {n_feat}")
    padf = -(-n_feat // Fb) * Fb - n_feat
    used = tuple(bins_used) + (bins_used[-1],) * padf
    return tuple(_first_rows(S, max(used[j::Fb])) for j in range(Fb))


def _streamed(S: int, first: tuple) -> list[int]:
    """One-hot rows a position streams: its first range and, apart from it,
    the missing bin's group."""
    return [u if u == S else u + _ROW_GROUP for u in first]


def _groups(rows: list[int]) -> list[tuple[int, int]]:
    """Consecutive features ``[f0, f1)`` whose one-hots are stacked to one
    left-hand side: a group closes at ``_GROUP_ROWS`` rows."""
    groups, f0, n = [], 0, 0
    for f, r in enumerate(rows):
        n += r
        if n >= _GROUP_ROWS:
            groups.append((f0, f + 1))
            f0, n = f + 1, 0
    if f0 < len(rows):
        groups.append((f0, len(rows)))
    return groups


def _runs(S: int, first: tuple, f0: int, f1: int) -> list[tuple[int, int, int]]:
    """Where the rows of group ``[f0, f1)``'s product go in the output slab:
    ``(row of the product, row of the slab, rows)``, one a stretch that is
    contiguous in both (the product's rows follow each other) — a feature's
    missing-bin group and the next feature's first range are one."""
    runs, a = [], 0
    for f in range(f0, f1):
        u = first[f]
        for o, n in ([(f * S, S)] if u == S
                     else [(f * S, u), (f * S + S - _ROW_GROUP, _ROW_GROUP)]):
            if runs and runs[-1][1] + runs[-1][2] == o:     # the slab's too
                runs[-1] = (*runs[-1][:2], runs[-1][2] + n)
            else:
                runs.append((a, o, n))
            a += n
    return runs


def _vmem_bytes(Nb: int, Fb: int, T: int, S: int, out_blocks: int = 1,
                rows: list[int] | None = None) -> int:
    """What a grid step holds in VMEM, by the padded shapes Mosaic gives
    them ((8, 128) tiles of 32-bit words, 16 sublanes of bf16, 32 of int8;
    bins counted at two bytes, their wider storage). The output slab is
    resident; where the call has more than one (``out_blocks``: node blocks
    x feature blocks) the pipeline holds the next one's buffer too. ``rows``:
    the one-hot rows each feature of the block streams (``S`` each without
    it); the stacked one-hot and its product are the largest group's."""
    k3 = Nb * 3
    digits = _digits()
    rows = rows or [S] * Fb
    stacked = max(sum(rows[f0:f1]) for f0, f1 in _groups(rows))
    item = 4 if _MXU_MODE == "highest" else 2       # the MXU's operands
    cols = digits * k3 if _packed(Nb) else k3       # of one product
    inputs = 2 * T * (_ceil_to(Fb, 32) * 2 + 8 * 4 + 8 * 4)  # double-buffered
    ns = _ceil_to(k3, 8) * T * 4
    rhs = digits * _ceil_to(k3, 16) * T * item
    if _packed(Nb):                                 # side by side, via f32
        rhs += _ceil_to(cols, 8) * T * 4 + _ceil_to(cols, 16) * T * item
    bins = _ceil_to(Fb, 8) * T * 4                  # upcast to int32
    onehot = stacked * T * item
    acc = stacked * _ceil_to(cols, _LANES) * 4
    out = Fb * S * _ceil_to(k3, _LANES) * 4 * min(out_blocks, 2)
    return inputs + ns + rhs + bins + onehot + acc + out


def _plan(n_nodes: int, n_feat: int, n_bins_tot: int, bins_used=None):
    """(node_block, feat_block, row_tile), or None if out of envelope. The
    feature block is the whole frame where that fits, else a multiple of 32
    (a sublane tile of every bin storage); the row tile is the longest
    multiple of 128 that ``_STEP_MATMULS`` and ``_VMEM_BUDGET`` allow, by
    the one-hot rows the block really streams (``bins_used``, as
    :func:`hist_pallas` takes it): the fewer, the longer."""
    S = _ceil_to(n_bins_tot, 8)
    Nb = min(n_nodes, _NODE_BLOCK)
    n_gb = -(-n_nodes // Nb)
    if n_gb > _MAX_NODE_BLOCKS:
        return None
    for Fb in [n_feat, *range((n_feat - 1) // 32 * 32, 0, -32)]:
        blocks = n_gb * -(-n_feat // Fb)
        rows = _streamed(S, _block_first_rows(S, Fb, n_feat, bins_used))
        per_128_rows = -(-sum(rows) // 16) * _passes(Nb)
        T = _LANES * max(1, min(_TILE_MAX // _LANES,
                                _STEP_MATMULS // per_128_rows))
        while (T > _LANES
               and _vmem_bytes(Nb, Fb, T, S, blocks, rows) > _VMEM_BUDGET):
            T -= _LANES
        if _vmem_bytes(Nb, Fb, T, S, blocks, rows) <= _VMEM_BUDGET:
            return Nb, Fb, T
    return None


def pallas_available(n_nodes: int, n_feat: int, n_bins_tot: int,
                     one_device: bool = True) -> bool:
    """Whether this level runs on the kernel: on a TPU (or anywhere in
    interpret mode), inside ``_plan``'s envelope, and only when the operand
    lives on ONE device — the kernel holds no collective, so over an array
    that spans several devices each shard's partial histogram would pass
    for the total."""
    if not one_device:
        return False
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return False
    return _plan(n_nodes, n_feat, n_bins_tot) is not None


def _hist_kernel(b_ref, n_ref, s_ref, out_ref, *, Nb, S, first):
    import jax.experimental.pallas as pl

    gb = pl.program_id(0)      # node block
    i = pl.program_id(2)       # row tile

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # ns[k, t] = (node[t] == gb*Nb + k//3) * ghw[k%3, t]; built once per
    # (node-block, row-tile). Inputs arrive ROW-MAJOR-TRANSPOSED ([3, R],
    # [1, R]): a narrow [R, 3] array in HBM pads its 3-wide minor dim to 128
    # lanes (42x memory blowup at 11M rows); [3, R] pads 3 sublanes to 8.
    k3 = Nb * 3
    nd = n_ref[0, :]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k3, 1), 0)
    ghw_rep = jnp.concatenate([s_ref[:]] * Nb, axis=0)             # [Nb*3, T]
    ns = jnp.where(nd[None, :] == gb * Nb + iota_k // 3, ghw_rep, 0.0)
    digits, packed = _digits(), _packed(Nb)
    if _MXU_MODE == "highest":
        oh_dtype, precision, rhs = jnp.float32, jax.lax.Precision.HIGHEST, [ns]
    else:
        # one-hot is bf16-exact; split only the stats operand into bf16
        # digits and accumulate the partial products in f32 — 2 (or 3)
        # digits instead of HIGHEST's 6 passes (see _MXU_MODE)
        oh_dtype, precision, rhs, r = jnp.bfloat16, None, [], ns
        for _ in range(digits):
            rhs.append(r.astype(jnp.bfloat16))
            r = r - rhs[-1].astype(jnp.float32)
        if packed:
            # [hi | lo] side by side: one pass of the one-hot for all digits
            rhs = [jnp.concatenate([m.astype(jnp.float32) for m in rhs], 0
                                   ).astype(jnp.bfloat16)]

    # i8/i16 in HBM (gbm._bin_frame packs <=125-bin configs to int8);
    # upcast per tile
    bins = b_ref[:].astype(jnp.int32)                              # [Fb, T]

    @cache          # one column of bin ids a range, whatever features share it
    def bin_ids(u):
        """The bin of each one-hot row a feature of first range ``u``
        streams: ``[0, u)``, then the missing bin's group, the stride's last."""
        if u == S:
            return jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
        r = jax.lax.broadcasted_iota(jnp.int32, (u + _ROW_GROUP, 1), 0)
        return jnp.where(r < u, r, r + (S - _ROW_GROUP - u))

    rows = _streamed(S, first)
    for f0, f1 in _groups(rows):
        oh = jnp.concatenate([bin_ids(first[f]) == bins[f:f + 1, :]
                              for f in range(f0, f1)], 0).astype(oh_dtype)
        parts = [jax.lax.dot_general(oh, m, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=precision) for m in rhs]
        if packed:                      # the digits' columns of one product
            parts = [parts[0][:, d * k3:(d + 1) * k3] for d in range(digits)]
        acc = parts[0]
        for part in parts[1:]:          # hi + lo (+ lo2), as a pass a digit
            acc = acc + part
        # each run of the product's rows to where its bins sit in the slab
        # (stride S a feature); the rows in between keep their zeros. A
        # dense group is one run, the whole product
        for a, o, n in _runs(S, first, f0, f1):
            out_ref[0, 0, o:o + n, :] += (acc if n == acc.shape[0]
                                          else acc[a:a + n])


@partial(jax.jit, static_argnames=("n_nodes", "n_bins_tot", "bins_used"))
def hist_pallas(binned_T, node, g, h, w, n_nodes: int, n_bins_tot: int,
                bins_used=None):
    """[F, n_nodes*n_bins_tot, 3] histograms (same layout as the XLA path).

    ``bins_used``: a static tuple of ``F`` ints, the bins each feature can
    hold; its one-hot rows past them are neither built nor streamed, and
    their histogram bins read exactly 0.0. THE CALLER'S PART: a bin id of
    feature ``f`` lies in ``[0, bins_used[f])`` or is the missing bin
    ``n_bins_tot - 1``; a row whose id does not is dropped from that
    feature's histogram without a word. ``None``: every feature holds
    ``n_bins_tot``; a tuple that skips nothing is the same call."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, Bt = n_nodes, n_bins_tot
    F, R = binned_T.shape
    S = _ceil_to(Bt, 8)
    Nb, Fb, T = _plan(N, F, Bt, bins_used)
    T = min(T, _ceil_to(R, _LANES))     # a frame shorter than the tile
    n_gb = -(-N // Nb)
    n_fb = -(-F // Fb)
    first = _block_first_rows(S, Fb, F, bins_used)
    padf = n_fb * Fb - F
    if padf:
        # feature padding: rows read a duplicate of the last feature; the
        # surplus output slabs are sliced off below
        binned_T = jnp.pad(binned_T, ((0, padf), (0, 0)), mode="edge")
    pad = (-R) % T
    if pad:
        # padded bin value Bt+1 never matches a one-hot row; padded node -1
        binned_T = jnp.pad(binned_T, ((0, 0), (0, pad)), constant_values=Bt + 1)
        node = jnp.pad(node, (0, pad), constant_values=-1)
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        w = jnp.pad(w, (0, pad))
    Rp = binned_T.shape[1]
    # counted where the kernel is TRACED (a cached trace adds nothing)
    HIST_KERNEL_LEVELS.labels(
        contraction="packed" if _packed(Nb) else "passes").inc()
    HIST_GRID_STEPS.inc(n_gb * n_fb * (Rp // T))
    HIST_ONEHOT_ROWS.labels(kind="streamed").inc(n_fb * sum(_streamed(S, first)))
    HIST_ONEHOT_ROWS.labels(kind="dense").inc(n_fb * Fb * S)
    act = node >= 0
    # stats-major [3, R] / [1, R]: see layout note in the kernel
    ghw_T = jnp.stack([g, h, w], 0) * act[None, :].astype(jnp.float32)
    nodec = jnp.where(act, node, -1)[None, :]
    out = pl.pallas_call(
        partial(_hist_kernel, Nb=Nb, S=S, first=first),
        interpret=_INTERPRET,
        out_shape=jax.ShapeDtypeStruct((n_gb, n_fb, Fb * S, Nb * 3),
                                       jnp.float32),
        grid=(n_gb, n_fb, Rp // T),
        in_specs=[
            pl.BlockSpec((Fb, T), lambda gb, fb, i: (fb, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T), lambda gb, fb, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, T), lambda gb, fb, i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, Fb * S, Nb * 3),
                               lambda gb, fb, i: (gb, fb, 0, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
    )(binned_T, nodec, ghw_T)
    # [n_gb, n_fb, Fb*S, Nb*3] → [F, N, S, 3] → clip padding → [F, N*Bt, 3]
    out = out.reshape(n_gb, n_fb, Fb, S, Nb, 3)
    out = out.transpose(1, 2, 0, 4, 3, 5).reshape(n_fb * Fb, n_gb * Nb, S, 3)
    out = out[:F, :N, :Bt]
    return out.reshape(F, N * Bt, 3)
