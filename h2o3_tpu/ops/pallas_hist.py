"""Pallas TPU kernel for level-synchronous histogram building.

The hot op of tree growth (reference: ``hex/tree/ScoreBuildHistogram2.java``
— per-bin (w, wY, wYY) accumulation, SURVEY.md §2.9's "Pallas histogram-build
kernel"). For every feature f, tree node n, bin b:

    hist[f, n, b, :] = Σ_rows [node==n]·[bin_f==b]·(g, h, w)

XLA's ``segment_sum`` lowering of this inside the fused tree program runs at
~110 ms/level on 500k×28 (scatter-add serialization); this kernel instead
rides the MXU: per (row-tile, feature) grid step it builds the transposed
bin one-hot [S, T] on the VPU and contracts it against a per-tile
node×stat spread matrix ns[T, Nb*3] (computed once per tile into VMEM
scratch), accumulating histograms in a resident VMEM output block.

Tiling (round-3 lift of the depth-6/narrow-F cliff): the grid is
(node-blocks, feature-blocks, row-tiles, features-in-block). The output
block holds one (feature-block × node-block) slab and stays VMEM-resident
across the row sweep; node blocks beyond the first re-read the inputs, so
HBM traffic scales with ``ceil(N / NODE_BLOCK)`` — the dispatch layer caps
how many blocks are worth it (measured crossover vs the scatter path; see
``_MAX_NODE_BLOCKS`` and ROOFLINE.md). FLOP cost is R·F·2·S·3·N MACs and
doubles per level — the MXU wins while the arithmetic stays under the
scatter path's serialization, not asymptotically.

Layout notes (Mosaic constraints): the bin one-hot is built TRANSPOSED
([S, T], bins on sublanes) because dynamic lane indexing is unsupported;
binned is passed pre-transposed [F, 1, R] so each grid step DMAs a
contiguous [1, 1, T] row block; the per-feature output offset uses an
8-aligned padded bin stride S.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_TILE = 1024
#: MXU precision mode for the one-hot contraction. The one-hot operand is
#: EXACTLY representable in bf16 (entries 0/1), so only the stats operand
#: needs splitting: "hilo" = 2 bf16 passes (stats to 16-bit mantissa,
#: ~1.5e-5 relative — vs the ~4e-3 of a single bf16 pass that flips
#: near-tie splits), "hilo3" = 3 passes (24-bit mantissa, f32-exact),
#: "highest" = XLA's 6-pass f32 decomposition (the round-3 default).
#: 2 passes ≈ 3x the MXU throughput of HIGHEST for identical tree quality
#: at the tolerance the split scan already works in (f32 cumsums).
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
#: validated up to 10.7MB resident (257 bins × 64 nodes × 28 features in one
#: slab) on v5e's 16MB VMEM — keep 256-bin × F≈28 configs single-block
_VMEM_BUDGET = 11 * 1024 * 1024


def _plan(n_nodes: int, n_feat: int, n_bins_tot: int):
    """(node_block, feat_block) tile sizes, or None if out of envelope."""
    S = ((n_bins_tot + 7) // 8) * 8
    Nb = min(n_nodes, _NODE_BLOCK)
    if (n_nodes + Nb - 1) // Nb > _MAX_NODE_BLOCKS:
        return None
    # resident out slab Fb*S*Nb*3*4 within budget after fixed costs
    fixed = (_TILE * Nb * 3 * 4          # ns scratch
             + S * _TILE * 4             # bin one-hot
             + 3 * _TILE * 128 * 4 * 2)  # padded input double-buffers
    per_feat = S * Nb * 3 * 4
    Fb = max(1, min(n_feat, (_VMEM_BUDGET - fixed) // per_feat))
    if Fb < 1 or fixed + per_feat > _VMEM_BUDGET:
        return None
    return Nb, Fb


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


def _hist_kernel(b_ref, n_ref, s_ref, out_ref, ns_ref, *, Nb, S, T, Fb):
    import jax.experimental.pallas as pl

    gb = pl.program_id(0)      # node block
    i = pl.program_id(2)       # row tile
    fi = pl.program_id(3)      # feature within block

    @pl.when(jnp.logical_and(i == 0, fi == 0))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # ns[k, t] = (node[t] == gb*Nb + k//3) * ghw[k%3, t]; built once per
    # (node-block, row-tile). Inputs arrive ROW-MAJOR-TRANSPOSED ([3, R],
    # [1, R]): a narrow [R, 3] array in HBM pads its 3-wide minor dim to 128
    # lanes (42x memory blowup at 11M rows); [3, R] pads 3 sublanes to 8.
    @pl.when(fi == 0)
    def _():
        nd = n_ref[0, :]
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (Nb * 3, 1), 0)
        ghw_rep = jnp.concatenate([s_ref[:]] * Nb, axis=0)         # [Nb*3, T]
        ns_ref[:] = jnp.where(nd[None, :] == gb * Nb + iota_k // 3,
                              ghw_rep, 0.0)

    binf = b_ref[0, 0, :].astype(jnp.int32)   # i8/i16 in HBM (gbm._bin_frame
    #                                           packs <=125-bin configs to
    #                                           int8); upcast per tile
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    if _MXU_MODE == "highest":
        bin_oh_T = (iota_r == binf[None, :]).astype(jnp.float32)   # [S, T]
        acc = jax.lax.dot_general(
            bin_oh_T, ns_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)                   # [S, Nb*3]
    else:
        # one-hot is bf16-exact; split only the stats operand into bf16
        # digits and accumulate the partial products in f32 — 2 (or 3)
        # MXU passes instead of HIGHEST's 6 (see _MXU_MODE)
        oh16 = (iota_r == binf[None, :]).astype(jnp.bfloat16)      # [S, T]

        def bdot(rhs16):
            return jax.lax.dot_general(
                oh16, rhs16, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        ns = ns_ref[:]
        hi = ns.astype(jnp.bfloat16)
        acc = bdot(hi)
        r1 = ns - hi.astype(jnp.float32)
        m1 = r1.astype(jnp.bfloat16)
        acc += bdot(m1)
        if _MXU_MODE == "hilo3":
            r2 = (r1 - m1.astype(jnp.float32)).astype(jnp.bfloat16)
            acc += bdot(r2)
    out_ref[0, 0, pl.ds(fi * S, S), :] += acc


@partial(jax.jit, static_argnames=("n_nodes", "n_bins_tot"))
def hist_pallas(binned_T, node, g, h, w, n_nodes: int, n_bins_tot: int):
    """[F, n_nodes*n_bins_tot, 3] histograms (same layout as the XLA path)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, Bt, T = n_nodes, n_bins_tot, _TILE
    F, R = binned_T.shape
    S = ((Bt + 7) // 8) * 8
    Nb, Fb = _plan(N, F, Bt)
    n_gb = (N + Nb - 1) // Nb
    n_fb = (F + Fb - 1) // Fb
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
    act = node >= 0
    # stats-major [3, R] / [1, R]: see layout note in the kernel
    ghw_T = jnp.stack([g, h, w], 0) * act[None, :].astype(jnp.float32)
    nodec = jnp.where(act, node, -1)[None, :]
    out = pl.pallas_call(
        partial(_hist_kernel, Nb=Nb, S=S, T=T, Fb=Fb),
        interpret=_INTERPRET,
        out_shape=jax.ShapeDtypeStruct((n_gb, n_fb, Fb * S, Nb * 3),
                                       jnp.float32),
        grid=(n_gb, n_fb, Rp // T, Fb),
        in_specs=[
            pl.BlockSpec((1, 1, T), lambda gb, fb, i, fi: (fb * Fb + fi, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T), lambda gb, fb, i, fi: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, T), lambda gb, fb, i, fi: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, Fb * S, Nb * 3),
                               lambda gb, fb, i, fi: (gb, fb, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((Nb * 3, T), jnp.float32)],
    )(binned_T[:, None, :], nodec, ghw_T)
    # [n_gb, n_fb, Fb*S, Nb*3] → [F, N, S, 3] → clip padding → [F, N*Bt, 3]
    out = out.reshape(n_gb, n_fb, Fb, S, Nb, 3)
    out = out.transpose(1, 2, 0, 4, 3, 5).reshape(n_fb * Fb, n_gb * Nb, S, 3)
    out = out[:F, :N, :Bt]
    return out.reshape(F, N * Bt, 3)
