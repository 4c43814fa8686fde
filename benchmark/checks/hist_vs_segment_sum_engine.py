"""``hist_vs_segment_sum`` at the ENGINE's bins: the histogram path the cell
takes against a plain float64 segment sum, at the bin count and bin storage
the built model really ran (read off the width of its ``edges``: with
categorical columns of more levels than ``nbins`` that is
``max(nbins, largest categorical bin count)``, 300 + the missing bin and
int16 in ``gbm-airline-cat-100``, where ``params.nbins`` says 100 and int8),
and at the TWO deepest histogram levels of a tree: with sibling subtraction
2^(depth-2) and 2^(depth-3) parent slots (256 and 128 at depth 10: node
blocks of 64, a pass a bf16 digit).

Same tolerances as ``hist_vs_segment_sum`` and for the same reason: rtol
5e-4, atol 5e-3, what the kernel's two-digit bf16 split (``hilo``) is tested
to in the program's own suite; a single bf16 digit (4e-3 relative) fails it.
"""

from __future__ import annotations

import functools

import numpy as np

RTOL, ATOL = 5e-4, 5e-3


def check(ctx) -> dict:
    import jax

    from benchmark.reference.hist_segment_sum import level_histograms
    from h2o3_tpu.models import tree
    from h2o3_tpu.ops import pallas_hist
    from h2o3_tpu.ops.quantile import bin_dtype
    from h2o3_tpu.parallel.mesh import row_sharding

    rows = ctx.cell.size(ctx.traffic, "hist_check_rows")
    depth = int(ctx.params["max_depth"])
    feats = int(ctx.data["features"])
    n_bins = int(ctx.model.output["edges"].shape[1]) + 1
    n_bins_tot = n_bins + 1
    dtype = np.dtype(bin_dtype(n_bins))

    rng = np.random.default_rng(ctx.cell.seed)
    binned = rng.integers(0, n_bins_tot, size=(rows, feats)).astype(dtype)
    g = rng.normal(size=rows).astype(np.float32)
    h = (rng.random(rows) + 0.1).astype(np.float32)
    w = np.ones(rows, np.float32)
    d_binned = jax.device_put(binned, row_sharding(2))
    d_g, d_h, d_w = (jax.device_put(v, row_sharding(1)) for v in (g, h, w))
    mesh = tree.hist_mesh(d_binned)
    if mesh is tree.UNFUSED:
        raise RuntimeError("a frame-like operand is not fusable")

    out = {"ok": True, "rows": rows, "n_bins_tot": n_bins_tot,
           "bin_dtype": dtype.name, "levels": []}
    for n_nodes in sorted({max(1, 2 ** (depth - 2)), max(1, 2 ** (depth - 3))},
                          reverse=True):
        node = rng.integers(-1, n_nodes, size=rows).astype(np.int32)
        want = level_histograms(binned, node, g, h, w, n_nodes, n_bins_tot)
        d_node = jax.device_put(node, row_sharding(1))
        if mesh is None:
            if not pallas_hist.pallas_available(n_nodes, feats, n_bins_tot):
                raise RuntimeError(f"{n_nodes} slots x {feats} features x "
                                   f"{n_bins_tot} bins is outside the "
                                   "kernel's envelope")
            path = "pallas"
            got = pallas_hist.hist_pallas(d_binned.T, d_node, d_g, d_h, d_w,
                                          n_nodes, n_bins_tot)
        else:
            path = "fused_scatter"
            got = jax.jit(functools.partial(
                tree._level_histograms_fused, n_nodes=n_nodes,
                n_bins_tot=n_bins_tot, mesh=mesh))(d_binned, d_node, d_g,
                                                   d_h, d_w)
        got = np.asarray(got, np.float64)
        ok = got.shape == want.shape and np.allclose(got, want, rtol=RTOL,
                                                     atol=ATOL)
        out["ok"] = bool(out["ok"] and ok)
        out["path"] = path
        out["levels"].append({
            "n_nodes": n_nodes, "ok": bool(ok),
            "max_scaled_err": float(np.max(np.abs(got - want)
                                           / (np.abs(want) + 1.0)))})
    return out
