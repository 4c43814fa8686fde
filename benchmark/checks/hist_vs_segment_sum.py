"""The histogram the cell's trees are built from, against a plain float64
segment sum, at the cell's own bins, bin storage and deepest level.

Which of the program's histogram paths is checked is what the program's own
dispatch (``tree.hist_mesh``) answers for an operand placed like a frame's
columns: on one chip the Pallas kernel ``hist_pallas``, across chips the
sharded ``_level_histograms_fused`` with its all-reduce. Tolerance: rtol
5e-4, atol 5e-3, what the kernel's two-pass bf16 split (``hilo``) is tested
to in the program's own suite; a single bf16 pass (4e-3 relative) fails it.
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
    nbins, depth = int(ctx.params["nbins"]), int(ctx.params["max_depth"])
    feats = int(ctx.data["features"])
    n_bins_tot = nbins + 1
    # with sibling subtraction the deepest level's call covers half of the
    # level's 2^(depth-1) nodes
    n_nodes = max(1, 2 ** (depth - 2))
    dtype = np.dtype(bin_dtype(nbins))

    rng = np.random.default_rng(ctx.cell.seed)
    binned = rng.integers(0, n_bins_tot, size=(rows, feats)).astype(dtype)
    node = rng.integers(-1, n_nodes, size=rows).astype(np.int32)
    g = rng.normal(size=rows).astype(np.float32)
    h = (rng.random(rows) + 0.1).astype(np.float32)
    w = np.ones(rows, np.float32)
    want = level_histograms(binned, node, g, h, w, n_nodes, n_bins_tot)

    d_binned = jax.device_put(binned, row_sharding(2))
    d_node, d_g, d_h, d_w = (jax.device_put(v, row_sharding(1))
                             for v in (node, g, h, w))
    mesh = tree.hist_mesh(d_binned)
    if mesh is None:
        if not pallas_hist.pallas_available(n_nodes, feats, n_bins_tot):
            raise RuntimeError("the cell's deepest level is outside the "
                               "kernel's envelope")
        path = "pallas"
        got = pallas_hist.hist_pallas(d_binned.T, d_node, d_g, d_h, d_w,
                                      n_nodes, n_bins_tot)
    else:
        if mesh is tree.UNFUSED:
            raise RuntimeError("a frame-like operand is not fusable")
        path = "fused_scatter"
        got = jax.jit(functools.partial(
            tree._level_histograms_fused, n_nodes=n_nodes,
            n_bins_tot=n_bins_tot, mesh=mesh))(d_binned, d_node, d_g, d_h, d_w)
    got = np.asarray(got, np.float64)
    err = float(np.max(np.abs(got - want) / (np.abs(want) + 1.0)))
    ok = got.shape == want.shape and np.allclose(got, want, rtol=RTOL,
                                                 atol=ATOL)
    return {"ok": bool(ok), "path": path, "rows": rows, "n_nodes": n_nodes,
            "n_bins_tot": n_bins_tot, "bin_dtype": dtype.name,
            "max_scaled_err": err}
