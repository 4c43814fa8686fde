"""Quantile bin-edge computation + feature binning.

Reference: H2O trees bin each feature into ``nbins`` histogram buckets; bin
edges come from global quantiles (``hex/tree/GlobalQuantilesCalc.java``,
``DHistogram.java`` QUANTILES_GLOBAL / UNIFORM_ADAPTIVE) and the XGBoost port
uses the hist method's global quantile sketch. Distributed quantiles in the
reference are an iterative-refinement histogram MRTask
(``hex/quantile/Quantile.java:15,190``).

TPU-native: edges are computed once per training run from a uniform row sample
(the LightGBM/sampled-sketch approach — statistically equivalent for binning
purposes), then the full column is binned on device by compare-and-count:
a value's bin is the number of edges <= it, B-1 compares and adds per
element fused into one pass over the rows (``bin_column``). A binary search
makes log2(B) steps instead, but each step is a GATHER from the edge table,
and on the TPU the gather sets the price, not the compare: at 255 edges
``jnp.searchsorted``'s default took 0.76 s a column of 11M rows (21 s of a
33 s XGBoost build), elementwise compares take tens of milliseconds
(PERF.md, PR 25). Missing values get a dedicated bin (B) so trees can learn
a default direction, matching XGBoost's learned-default-direction semantics.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.utils.telemetry import BIN_COLUMNS


def compute_bin_edges(X_host: np.ndarray, nbins: int,
                      w_host: np.ndarray | None = None) -> np.ndarray:
    """Per-feature quantile edges, shape [F, nbins-1] (inf-padded).

    ``X_host``: a row sample [n, F] (NaNs allowed); ``w_host``: matching
    per-row weights.  Bin b covers [edges[b-1], edges[b]);
    bin(x) = #edges <= x.

    Quantiles are weighted inverted-CDF (the smallest value whose
    cumulative weight reaches q·total).  That definition makes a row
    with weight k bin IDENTICALLY to the same row repeated k times —
    the reference's weights-as-replication contract
    (``pyunit_weights_gbm.py``; ``hex/tree/DHistogram`` sees weighted
    counts the same way).
    """
    n, F = X_host.shape
    qs = np.linspace(0, 1, nbins + 1)[1:-1]
    edges = np.full((F, nbins - 1), np.inf, np.float32)
    if w_host is None:
        w_host = np.ones(n, np.float64)
    for f in range(F):
        col = X_host[:, f]
        m = ~np.isnan(col) & (w_host > 0)
        col, w = col[m], w_host[m]
        if col.size == 0:
            continue
        order = np.argsort(col, kind="stable")
        c, cw = col[order], np.cumsum(w[order])
        pos = np.searchsorted(cw, qs * cw[-1], side="left")
        e = np.unique(c[np.clip(pos, 0, len(c) - 1)])
        edges[f, : len(e)] = e
    return edges


def bin_dtype(nbins: int):
    """Narrowest integer dtype that holds every bin id (0..nbins, where
    ``nbins`` is the NA bin) PLUS the Pallas pad sentinel ``nbins + 2``
    (pallas_hist pads row tiles with ``n_bins_tot + 1``).  The ONE place
    the int8/int16 threshold lives — training (gbm._bin_frame) and
    scoring-frame binning (bin_features) must agree or bins overflow."""
    return jnp.int8 if nbins + 2 <= 127 else jnp.int16


@jax.jit
def _bin_by_compare(col: jax.Array, e: jax.Array) -> jax.Array:
    """``col`` [rows] against one row of edges ``e`` [B-1] (sorted,
    inf-padded) → bins in ``bin_dtype(B)``: the count of edges <= x, which
    is ``np.searchsorted(e, x, side="right")`` on ties, ±inf, -0.0 and the
    inf padding alike; NaN → B. Broadcast compares reduced over the edge
    axis, rows on the minor axis: XLA fuses compare, convert and reduce
    into one loop over the rows, so the [B-1, rows] predicate is never
    stored. No gather, no ``while`` (tests/test_binning.py holds both)."""
    nbins = e.shape[0] + 1
    b = (col[None, :] >= e[:, None]).sum(0, dtype=jnp.int32)
    return jnp.where(jnp.isnan(col), nbins, b).astype(bin_dtype(nbins))


@jax.jit
def _bin_matrix(X: jax.Array, edges: jax.Array) -> jax.Array:
    return jax.vmap(_bin_by_compare, in_axes=(1, 0), out_axes=1)(X, edges)


def bin_column(col: jax.Array, e: jax.Array) -> jax.Array:
    """Bin one column [rows] against its row of ``edges`` [B-1] → int8/int16
    bins in [0, B]; NaN → B (the missing bin). The one binning primitive:
    training frames and checkpoint re-bins (``GBM._bin_frame``, a column at
    a time) and validation frames (``bin_features``) both go through it."""
    BIN_COLUMNS.labels(path="compare").inc()
    return _bin_by_compare(col, e)


def cat_bins_for_codes(X, cat_card, cat_bins: int) -> jax.Array:
    """Map raw categorical codes ``X`` [rows, F] to histogram bins: a bin a
    level while the cardinality ``cat_card`` [F] is at most ``cat_bins``
    (the builder's ``nbins_cats``), contiguous range-grouping into
    ``cat_bins`` bins past it (reference DHistogram nbins_cats grouping).
    A NaN reads bin 0: the caller puts missing rows where it wants them."""
    code = jnp.nan_to_num(X, nan=0.0).astype(jnp.int32)
    card = jnp.maximum(cat_card, 1)[None, :]
    grouped = (code * cat_bins) // card
    return jnp.where(cat_card[None, :] > cat_bins,
                     jnp.clip(grouped, 0, cat_bins - 1),
                     jnp.clip(code, 0, cat_bins - 1)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cat_bins", "n_bins"))
def _bin_by_level(col, card, cat_bins: int, n_bins: int):
    b = cat_bins_for_codes(col[:, None], card[None], cat_bins)[:, 0]
    return jnp.where(jnp.isnan(col), n_bins, b).astype(bin_dtype(n_bins))


def bin_levels(col: jax.Array, card: int, cat_bins: int,
               n_bins: int) -> jax.Array:
    """Bin one CATEGORICAL column [rows] of level codes (as floats) by level
    code (:func:`cat_bins_for_codes`) → bins in ``bin_dtype(n_bins)``;
    NaN → ``n_bins``, the engine's missing bin, which a numeric column's
    edges give it by their width (``n_bins - 1``). One program for every
    cardinality (``card`` is an operand)."""
    BIN_COLUMNS.labels(path="levels").inc()
    return _bin_by_level(col, jnp.int32(card), cat_bins, n_bins)


def bin_features(X: jax.Array, edges: jax.Array) -> jax.Array:
    """Bin a [rows, F] matrix against ``edges`` [F, B-1] → [rows, F]:
    ``bin_column`` over the columns, in one program.

    The narrowest dtype that also holds the Pallas pad sentinel (B + 2)
    is used: int8 up to 125 bins — half the HBM traffic of the histogram
    kernel's dominant input — else int16 (nbins <= 32k).
    """
    BIN_COLUMNS.labels(path="compare").inc(X.shape[1])
    return _bin_matrix(X, edges)


def sample_rows_host(X: jax.Array, nrows: int, max_sample: int = 100_000) -> np.ndarray:
    """Strided row sample fetched to host for edge computation."""
    stride = max(1, nrows // max_sample)
    return np.asarray(jax.device_get(X[: nrows][:: stride]))
