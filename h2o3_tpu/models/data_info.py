"""DataInfo — adapts a Frame into a model-ready design matrix.

Reference: ``h2o-algos/.../hex/DataInfo.java`` (~1.3 kLoC): shared by
GLM/DL/GAM/PCA etc.; lays out categorical one-hot blocks first then numeric
columns, handles ``use_all_factor_levels`` (``DataInfo.java:112``),
standardization (``_normMul`` ``:120``), and missing-value imputation
(``:149``). Test-time frames are adapted to the train layout
(``hex/Model.adaptTestForTrain``): categorical levels are matched by name,
unseen levels become missing.

TPU-native: expansion is a jitted gather/compare producing a dense f32
[rows, K] matrix straight into HBM — dense one-hot blocks feed the MXU
(a Gram of one-hot blocks is exactly a matmul), so there is no sparse row
format like the reference's ``DataInfo.Row``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.types import VecType


@dataclasses.dataclass
class DataInfo:
    cat_cols: list[str]
    num_cols: list[str]
    cat_domains: list[tuple[str, ...]]     # train-time domains, layout order
    cat_offsets: np.ndarray                # start of each cat block in X
    num_means: np.ndarray                  # imputation values
    num_mul: np.ndarray                    # 1/sigma (or 1) per numeric col
    num_sub: np.ndarray                    # mean (or 0) per numeric col
    use_all_factor_levels: bool
    standardize: bool
    ncats_expanded: int
    # predictors ``make(ignore_const_cols=True)`` left out: constant over the
    # training rows, so absent from cat_cols / num_cols and from the design
    ignored_const_cols: tuple[str, ...] = ()

    @property
    def ncols_expanded(self) -> int:
        return self.ncats_expanded + len(self.num_cols)

    @property
    def coef_names(self) -> list[str]:
        names = []
        for col, dom in zip(self.cat_cols, self.cat_domains):
            lo = 0 if self.use_all_factor_levels else 1
            names += [f"{col}.{lvl}" for lvl in dom[lo:]]
        return names + list(self.num_cols)

    # -- construction (train side) ------------------------------------------

    @staticmethod
    def make(frame: Frame, x: list[str], standardize: bool = True,
             use_all_factor_levels: bool = False,
             ignore_const_cols: bool = False) -> "DataInfo":
        """``ignore_const_cols`` (reference: ``Model.Parameters
        ._ignore_const_cols``) leaves out every predictor whose roll-up says
        it is constant over the training rows: min equal to max, or nothing
        but missing values. A scoring frame may still hold the column;
        ``expand`` reads the kept columns by name."""
        dropped: tuple[str, ...] = ()
        if ignore_const_cols:
            dropped = tuple(c for c in x if _is_constant(frame.vec(c)))
            x = [c for c in x if c not in dropped]
        cat_cols = [c for c in x if frame.vec(c).is_categorical]
        num_cols = [c for c in x if not frame.vec(c).is_categorical]
        for c in num_cols:
            if not frame.vec(c).type.on_device:
                raise TypeError(f"column {c!r} has type {frame.vec(c).type}; not trainable")
        cat_domains = [frame.vec(c).domain for c in cat_cols]
        offs, k = [], 0
        for dom in cat_domains:
            offs.append(k)
            k += len(dom) if use_all_factor_levels else max(len(dom) - 1, 0)
        means = np.array([frame.vec(c).mean() for c in num_cols], np.float32)
        sigmas = np.array([frame.vec(c).sigma() for c in num_cols], np.float32)
        means = np.nan_to_num(means)
        mul = np.where((sigmas > 0) & np.isfinite(sigmas), 1.0 / np.maximum(sigmas, 1e-30), 1.0).astype(np.float32) \
            if standardize else np.ones_like(means)
        sub = means if standardize else np.zeros_like(means)
        return DataInfo(cat_cols, num_cols, cat_domains, np.array(offs, np.int32),
                        means, mul, sub, use_all_factor_levels, standardize, k,
                        dropped)

    # -- expansion (train or adapted test) ----------------------------------

    def expand(self, frame: Frame) -> jax.Array:
        """Build the [plen, K] design matrix; test domains adapted by name."""
        cats = []
        for col, train_dom in zip(self.cat_cols, self.cat_domains):
            v = frame.vec(col)
            codes = v.data
            if v.type is not VecType.CAT:
                raise TypeError(f"column {col!r} must be categorical at scoring time")
            if v.domain != train_dom:
                codes = _remap_codes(codes, v.domain, train_dom)
            cats.append(codes)
        nums = [frame.vec(c).data for c in self.num_cols]
        if not cats and not nums:
            return jnp.zeros((frame.plen, 0), jnp.float32)
        # the columns go in as they are: one dispatch, nothing stacked first
        cards = tuple(len(d) for d in self.cat_domains)
        return _expand(tuple(cats), tuple(nums), cards,
                       self.use_all_factor_levels, self.num_sub, self.num_mul,
                       self.num_means)

    def response(self, frame: Frame, y: str) -> tuple[jax.Array, int]:
        """Response column as f32 (codes for cat) + number of classes (0=regression)."""
        v = frame.vec(y)
        if v.is_categorical:
            return v.data.astype(jnp.float32), v.cardinality()
        return v.data, 0


def _is_constant(vec) -> bool:
    """One value, or none, over the rows the roll-up saw (reference:
    ``Vec.isConst() || Vec.isBad()``)."""
    r = vec.rollups()
    return r.na_cnt >= r.nrows or r.min == r.max


def response_as_float(vec) -> tuple[jax.Array, jax.Array]:
    """Response as f32 + per-row validity mask — THE canonical NA semantics for
    supervised training/metrics (cat code -1 and numeric NaN are missing).
    Single home so trainers, holdout metrics, and CV masks cannot diverge."""
    yy = vec.data.astype(jnp.float32) if vec.is_categorical else vec.data
    valid = (vec.data >= 0) if vec.is_categorical else ~jnp.isnan(vec.data)
    return yy, valid


def expand_interactions(frame, interactions: list[str], domains=None):
    """Pairwise interaction columns among ``interactions`` (reference:
    ``hex/DataInfo.java`` interactions / ``CreateInteractions``):

    - num × num → elementwise product column ``a_b``
    - cat × num → one numeric column per level: ``cat.lvl_num`` (indicator
      times the numeric value)
    - cat × cat → combined factor ``a_b`` (level cross)

    Returns an EXTENDED frame (originals untouched); both train and score
    paths route through here so the expansion cannot drift. ``domains``
    (``{col: train_domain}``, captured at train) pins the cat×num column
    set: a scoring batch missing some training level still produces every
    design column (its indicator is simply all-zero)."""
    import itertools

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.types import VecType
    from h2o3_tpu.frame.vec import Vec

    domains = domains or {}
    out = Frame(list(frame.names), list(frame.vecs), key=frame.key)
    for a, b in itertools.combinations(interactions, 2):
        va, vb = frame.vec(a), frame.vec(b)
        name = f"{a}_{b}"
        if va.is_categorical and vb.is_categorical:
            from h2o3_tpu.frame.utils import interaction as cat_cross
            crossed = cat_cross(frame, [[a, b]], pairwise=True)
            out.add(name, crossed.vec(0))
        elif not va.is_categorical and not vb.is_categorical:
            out.add(name, Vec(va.as_float() * vb.as_float(), VecType.NUM,
                              frame.nrows))
        else:
            cat, num = (va, vb) if va.is_categorical else (vb, va)
            cname = a if va.is_categorical else b
            dom = domains.get(cname, cat.domain or ())
            codes = cat.data
            if cat.domain != tuple(dom):
                codes = _remap_codes(codes, cat.domain or (), tuple(dom))
            for li, lvl in enumerate(dom):
                ind = (codes == li).astype(jnp.float32)
                out.add(f"{cname}.{lvl}_{name}",
                        Vec(ind * jnp.nan_to_num(num.as_float(), nan=0.0),
                            VecType.NUM, frame.nrows))
    return out


def response_adapted(vec, train_domain) -> tuple[jax.Array, jax.Array]:
    """Response as f32 + validity, remapped to the TRAIN domain when the
    frame's categorical levels differ (``Model.adaptTestForTrain`` semantics;
    unseen levels → invalid). The single home for held-out response adaptation
    — model_performance and mid-train validation scoring both route here."""
    if train_domain and vec.is_categorical and vec.domain != train_domain:
        codes = _remap_codes(vec.data, vec.domain or (), train_domain)
        return codes.astype(jnp.float32), codes >= 0
    return response_as_float(vec)


def _remap_codes(codes: jax.Array, src_dom: tuple[str, ...], dst_dom: tuple[str, ...]) -> jax.Array:
    """Align test categorical codes to the train domain (unseen → NA).

    Reference: ``Model.adaptTestForTrain`` domain mapping."""
    lut_host = np.full(max(len(src_dom), 1), -1, np.int32)
    dst = {s: i for i, s in enumerate(dst_dom)}
    for i, s in enumerate(src_dom):
        lut_host[i] = dst.get(s, -1)
    lut = jnp.asarray(lut_host)
    return jnp.where(codes >= 0, lut[jnp.clip(codes, 0, len(lut_host) - 1)], -1)


_STRIP = 128   # design columns made at a time


@partial(jax.jit, static_argnames=("cards", "use_all"))
def _expand(cats: tuple, nums: tuple, cards: tuple[int, ...], use_all: bool,
            sub, mul, impute):
    """Dense one-hot + standardized-numeric expansion of a frame's columns
    (``cats``: the level codes of each categorical column, ``nums``: each
    numeric column, all [rows]), fully fused.

    Missing values: cat NA (-1) → all-zero block; numeric NaN → imputed to the
    mean, i.e. 0 after standardization (reference MeanImputation semantics).

    Which source column and which level a design column stands for are
    constants of its index, so a one-hot column is ``code of its source ==
    its level``: ONE compare an element, once the source's codes stand over
    the source's columns. The design is made in strips of 128 columns, each by
    elementwise work over its own [rows, 128] alone: the codes are laid over
    the strip by one select a source that reaches into it (one categorical
    column that fills a strip costs none, a hundred narrow ones their count),
    so a frame pays for the sources a strip holds and not for all it has.
    XLA writes each strip into the output in place (the design's layout on a
    TPU is column-major). Blocks of the sources' own widths built apart and
    concatenated cost a second buffer as large as the design (7.7 GB at 3M x
    668 on a v5e, reserved by the program and not shown in
    ``peak_bytes_in_use``; PERF.md, PR 26).
    """
    lo = 0 if use_all else 1
    rows = (cats or nums)[0].shape[0]
    # first design column of every source, left to right; sources without a
    # column (one level, first dropped) have none
    first, k = [], 0
    for j, card in enumerate(cards):
        if card - lo > 0:
            first.append((k, j))
            k += card - lo
    k_cat = k
    k += len(nums)
    # mean imputation always (reference MeanImputation), independent of
    # whether standardization is on (sub is 0 when standardize=False)
    scaled = [(jnp.where(jnp.isnan(x), impute[i], x) - sub[i]) * mul[i]
              for i, x in enumerate(nums)]
    starts = np.array([off for off, _ in first])
    strips = []
    for start in range(0, k, _STRIP):
        col = np.arange(start, min(start + _STRIP, k))
        strip = jnp.zeros((rows, len(col)), jnp.float32)
        if start < k_cat:
            # the sources of this strip's columns; a numeric column falls to
            # the last one and takes a level no code has
            owner = np.searchsorted(starts, col, side="right") - 1
            level = np.where(col < k_cat, col - starts[owner] + lo, -2)
            code = cats[first[owner[0]][1]][:, None]
            for o in range(owner[0] + 1, owner[-1] + 1):
                code = jnp.where((col >= starts[o])[None, :],
                                 cats[first[o][1]][:, None], code)
            strip = (code == jnp.asarray(level, code.dtype)[None, :]
                     ).astype(jnp.float32)
        for i in range(max(start - k_cat, 0), min(col[-1] + 1 - k_cat, len(nums))):
            strip = jnp.where((col == k_cat + i)[None, :], scaled[i][:, None], strip)
        strips.append(strip)
    if not strips:
        return jnp.zeros((rows, 0), jnp.float32)
    return jnp.concatenate(strips, axis=1)
