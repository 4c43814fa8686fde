"""Frame — a named list of equal-length distributed columns.

Reference: ``water/fvec/Frame.java`` (2,005 LoC) — ordered name→Vec mapping with
column add/remove/slice; all Vecs share one ESPC row layout. Here all Vecs of a
Frame share one padded length and one row sharding, so any subset of columns can
be stacked into a [rows, k] matrix for MXU-friendly compute without relayout.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.types import VecType
from h2o3_tpu.frame.vec import Vec, padded_len


class Frame:
    """Distributed columnar table (reference: ``water.fvec.Frame``)."""

    def __init__(self, names: Sequence[str], vecs: Sequence[Vec], key: str | None = None):
        if len(names) != len(vecs):
            raise ValueError("names/vecs length mismatch")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate column names: {names}")
        nrows = {v.nrows for v in vecs}
        if len(nrows) > 1:
            raise ValueError(f"vecs disagree on nrows: {nrows}")
        self.names: list[str] = list(names)
        self.vecs: list[Vec] = list(vecs)
        self.key = key
        # mesh-view bookkeeping (see on_mesh): structural mutations bump the
        # epoch, which invalidates every cached resharded view of this frame
        self._view_epoch: int = 0
        self._mesh_views: dict[tuple, "Frame | str"] = {}
        self._is_mesh_view: bool = False

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_arrays(cols: Mapping[str, np.ndarray], types: Mapping[str, VecType] | None = None,
                    key: str | None = None) -> "Frame":
        """Build a frame with BATCHED device upload: all float columns go up
        as one transfer and all categorical code columns as another (in
        place of one ``device_put`` per column)."""
        from h2o3_tpu.frame.vec import CAT_NA, _factorize, _guess_type, upload_columns
        types = types or {}
        names = list(cols.keys())
        plans: dict[str, Vec] = {}
        float_cols: list[tuple[str, np.ndarray, VecType]] = []
        cat_cols: list[tuple[str, np.ndarray, tuple]] = []
        for k in names:
            v = np.asarray(cols[k])
            t = types.get(k) or _guess_type(v)
            if t is VecType.CAT and v.dtype.kind not in "iu":
                codes, dom = _factorize(v)
                cat_cols.append((k, codes.astype(np.int32), tuple(dom)))
            elif t is VecType.CAT:
                # caller passed codes + (domain unknown) — per-column path
                plans[k] = Vec.from_numpy(v, type=t)
            elif t in (VecType.NUM, VecType.INT) and v.dtype.kind in "fiub":
                float_cols.append((k, np.asarray(v, np.float32), t))
            else:
                plans[k] = Vec.from_numpy(v, type=t)
        nrows = len(next(iter(cols.values()))) if cols else 0
        fdev = upload_columns([h for _, h, _ in float_cols], nrows, np.nan, np.float32)
        cdev = upload_columns([c for _, c, _ in cat_cols], nrows, CAT_NA, np.int32)
        for (k, _, t), d in zip(float_cols, fdev):
            plans[k] = Vec.from_device(d, nrows, t)
        for (k, _, dom), d in zip(cat_cols, cdev):
            plans[k] = Vec.from_device(d, nrows, VecType.CAT, domain=dom)
        return Frame(names, [plans[k] for k in names], key=key)

    @staticmethod
    def from_pandas(df, key: str | None = None) -> "Frame":
        """Convert a pandas DataFrame (type guessing per parser semantics);
        numeric/categorical columns ride the batched upload of
        :meth:`from_arrays`."""
        cols: dict[str, np.ndarray] = {}
        types: dict[str, VecType] = {}
        time_cols: dict[str, np.ndarray] = {}
        for col in df.columns:
            s = df[col]
            name = str(col)
            if s.dtype.kind in "OUS" or str(s.dtype) in ("category", "str"):
                if str(s.dtype) == "category":
                    # re-factorize so the domain is sorted (parser contract)
                    cols[name] = s.astype(object).to_numpy()
                else:
                    cols[name] = s.to_numpy(dtype=object)
            elif s.dtype.kind == "M":
                # pandas >=3.0 defaults to datetime64[us]; Vec normalizes to ns
                time_cols[name] = s.to_numpy()
            elif s.dtype.kind == "b":
                cols[name] = s.to_numpy().astype(np.float32)
                types[name] = VecType.INT
            else:
                cols[name] = s.to_numpy(dtype=np.float32, na_value=np.nan)
        fr = Frame.from_arrays(cols, types=types)
        names, vecs = [], []
        for col in df.columns:
            name = str(col)
            if name in time_cols:
                names.append(name)
                vecs.append(Vec.from_numpy(time_cols[name], type=VecType.TIME))
            else:
                names.append(name)
                vecs.append(fr.vec(name))
        return Frame(names, vecs, key=key)

    # -- shape --------------------------------------------------------------

    @property
    def nrows(self) -> int:
        return self.vecs[0].nrows if self.vecs else 0

    @property
    def ncols(self) -> int:
        return len(self.vecs)

    @property
    def plen(self) -> int:
        """Padded device length shared by all on-device columns."""
        return self.vecs[0].plen if self.vecs else padded_len(0)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.nrows, self.ncols)

    @property
    def nbytes(self) -> int:
        """Summed resident bytes of every column (device chunks + host
        payloads) — what `/3/Memory` reports for this frame's key."""
        return sum(v.nbytes for v in self.vecs)

    @property
    def types(self) -> dict[str, str]:
        return {n: str(v.type) for n, v in zip(self.names, self.vecs)}

    def drop_device_views(self) -> int:
        """Release every column's derived (decompress-on-access) device
        array — the Cleaner's cheapest eviction tier for frames built by
        the streaming ingest path. Returns freed device bytes; columns
        without a compressed host payload are untouched."""
        return sum(v.drop_device() for v in self.vecs)

    # -- column access ------------------------------------------------------

    def vec(self, col: int | str) -> Vec:
        return self.vecs[self._index(col)]

    def _index(self, col: int | str) -> int:
        if isinstance(col, (int, np.integer)):
            return int(col)
        try:
            return self.names.index(col)
        except ValueError:
            raise KeyError(f"no such column: {col!r} (have {self.names})") from None

    def __getitem__(self, sel):
        if isinstance(sel, (str, int, np.integer)):
            i = self._index(sel)
            return Frame([self.names[i]], [self.vecs[i]])
        if isinstance(sel, (list, tuple)):
            idxs = [self._index(c) for c in sel]
            return Frame([self.names[i] for i in idxs], [self.vecs[i] for i in idxs])
        if isinstance(sel, Vec):           # boolean row filter (rapids AstRowSlice)
            return self.filter(sel)
        raise TypeError(f"unsupported selector {sel!r}")

    def __contains__(self, name: str) -> bool:
        return name in self.names

    def add(self, name: str, vec: Vec) -> "Frame":
        if vec.nrows != self.nrows and self.vecs:
            raise ValueError("row count mismatch")
        if name in self.names:
            raise ValueError(f"duplicate column name: {name!r}")
        self.names.append(name)
        self.vecs.append(vec)
        self.invalidate_views()
        return self

    def remove(self, col: int | str) -> Vec:
        i = self._index(col)
        self.names.pop(i)
        self.invalidate_views()
        return self.vecs.pop(i)

    def replace_vec(self, col: int | str, vec: Vec) -> "Frame":
        """Replace a column's Vec IN PLACE (impute, pipeline transforms).
        Goes through here — not ``frame.vecs[i] = ...`` — so cached mesh
        views are invalidated: a slice-bound build resharding this frame
        must see the replacement, never the pre-mutation column."""
        if vec.nrows != self.nrows and self.vecs:
            raise ValueError("row count mismatch")
        self.vecs[self._index(col)] = vec
        self.invalidate_views()
        return self

    def subframe(self, cols: Iterable[str]) -> "Frame":
        return self[list(cols)]

    # -- mesh views (slice-bound builds; orchestration/scheduler.py) ---------

    def invalidate_views(self) -> None:
        """Drop every cached resharded view (called on structural mutation —
        add/remove — so a slice-bound build can never train on a stale
        column set). DKV-registered view keys are removed so their bytes
        leave ``/3/Memory`` with them."""
        self._view_epoch += 1
        stale, self._mesh_views = self._mesh_views, {}
        if any(isinstance(v, str) for v in stale.values()):
            from h2o3_tpu.utils.registry import DKV
            for v in stale.values():
                if isinstance(v, str):
                    DKV.remove(v)

    def on_mesh(self, mesh) -> "Frame":
        """This frame resharded onto ``mesh`` — ONE batched ``device_put``
        of the stacked column matrix per dtype (the ``upload_columns``
        pattern: one batched transfer, not one per column).

        Returns ``self`` when the frame is already laid out on ``mesh``'s
        device set. Views are cached per (device set, mutation epoch) and
        byte-accounted: a keyed frame's views register in the DKV under
        ``{key}::mesh[...]`` so ``/3/Memory`` shows resharded bytes and the
        Cleaner can evict them (an evicted view is simply rebuilt from the
        source columns on next use)."""
        from h2o3_tpu.parallel.mesh import mesh_device_ids
        dev_idx = [i for i, v in enumerate(self.vecs) if v.data is not None]
        if not dev_idx:
            return self
        target = mesh_device_ids(mesh)
        cur = getattr(self.vecs[dev_idx[0]].data, "sharding", None)
        cur_devs = tuple(sorted(d.id for d in getattr(cur, "device_set", ())
                                )) if cur is not None else ()
        if cur_devs == target:
            return self
        ck = (target, self._view_epoch)
        cached = self._mesh_views.get(ck)
        if cached is not None:
            if isinstance(cached, Frame):
                return cached
            # DKV-registered view: rebuild if it was evicted/removed
            from h2o3_tpu.utils.cleaner import CLEANER
            from h2o3_tpu.utils.registry import DKV
            with DKV._lock:
                live = DKV._store.get(cached)
            if type(live).__name__ == "Frame":
                # keep hot views off the LRU chopping block (on_mesh reads
                # the raw store, so DKV.get's access accounting never fires)
                CLEANER.touch(cached)
                return live
        view = self._reshard(mesh)
        view._is_mesh_view = True
        if self.key:
            from h2o3_tpu.utils.registry import DKV
            vkey = f"{self.key}::mesh[{'-'.join(map(str, target))}]" \
                   f"@{self._view_epoch}"
            view.key = vkey
            DKV.put(vkey, view)
            self._mesh_views[ck] = vkey
        else:
            self._mesh_views[ck] = view
        return view

    def _reshard(self, mesh) -> "Frame":
        """Copy every device column onto ``mesh`` in (at most) two batched
        transfers — one [k, plen] float stack, one int stack for CAT codes —
        then slice rows back out (each slice inherits the target row
        sharding, exactly like ``upload_columns``)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from h2o3_tpu.parallel.mesh import ROWS
        sharding = NamedSharding(mesh, P(None, ROWS))
        groups: dict[str, list[int]] = {}
        for i, v in enumerate(self.vecs):
            if v.data is not None:
                groups.setdefault(str(v.data.dtype), []).append(i)
        moved: dict[int, jax.Array] = {}
        for idxs in groups.values():
            stacked = jnp.stack([self.vecs[i].data for i in idxs], axis=0)
            dev = jax.device_put(stacked, sharding)
            for j, i in enumerate(idxs):
                moved[i] = dev[j]
        vecs = []
        for i, v in enumerate(self.vecs):
            if i not in moved:
                vecs.append(v)          # host-only columns share the payload
                continue
            nv = Vec(moved[i], v.type, v.nrows, domain=v.domain,
                     host_values=v.host_values, time_offset=v.time_offset)
            nv._rollups = v._rollups    # rollups are layout-independent
            vecs.append(nv)
        return Frame(list(self.names), vecs)

    # -- device views -------------------------------------------------------

    def row_mask(self) -> jax.Array:
        """Boolean [plen] device array marking logical (non-padding) rows."""
        return _row_mask(self.plen, jnp.int32(self.nrows))

    def matrix(self, cols: Sequence[str] | None = None) -> jax.Array:
        """Stack on-device columns into a [plen, k] float32 matrix.

        Categorical columns contribute their raw codes as floats (NaN for NA);
        model-ready expansion (one-hot etc.) lives in DataInfo, mirroring the
        reference split between ``Frame`` and ``hex/DataInfo.java``.
        """
        cols = list(cols) if cols is not None else [n for n, v in zip(self.names, self.vecs)
                                                    if v.type.on_device]
        arrs = []
        for c in cols:
            v = self.vec(c)
            if not v.type.on_device:
                raise TypeError(f"column {c!r} of type {v.type} has no device data")
            arrs.append(v.as_float())
        return jnp.stack(arrs, axis=1)

    # -- host round-trip ----------------------------------------------------

    def to_pandas(self):
        import pandas as pd
        out = {}
        for n, v in zip(self.names, self.vecs):
            if v.type is VecType.CAT:
                codes = v.to_numpy()
                if len(v.domain) == 0:  # all-missing column: no levels to index
                    out[n] = pd.Series([None] * v.nrows, dtype=object)
                    continue
                dom = np.asarray(v.domain, dtype=object)
                vals = np.where(codes >= 0, dom[np.clip(codes, 0, None)], None)
                out[n] = pd.Series(vals, dtype=object)
            elif v.type is VecType.TIME:
                out[n] = pd.to_datetime(pd.Series(v.to_numpy()), unit="ms")
            elif v.type.on_device:
                out[n] = v.to_numpy()
            else:
                out[n] = pd.Series(v.host_values, dtype=object)
        return pd.DataFrame(out)

    def head(self, n: int = 10):
        return self.to_pandas().head(n)

    # -- munging surface (rapids layer; mirrors h2o-py H2OFrame methods) -----

    def sort(self, by, ascending=True) -> "Frame":
        from h2o3_tpu.rapids import munge
        return munge.sort(self, by, ascending)

    def group_by(self, by):
        from h2o3_tpu.rapids import GroupBy
        return GroupBy(self, by)

    def merge(self, other: "Frame", by=None, all_x: bool = False,
              all_y: bool = False) -> "Frame":
        from h2o3_tpu.rapids import munge
        return munge.merge(self, other, by=by, all_x=all_x, all_y=all_y)

    def filter(self, mask) -> "Frame":
        from h2o3_tpu.rapids import munge
        return munge.filter_rows(self, mask)

    def rbind(self, *others: "Frame") -> "Frame":
        from h2o3_tpu.rapids import munge
        return munge.rbind(self, *others)

    def cbind(self, *others: "Frame") -> "Frame":
        from h2o3_tpu.rapids import munge
        return munge.cbind(self, *others)

    def split_frame(self, ratios=(0.75,), destination_frames=None,
                    seed: int = -1) -> list["Frame"]:
        from h2o3_tpu.frame.utils import split_frame
        return split_frame(self, ratios, destination_frames, seed)

    def unique(self, cols=None) -> "Frame":
        from h2o3_tpu.rapids import munge
        return munge.unique(self, cols)

    def pivot(self, index: str, column: str, value: str, agg: str = "mean") -> "Frame":
        from h2o3_tpu.rapids import munge
        return munge.pivot(self, index, column, value, agg)

    def melt(self, id_vars, value_vars=None, **kw) -> "Frame":
        from h2o3_tpu.rapids import munge
        return munge.melt(self, id_vars, value_vars, **kw)

    def quantile(self, probs=(0.001, 0.01, 0.1, 0.25, 0.333, 0.5, 0.667,
                              0.75, 0.9, 0.99, 0.999)) -> "Frame":
        from h2o3_tpu.rapids import ops
        return ops.quantile(self, probs)

    def impute(self, column: str, method: str = "mean", by=None) -> "Frame":
        from h2o3_tpu.rapids import ops
        return ops.impute(self, column, method, by)

    def scale(self, center: bool = True, scale: bool = True) -> "Frame":
        from h2o3_tpu.rapids import ops
        return ops.scale(self, center, scale)

    def __len__(self) -> int:
        return self.nrows

    def __repr__(self) -> str:
        return f"Frame({self.nrows} rows x {self.ncols} cols: {self.names[:8]}{'...' if self.ncols > 8 else ''})"


from functools import partial


@partial(jax.jit, static_argnames=("plen",))
def _row_mask(plen: int, nrows: jax.Array) -> jax.Array:
    return jnp.arange(plen) < nrows
