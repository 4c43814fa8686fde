"""Vec — one distributed column.

Reference: ``water/fvec/Vec.java`` — a Vec is a collection of ~64KB compressed
chunks distributed over the cloud by an ESPC (element-start-per-chunk) layout
shared per VectorGroup (``Vec.java:152,264``), with lazily computed rollup
statistics (``RollupStats.java``).

TPU-native redesign: a Vec is ONE row-sharded ``jax.Array`` in HBM, padded to a
multiple of the mesh's row-axis size. The ESPC layout becomes the (uniform)
``NamedSharding(mesh, P("rows"))`` partition; chunk compression becomes dtype
choice (see :mod:`h2o3_tpu.frame.types`); decompress-on-access (``Chunk.atd``)
is unnecessary. String/UUID columns stay host-resident (numpy object arrays) —
they feed munging and parsing, never device compute, matching how the reference
excludes them from model training.
"""

from __future__ import annotations

import math
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.types import CAT_NA, VecType
from h2o3_tpu.frame.rollups import Rollups, cat_rollups, numeric_rollups
from h2o3_tpu.parallel.mesh import (ROWS, bound_mesh, num_global_devices,
                                    row_sharding)
from h2o3_tpu.utils.telemetry import ROLLUP_SECONDS, ROLLUPS
from h2o3_tpu.utils.timeline import OUTSIDE, PHASE

# exported from the start: 0 roll-up seconds is a reading, not absence
ROLLUP_SECONDS.labels(phase=OUTSIDE)

# Pad row counts to a multiple of (devices * _ROW_ALIGN) so every shard is
# sublane-aligned for float32 tiles (8 x 128 min tile).
_ROW_ALIGN = 8


def padded_len(nrows: int, ndev: int | None = None) -> int:
    # against the GLOBAL device count, never just a bound slice: a frame's
    # padded length is a process-wide invariant, and scheduler slices divide
    # it (slice_meshes carves equal divisors), so arrays pad identically no
    # matter which lease creates them. A bound mesh whose size does NOT
    # divide the global unit (public mesh_context with an arbitrary submesh)
    # widens the unit to the lcm so the same array shards cleanly on both
    # the bound and the global mesh.
    if ndev is None:
        ndev = num_global_devices()
        b = bound_mesh()
        if b is not None and ROWS in b.shape:
            ndev = math.lcm(ndev, b.shape[ROWS])
    unit = ndev * _ROW_ALIGN
    return max(unit, ((nrows + unit - 1) // unit) * unit)


def _put(host: np.ndarray, sharding) -> jax.Array:
    """Host→device under the given sharding. Multi-process: the sharding may
    span devices this process cannot address — materialize only the local
    shards from the (replicated) host array (every process holds the full
    ingest, the cross-host Frame layout comes from the mesh)."""
    if jax.process_count() > 1:
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])
    return jax.device_put(host, sharding)


def _upload(host: np.ndarray, nrows: int, fill) -> jax.Array:
    plen = padded_len(nrows)
    padded = np.full(plen, fill, dtype=host.dtype)
    padded[:nrows] = host
    return _put(padded, row_sharding(1))


def upload_columns(hosts: list[np.ndarray], nrows: int, fill, dtype) -> list[jax.Array]:
    """Upload many same-length columns as ONE [ncols, plen] transfer, then
    slice rows on device: one batched transfer in place of one ``device_put``
    per column. The matrix is sharded (replicated, rows) so each row slice
    comes out row-sharded exactly like a per-column upload."""
    if not hosts:
        return []
    from jax.sharding import NamedSharding, PartitionSpec as P

    from h2o3_tpu.parallel.mesh import ROWS, get_mesh
    plen = padded_len(nrows)
    mat = np.full((len(hosts), plen), fill, dtype=dtype)
    for i, h in enumerate(hosts):
        mat[i, :nrows] = h
    dev = _put(mat, NamedSharding(get_mesh(), P(None, ROWS)))
    return [dev[i] for i in range(len(hosts))]


class Vec:
    """One named, typed, distributed column of a Frame."""

    def __init__(
        self,
        data: jax.Array | None,
        type: VecType,
        nrows: int,
        domain: tuple[str, ...] | None = None,
        host_values: np.ndarray | None = None,
        time_offset: float = 0.0,
        compressed=None,
    ):
        self._data = data                 # padded, row-sharded device array (or None for STR/UUID)
        # compressed host payload (ingest/encode.CompressedChunk): when set,
        # the device array is a DERIVED view — ``data`` materializes it on
        # first access and the Cleaner may drop it again (drop_device)
        self._compressed = compressed
        self.type = type
        self.nrows = nrows
        self.domain = domain              # categorical level names, sorted (parser semantics)
        self.host_values = host_values    # host-only payload (STR/UUID; exact f64 ms for TIME)
        # TIME device data is float32 *relative* ms (value - time_offset): epoch
        # millis (~1.8e12) overflow a float32 mantissa, so absolute times live
        # host-side in float64 and device compute uses the shifted column.
        self.time_offset = time_offset
        self._rollups: Rollups | None = None
        # per-vec histogram cache (filled by api/schemas._histogram_cached;
        # lives here so invalidate_rollups clears BOTH derived summaries)
        self._hist_cache: dict | None = None

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_numpy(values: np.ndarray, type: VecType | None = None,
                   domain: Sequence[str] | None = None) -> "Vec":
        """Build a Vec from a host array, guessing the type if not given."""
        nrows = len(values)
        if type is None:
            type = _guess_type(values)
        if type is VecType.TIME and np.asarray(values).dtype.kind == "M":
            ns = np.asarray(values).astype("datetime64[ns]")
            ms = ns.astype(np.int64).astype(np.float64) / 1e6
            ms = np.where(np.isnat(ns), np.nan, ms)
            offset = float(np.nanmin(ms)) if np.isfinite(ms).any() else 0.0
            data = _upload((ms - offset).astype(np.float32), nrows, np.nan)
            return Vec(data, VecType.TIME, nrows, host_values=ms, time_offset=offset)
        if type in (VecType.STR, VecType.UUID):
            return Vec(None, type, nrows, host_values=np.asarray(values, dtype=object))
        if type is VecType.CAT:
            if domain is None:
                codes, domain = _factorize(values)
            else:
                codes = np.asarray(values, dtype=np.int32)
            data = _upload(codes.astype(np.int32), nrows, CAT_NA)
            return Vec(data, type, nrows, domain=tuple(domain))
        host = np.asarray(values, dtype=np.float32)
        data = _upload(host, nrows, np.nan)
        return Vec(data, type, nrows)

    @staticmethod
    def from_device(data: jax.Array, nrows: int, type: VecType = VecType.NUM,
                    domain: tuple[str, ...] | None = None) -> "Vec":
        """Wrap an existing padded, row-sharded device array."""
        return Vec(data, type, nrows, domain=domain)

    @staticmethod
    def from_compressed(chunk, type: VecType, nrows: int,
                        domain: tuple[str, ...] | None = None) -> "Vec":
        """Wrap a compressed host payload (ingest/encode.CompressedChunk);
        the device array materializes lazily on first ``data`` access."""
        return Vec(None, type, nrows, domain=domain, compressed=chunk)

    # -- properties ---------------------------------------------------------

    @property
    def data(self) -> jax.Array | None:
        """The padded, row-sharded device column. For compressed vecs this
        is a DERIVED view: first access decodes the host payload and
        uploads (``Chunk.atd`` decompress-on-access, amortized per column);
        :meth:`drop_device` releases it again."""
        arr = self._data    # local: a concurrent Cleaner drop_device between
        # materialization and return must not turn this access into None
        if arr is None and self._compressed is not None:
            from h2o3_tpu.utils import telemetry as _tm2
            decoded = self._compressed.decode()
            fill = CAT_NA if self.type is VecType.CAT else np.nan
            arr = _upload(decoded, self.nrows, fill)
            self._data = arr
            _tm2.CHUNK_DECOMPRESS.inc()
            _tm2.CHUNK_DECOMPRESS_BYTES.inc(int(decoded.nbytes))
        return arr

    @data.setter
    def data(self, value) -> None:
        self._data = value

    @property
    def compressed(self):
        """The compressed host payload, if this Vec carries one."""
        return self._compressed

    @property
    def device_resident(self) -> bool:
        """True when a device array is materialized RIGHT NOW — the
        accounting view (never triggers decompress, unlike ``data``)."""
        return self._data is not None

    def drop_device(self) -> int:
        """Release the derived device array of a compressed Vec (the
        Cleaner's cheapest eviction: the host payload rebuilds it on next
        access). Returns the freed device bytes; 0 when there is nothing
        safely droppable."""
        if self._compressed is None or self._data is None:
            return 0
        freed = int(self._data.nbytes)
        self._data = None
        return freed

    @property
    def plen(self) -> int:
        return self._data.shape[0] if self._data is not None \
            else padded_len(self.nrows)

    @property
    def nbytes(self) -> int:
        """Resident bytes of this column: the padded device chunk plus any
        host-side payload (reference: summed ``Chunk`` byte sizes — the
        per-key accounting ``utils/memory.py`` registers with the DKV)."""
        from h2o3_tpu.utils.memory import vec_nbytes
        return vec_nbytes(self)

    @property
    def is_categorical(self) -> bool:
        return self.type is VecType.CAT

    @property
    def is_numeric(self) -> bool:
        return self.type.is_numeric

    def cardinality(self) -> int:
        """Number of categorical levels (reference: ``Vec.cardinality()``)."""
        return len(self.domain) if self.domain is not None else -1

    # -- rollups (lazy, cached; reference RollupStats semantics) ------------

    def rollups(self) -> Rollups:
        if self._rollups is None:
            # counted where it runs: the wall ends with the fetch and goes
            # under the phase that asked (else a build would be charged the
            # roll-ups of the frame's making); the roll-up program's own
            # first call, inside that wall, is booked under `frame:rollups`
            t0 = time.perf_counter()
            asked = PHASE.get() or OUTSIDE
            phase = PHASE.set("frame:rollups")
            try:
                if self.type is VecType.CAT:
                    kind = "cat"
                    self._rollups = cat_rollups(self.data, self.nrows)
                elif self.type.on_device:
                    kind = "numeric"
                    self._rollups = numeric_rollups(self.data, self.nrows)
                else:
                    kind = "other"
                    na = int(sum(v is None for v in self.host_values))
                    self._rollups = Rollups(self.nrows, na, float("nan"), float("nan"),
                                            float("nan"), float("nan"), 0, False, 0, 0)
            finally:
                PHASE.reset(phase)
            ROLLUPS.labels(kind=kind).inc()
            ROLLUP_SECONDS.labels(phase=asked).inc(time.perf_counter() - t0)
        return self._rollups

    def invalidate_rollups(self) -> None:
        """Call after mutating ``data`` (reference: rollup epoch bump)."""
        self._rollups = None
        self._hist_cache = None

    def min(self) -> float: return self.rollups().min
    def max(self) -> float: return self.rollups().max
    def mean(self) -> float: return self.rollups().mean
    def sigma(self) -> float: return self.rollups().sigma
    def na_cnt(self) -> int: return self.rollups().na_cnt
    def is_int(self) -> bool: return self.rollups().is_int

    # -- access -------------------------------------------------------------

    def to_numpy(self) -> np.ndarray:
        """Gather the logical (unpadded) column to host (TIME: exact f64 ms)."""
        if not self.type.on_device:
            return self.host_values
        if self.type is VecType.TIME and self.host_values is not None:
            return self.host_values[: self.nrows]
        if self._data is None and self._compressed is not None:
            # host read of an unmaterialized compressed column: decode
            # directly — no reason to round-trip through the device. COPY:
            # the identity codec decodes to the payload itself, and the
            # eager path's fetch() always returns a fresh array callers
            # may mutate — never alias the host source of truth
            return self._compressed.decode()[: self.nrows].copy()
        from h2o3_tpu.parallel.distributed import fetch
        return fetch(self.data)[: self.nrows]

    def labels(self) -> np.ndarray:
        """Categorical column as its level strings (NA → None); the view the
        h2o-py client renders for CAT columns (``as_data_frame``)."""
        if not self.is_categorical:
            raise ValueError("labels() requires a categorical Vec")
        codes = self.to_numpy()
        dom = np.array(self.domain, dtype=object)
        out = np.full(len(codes), None, dtype=object)
        ok = codes >= 0
        out[ok] = dom[codes[ok]]
        return out

    def as_float(self) -> jax.Array:
        """Device column as float32 with NaN for missing (cats → code floats)."""
        if self.type is VecType.CAT:
            return jnp.where(self.data < 0, jnp.nan, self.data.astype(jnp.float32))
        return self.data

    def __len__(self) -> int:
        return self.nrows

    def __repr__(self) -> str:
        dom = f", card={self.cardinality()}" if self.is_categorical else ""
        return f"Vec({self.type}, nrows={self.nrows}{dom})"

    # -- elementwise operators (reference: water/rapids/ast/prims/operators/) --
    #
    # Results are NUM Vecs; NA propagates through NaN arithmetic for free
    # (padding is NaN too, so padded slots stay invalid). Comparisons yield
    # 0.0/1.0 with NaN for NA operands, matching the reference's binary ops.

    def _operand(self, other):
        if isinstance(other, Vec):
            if other.nrows != self.nrows:
                raise ValueError("Vec length mismatch")
            o = other.as_float()
            # TIME device data is offset-relative (see __init__); align the
            # operand into THIS column's frame so differences/compares are
            # exact regardless of each column's own offset
            if self.type is VecType.TIME or other.type is VecType.TIME:
                o = o + (other.time_offset - self.time_offset)
            return o
        if isinstance(other, str):
            if not self.is_categorical:
                raise TypeError("string comparand requires a categorical Vec")
            try:
                return float(self.domain.index(other))
            except ValueError:
                return float("nan")   # unknown level: matches nothing
        o = float(other)
        if self.type is VecType.TIME:
            o = o - self.time_offset   # scalars are absolute epoch ms
        return o

    def _time_pair_host(self, other):
        """Both-TIME operand pair as exact float64 host ms, or None. A 25-year
        offset difference overflows the f32 relative representation, so
        TIME⋅TIME arithmetic runs on the exact host payload."""
        if (isinstance(other, Vec) and self.type is VecType.TIME
                and other.type is VecType.TIME
                and self.host_values is not None
                and other.host_values is not None):
            return (self.host_values[: self.nrows].astype(np.float64),
                    other.host_values[: other.nrows].astype(np.float64))
        return None

    def _ew(self, other, fn, swap: bool = False):
        pair = self._time_pair_host(other)
        if pair is not None:
            a, o = pair
            # numpy twin of the jnp ufunc: jnp would downcast the exact f64
            # epoch values to f32 (x64 is disabled)
            fn = getattr(np, getattr(fn, "__name__", ""), fn)
            out = np.asarray(fn(o, a) if swap else fn(a, o), np.float32)
            return Vec.from_numpy(out, type=VecType.NUM)
        o = self._operand(other)
        a = self.as_float()
        out = fn(o, a) if swap else fn(a, o)
        return Vec(out.astype(jnp.float32), VecType.NUM, self.nrows)

    def __add__(self, o): return self._ew(o, jnp.add)
    def __radd__(self, o): return self._ew(o, jnp.add)
    def __sub__(self, o): return self._ew(o, jnp.subtract)
    def __rsub__(self, o): return self._ew(o, jnp.subtract, swap=True)
    def __mul__(self, o): return self._ew(o, jnp.multiply)
    def __rmul__(self, o): return self._ew(o, jnp.multiply)
    def __truediv__(self, o): return self._ew(o, jnp.divide)
    def __rtruediv__(self, o): return self._ew(o, jnp.divide, swap=True)
    def __pow__(self, o): return self._ew(o, jnp.power)
    def __rpow__(self, o): return self._ew(o, jnp.power, swap=True)
    def __mod__(self, o): return self._ew(o, jnp.mod)
    def __rmod__(self, o): return self._ew(o, jnp.mod, swap=True)
    def __floordiv__(self, o): return self._ew(o, jnp.floor_divide)
    def __rfloordiv__(self, o): return self._ew(o, jnp.floor_divide, swap=True)
    def __neg__(self): return self._ew(-1.0, jnp.multiply)

    def _cmp(self, other, fn):
        pair = self._time_pair_host(other)
        if pair is not None:
            a, o = pair
            fn = getattr(np, getattr(fn, "__name__", ""), fn)   # keep f64 exact
            out = np.where(np.isnan(a) | np.isnan(o), np.nan,
                           np.asarray(fn(a, o), np.float32))
            return Vec.from_numpy(out.astype(np.float32), type=VecType.NUM)
        o = self._operand(other)
        a = self.as_float()
        valid = ~jnp.isnan(a)
        if isinstance(o, jax.Array):
            valid = valid & ~jnp.isnan(o)
        out = jnp.where(valid, fn(a, o).astype(jnp.float32), jnp.nan)
        return Vec(out, VecType.NUM, self.nrows)

    def __lt__(self, o): return self._cmp(o, jnp.less)
    def __le__(self, o): return self._cmp(o, jnp.less_equal)
    def __gt__(self, o): return self._cmp(o, jnp.greater)
    def __ge__(self, o): return self._cmp(o, jnp.greater_equal)
    def __eq__(self, o): return self._cmp(o, lambda a, b: a == b)
    def __ne__(self, o): return self._cmp(o, lambda a, b: a != b)
    __hash__ = object.__hash__   # __eq__ returns a Vec, not a bool

    def __and__(self, o): return self._cmp(o, lambda a, b: (a != 0) & (b != 0))
    def __or__(self, o): return self._cmp(o, lambda a, b: (a != 0) | (b != 0))
    def __invert__(self): return self._cmp(0.0, lambda a, b: a == b)

    def isna(self) -> "Vec":
        """1.0 where the value is missing (works on padded slots too — they
        read as NA but are excluded by the frame row mask downstream)."""
        return Vec(jnp.isnan(self.as_float()).astype(jnp.float32),
                   VecType.NUM, self.nrows)


def _guess_type(values: np.ndarray) -> VecType:
    values = np.asarray(values)
    if values.dtype.kind in "fc":
        finite = values[np.isfinite(values)]
        return VecType.INT if finite.size and np.all(finite == np.round(finite)) else VecType.NUM
    if values.dtype.kind in "iu":
        return VecType.INT
    if values.dtype.kind == "b":
        return VecType.INT
    if values.dtype.kind == "M":
        return VecType.TIME
    return VecType.CAT


def _factorize(values: np.ndarray) -> tuple[np.ndarray, list[str]]:
    """Categorical encoding with a lexicographically sorted domain.

    Reference: the parser sorts categorical domains (``water/parser`` packed
    domain merge), so codes are stable across chunk orderings.
    """
    arr = np.asarray(values, dtype=object)
    mask = np.array([v is None or (isinstance(v, (float, np.floating)) and np.isnan(v)) for v in arr],
                    dtype=bool)
    strs = np.array([str(v) for v in arr[~mask]])
    domain = sorted(set(strs.tolist()))
    lut = {s: i for i, s in enumerate(domain)}
    codes = np.full(len(arr), CAT_NA, dtype=np.int32)
    codes[~mask] = np.array([lut[s] for s in strs], dtype=np.int32)
    return codes, domain
