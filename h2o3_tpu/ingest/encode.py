"""Compressed chunk encodings — lossless narrow host payloads per column.

Reference: ``water/fvec/NewChunk.java:993-997`` — the reference parser picks
the cheapest of ~20 chunk codecs per 64KB fragment (``C1Chunk``/``C2SChunk``/
``C4Chunk`` narrow ints with bias, ``CXIChunk`` sparse, categorical domain
codes), and every read decompresses on access (``Chunk.atd``). That codec
zoo is why H2O-3's substrate survives datasets bigger than RAM (PAPER.md L2).

TPU-native subset: device compute wants dense float32/int32, so compression
lives HOST-side only. A :class:`CompressedChunk` is a column's resident host
payload in its cheapest **lossless** encoding:

- ``i8``/``i16`` — bias-shifted narrow ints for integral columns whose value
  range fits the width (the C1/C2-style codecs); NaN maps to the width's
  minimum as an NA sentinel, so round-trip is exact.
- ``dict8``/``dict16``/``dict32`` — dictionary codes for categoricals (the
  domain IS the dictionary; codes are narrowed to the cheapest width that
  holds the cardinality, with -1 = NA riding in every signed width).
- ``f32``/``i32`` — identity fallbacks when nothing narrower is lossless.

``decode()`` reproduces the exact float32 (or int32 code) array the eager
parse path would have produced — bit-identical model inputs are the
contract the ingest tests and ``bench_ingest`` hold.
"""

from __future__ import annotations

import numpy as np

from h2o3_tpu.frame.types import CAT_NA

#: widths tried for integral numeric columns, cheapest first; each reserves
#: its dtype's minimum as the NA sentinel so the usable range is one short
_INT_WIDTHS = ((np.int8, 1), (np.int16, 2))

#: widths tried for categorical code columns (codes are >= -1 = CAT_NA,
#: which every signed width represents natively)
_DICT_WIDTHS = ((np.int8, "dict8"), (np.int16, "dict16"))


class CompressedChunk:
    """One column's host payload in its cheapest lossless encoding.

    ``payload`` is the narrow numpy array; ``codec`` names the encoding;
    ``bias`` shifts narrow-int payloads back to the original values.
    """

    __slots__ = ("codec", "payload", "bias", "raw_bytes")

    def __init__(self, codec: str, payload: np.ndarray, bias: float = 0.0,
                 raw_bytes: int | None = None):
        self.codec = codec
        self.payload = payload
        self.bias = float(bias)
        # what the uncompressed (float32/int32) column would have occupied —
        # the numerator of the compression ratio
        self.raw_bytes = int(raw_bytes if raw_bytes is not None
                             else len(payload) * 4)

    @property
    def nbytes(self) -> int:
        return int(self.payload.nbytes)

    @property
    def nrows(self) -> int:
        return int(len(self.payload))

    def decode(self) -> np.ndarray:
        """The exact array the eager path would hold: float32 with NaN for
        numeric codecs, int32 codes (CAT_NA for missing) for dict codecs."""
        p = self.payload
        if self.codec == "f32":
            return p
        if self.codec == "i32":
            return p.astype(np.float32)
        if self.codec.startswith("dict"):
            return p.astype(np.int32)
        # narrow int with bias: the dtype minimum is the NA sentinel
        sentinel = np.iinfo(p.dtype).min
        out = p.astype(np.float32) + np.float32(self.bias)
        out[p == sentinel] = np.nan
        return out

    def __repr__(self) -> str:
        return (f"CompressedChunk({self.codec}, n={self.nrows}, "
                f"{self.nbytes}B/{self.raw_bytes}B)")


def encode_numeric(values: np.ndarray) -> CompressedChunk:
    """Encode a float32 numeric column (NaN = missing) losslessly.

    Narrow-int widths apply only when every finite value is integral AND
    exactly representable in float32 after the bias shift — otherwise the
    identity ``f32`` codec keeps the column as-is."""
    v = np.asarray(values, dtype=np.float32)
    finite = v[np.isfinite(v)]
    if finite.size and np.all(finite == np.round(finite)):
        lo = float(finite.min())
        hi = float(finite.max())
        for dtype, _width in _INT_WIDTHS:
            info = np.iinfo(dtype)
            # reserve info.min for NA; bias at the column minimum so the
            # span (not the magnitude) decides the width
            if hi - lo <= info.max - (info.min + 1):
                sentinel = info.min
                shifted = np.full(v.shape, sentinel, dtype=dtype)
                ok = np.isfinite(v)
                shifted[ok] = (v[ok] - np.float32(lo)).astype(np.int64) \
                    + (sentinel + 1)
                chunk = CompressedChunk(f"i{np.dtype(dtype).itemsize * 8}",
                                        shifted,
                                        bias=lo - (sentinel + 1),
                                        raw_bytes=v.nbytes)
                # paranoid losslessness check on the chunk boundary values:
                # float32 cannot represent every int past 2**24, in which
                # case the identity codec is the only exact one
                if np.array_equal(chunk.decode(), v, equal_nan=True):
                    return chunk
    return CompressedChunk("f32", v, raw_bytes=v.nbytes)


def encode_codes(codes: np.ndarray, cardinality: int) -> CompressedChunk:
    """Dictionary-code a categorical column: codes are already the
    dictionary indices (the Vec's domain is the dictionary); narrow them to
    the cheapest width holding ``cardinality`` (CAT_NA = -1 fits every
    signed width)."""
    c = np.asarray(codes, dtype=np.int32)
    for dtype, codec in _DICT_WIDTHS:
        if cardinality - 1 <= np.iinfo(dtype).max:
            return CompressedChunk(codec, c.astype(dtype), raw_bytes=c.nbytes)
    return CompressedChunk("dict32", c, raw_bytes=c.nbytes)


def encode_column(values: np.ndarray, is_categorical: bool = False,
                  cardinality: int = 0) -> CompressedChunk:
    """Encode one parsed column chunk (float32 numerics or int32 codes)."""
    if is_categorical:
        return encode_codes(values, cardinality)
    return encode_numeric(values)


def concat_chunks(chunks: list[CompressedChunk],
                  is_categorical: bool = False,
                  cardinality: int = 0) -> CompressedChunk:
    """Fuse per-chunk encodings of one column into a single column-spanning
    chunk, re-encoded so the fused payload is as narrow as the fused value
    range allows (two chunks may each fit i8 under different biases)."""
    if len(chunks) == 1 and not is_categorical:
        return chunks[0]
    decoded = np.concatenate([c.decode() for c in chunks]) if chunks \
        else np.empty(0, np.float32)
    return encode_column(decoded, is_categorical=is_categorical,
                         cardinality=cardinality)
