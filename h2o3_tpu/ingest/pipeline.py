"""Streaming chunked parse — read → decompress → tokenize → device stages.

Reference: the 2-phase distributed parse (``water/parser/ParseDataset.java``:
a ParseSetup type/header guess pass, then an MRTask over raw file chunks with
per-chunk CSV state machines). The all-at-once path (``frame/parse.py``)
reads the whole file, materializes full host columns, and uploads once —
host peak is O(file). This module replaces that for large/compressed inputs
with the overlapped input-pipeline design (TensorFlow's prefetch/stage
decoupling, PAPERS.md): four stages connected by small bounded queues,

    read (raw byte blocks)
      → decompress (incremental gzip, line re-assembly, fixed-row batching)
      → tokenize/columnarize + encode (CSV → typed columns → CompressedChunk)
      → assemble/device_put (fuse chunks into Vecs; upload or stay lazy)

so host peak transient memory is O(chunk), not O(file) — the only O(file)
residency is the *compressed* column payloads the Frame keeps (and the
Cleaner can spill those; utils/cleaner.py). Every queue wait is bounded
with an abort-flag recheck (graftlint WTX001): a died stage can never park
its neighbours.

Type inference runs on the first chunk (the ParseSetup sample); a later
chunk that breaks a column's numeric guess raises a promote-and-reparse
restart with that column forced categorical — bounded by ncols restarts,
exactly the reference's setup-vs-parse split collapsed into a retry.
"""

from __future__ import annotations

import io
import os
import queue
import threading
import zlib

import numpy as np

from h2o3_tpu.frame.types import CAT_NA, VecType
from h2o3_tpu.ingest.encode import CompressedChunk, encode_codes, encode_numeric
from h2o3_tpu.utils import telemetry as _tm

#: raw-read block size (bytes) — the unit the read stage hands downstream
_READ_BLOCK = 1 << 20

#: bounded-queue poll period; every wait rechecks the abort flag at this
#: cadence so a dead neighbour stage can never park a thread forever
_POLL_S = 0.2

_EOF = object()


class ParsePromoted(Exception):
    """A chunk past the sample broke one or more columns' numeric guesses
    — reparse with those columns forced categorical (internal control
    flow). Carries EVERY failing column of the offending chunk so k
    simultaneous breaks cost one restart, not k."""

    def __init__(self, columns: list[str]):
        super().__init__(", ".join(columns))
        self.columns = list(columns)


class _Aborted(Exception):
    """A sibling stage failed; unwind quietly (its error is the real one)."""


class IngestStats:
    """One streaming parse's accounting — rides into the
    ``h2o3_ingest_*`` metrics."""

    def __init__(self):
        self.rows = 0
        self.chunks = 0
        self.bytes_in = 0            # decompressed source bytes consumed
        self.bytes_raw = 0           # what eager float32/int32 columns would hold
        self.bytes_encoded = 0       # compressed host payload bytes
        self.restarts = 0
        self.inflight_peak = 0       # high-water of bytes queued between stages
        self._inflight = 0
        self._lock = threading.Lock()

    def grow(self, n: int) -> None:
        with self._lock:
            self._inflight += n
            if self._inflight > self.inflight_peak:
                self.inflight_peak = self._inflight

    def shrink(self, n: int) -> None:
        with self._lock:
            self._inflight -= n

    @property
    def compression_ratio(self) -> float:
        return (self.bytes_raw / self.bytes_encoded) if self.bytes_encoded \
            else 1.0

    def as_dict(self) -> dict:
        return {"rows": self.rows, "chunks": self.chunks,
                "bytes_in": self.bytes_in, "bytes_raw": self.bytes_raw,
                "bytes_encoded": self.bytes_encoded,
                "compression_ratio": round(self.compression_ratio, 3),
                "restarts": self.restarts,
                "inflight_peak_bytes": self.inflight_peak}


def chunk_rows_default() -> int:
    return int(os.environ.get("H2O3TPU_INGEST_CHUNK_ROWS", str(1 << 16)))


def queue_depth_default() -> int:
    return int(os.environ.get("H2O3TPU_INGEST_QUEUE", "4"))


# ---------------------------------------------------------------------------
# bounded-queue plumbing (WTX001-clean: every wait polls the abort flag)


def _q_put(q: "queue.Queue", item, abort: threading.Event) -> None:
    while True:
        if abort.is_set():
            raise _Aborted()
        try:
            q.put(item, timeout=_POLL_S)
            return
        except queue.Full:
            continue


def _q_get(q: "queue.Queue", abort: threading.Event):
    while True:
        if abort.is_set():
            raise _Aborted()
        try:
            return q.get(timeout=_POLL_S)
        except queue.Empty:
            continue


def _split_records(data: bytes, in_quote: bool):
    """Split ``data`` on newlines that are OUTSIDE double-quoted fields
    (RFC-4180: a quoted field may contain embedded newlines; `""` escapes
    toggle parity twice and fall out naturally). Vectorized over the block
    — a Python char loop on 1MB blocks would dominate the stage. Returns
    (records, remainder, in_quote) where ``in_quote`` is the state at the
    START of the remainder (the caller re-scans the remainder next round;
    a cut newline sits at quote depth 0, so any cut resets it)."""
    arr = np.frombuffer(data, np.uint8)
    parity = (np.cumsum(arr == ord('"')) & 1).astype(bool)
    if in_quote:
        parity = ~parity
    cuts = np.flatnonzero((arr == ord("\n")) & ~parity)
    records = []
    start = 0
    for c in cuts.tolist():
        records.append(data[start:c])
        start = c + 1
    return records, data[start:], in_quote if not len(cuts) else False


class _Stage(threading.Thread):
    """One pipeline stage: runs ``fn``, records its error, trips the shared
    abort flag so every sibling unwinds within one poll period."""

    def __init__(self, name: str, fn, abort: threading.Event):
        super().__init__(name=f"ingest-{name}", daemon=True)
        self._fn = fn
        self._abort = abort
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            self._fn()
        except _Aborted:
            pass
        except BaseException as e:   # noqa: BLE001 — carried to the driver
            self.error = e
            self._abort.set()


# ---------------------------------------------------------------------------
# stage bodies


def _read_stage(path: str, out_q, abort, progress) -> None:
    """Raw byte blocks off disk — never the whole file (graftlint ING001).
    ``progress`` is fed the raw (on-disk) byte offset for Job accounting."""
    with open(path, "rb") as fh:
        while True:
            block = fh.read(_READ_BLOCK)
            progress["raw_pos"] = fh.tell()
            if not block:
                break
            _q_put(out_q, block, abort)
    _q_put(out_q, _EOF, abort)


def _decompress_stage(in_q, out_q, abort, gzipped: bool, chunk_rows: int,
                      stats: IngestStats, has_header: bool) -> None:
    """Incremental gunzip + line re-assembly + fixed-row-count batching.

    Emits ``("header", line)`` once (when the file has one), then
    ``("lines", [line, ...])`` batches of exactly ``chunk_rows`` rows
    (except the tail). Holds at most one partial line + one open batch —
    O(chunk) regardless of file size."""
    dec = zlib.decompressobj(wbits=47) if gzipped else None   # gzip|zlib hdr
    tail = b""
    in_quote = False

    def gunzip(block: bytes) -> bytes:
        """Incremental decompress across MEMBER boundaries: concatenated
        gzip members (pigz, log rotation, `cat a.gz b.gz`) are one valid
        stream, but a decompressobj stops at its member's end — restart on
        ``unused_data`` or every member after the first silently drops."""
        nonlocal dec
        out = b""
        while block:
            out += dec.decompress(block)
            if not dec.eof:
                break
            block = dec.unused_data
            dec = zlib.decompressobj(wbits=47)
        return out
    batch: list[bytes] = []
    header_sent = not has_header

    def flush_batch():
        nonlocal batch
        if batch:
            nb = sum(len(ln) for ln in batch)
            stats.grow(nb)
            _q_put(out_q, ("lines", batch, nb), abort)
            batch = []

    while True:
        block = _q_get(in_q, abort)
        if block is _EOF:
            if dec is not None:
                tail += dec.flush()
            break
        if dec is not None:
            block = gunzip(block)
        stats.bytes_in += len(block)
        lines, tail, in_quote = _split_records(tail + block, in_quote)
        for ln in lines:
            if ln.endswith(b"\r"):
                ln = ln[:-1]
            if not ln:
                continue
            if not header_sent:
                header_sent = True
                _q_put(out_q, ("header", ln), abort)
                continue
            batch.append(ln)
            if len(batch) >= chunk_rows:
                flush_batch()
    if tail.strip():
        ln = tail[:-1] if tail.endswith(b"\r") else tail
        if not header_sent:
            _q_put(out_q, ("header", ln), abort)
        else:
            batch.append(ln)
    flush_batch()
    _q_put(out_q, _EOF, abort)


class _ColumnState:
    """One column's accumulated encoded chunks + (for categoricals) the
    insertion-order dictionary built across chunks."""

    def __init__(self, name: str, forced: "VecType | None"):
        self.name = name
        self.forced = forced
        self.kind: str | None = \
            "cat" if forced is VecType.CAT else \
            "num" if forced in (VecType.NUM, VecType.INT) else None
        self.chunks: list[CompressedChunk] = []
        self.lut: dict[str, int] = {}        # categorical level -> raw code
        # INT-vs-NUM typing mirrors the eager _guess_type contract (some
        # finite values, all integral) — NOT the achieved codec, which
        # falls back to f32 for integral spans wider than i16
        self.integral = True
        self.has_finite = False


def _tokenize_stage(in_q, out_q, abort, sep: str, na_strings, forced: dict,
                    columns: list[_ColumnState], stats: IngestStats) -> None:
    """CSV lines → typed per-column arrays → CompressedChunks.

    The first batch is the ParseSetup sample: undeclared columns guess
    numeric-vs-categorical from it. A later batch whose numeric column
    holds an unparseable token raises :class:`ParsePromoted` — the driver
    restarts the whole parse with that column forced categorical."""
    import pandas as pd

    def parse_batch(lines: list[bytes], na_filter: bool = True):
        # same dialect as the eager pd.read_csv path (no skipinitialspace):
        # a file must produce identical names/domains whichever path routes
        buf = io.BytesIO(b"\n".join(lines))
        if not na_filter:   # header parse: a column named "NA" stays "NA"
            return pd.read_csv(buf, header=None, sep=sep, dtype=str,
                               na_filter=False)
        return pd.read_csv(buf, header=None, sep=sep, dtype=str,
                           na_values=na_strings, keep_default_na=True)

    while True:
        item = _q_get(in_q, abort)
        if item is _EOF:
            break
        if item[0] == "header":
            # parse the header line with the SAME csv reader as the data
            # (quoted names containing the separator split correctly) but
            # WITHOUT NA filtering — a column literally named "NA" keeps
            # its name, matching the eager path
            hdr = parse_batch([item[1]], na_filter=False)
            names = [str(v) if v is not None and v == v else ""
                     for v in hdr.iloc[0].tolist()]
            seen: dict[str, int] = {}
            for i, n in enumerate(names):
                n = n or f"C{i + 1}"
                if n in seen:   # pandas-style dedup: x, x.1, x.2 ...
                    seen[n] += 1
                    n = f"{n}.{seen[n]}"
                seen.setdefault(n, 0)
                columns.append(_ColumnState(n, forced.get(n)))
            continue
        _tag, lines, nb = item
        df = parse_batch(lines)
        if not columns:            # headerless file: C1..Cn on first batch
            for i in range(df.shape[1]):
                columns.append(_ColumnState(f"C{i + 1}",
                                            forced.get(f"C{i + 1}")))
        if df.shape[1] != len(columns):
            raise ValueError(
                f"row has {df.shape[1]} fields, header declares "
                f"{len(columns)} (chunk of {len(lines)} rows)")
        enc_bytes = 0
        promote: list[str] = []
        for j, col in enumerate(columns):
            s = df.iloc[:, j]
            nums = pd.to_numeric(s, errors="coerce")
            if col.kind is None:
                # the sample decides: any token that is non-NA yet
                # non-numeric makes the column categorical
                bad = nums.isna() & s.notna()
                col.kind = "cat" if bool(bad.any()) else "num"
            if col.kind == "num":
                bad = nums.isna() & s.notna()
                if bool(bad.any()) and col.forced is None:
                    # only a GUESSED numeric promotes; a user-forced
                    # numeric column treats bad tokens as NA (h2o-py
                    # col_types semantics), which the coerce already did.
                    # Keep scanning: every column this chunk breaks rides
                    # ONE restart
                    promote.append(col.name)
                    continue
                host = nums.to_numpy(np.float32)
                finite = host[np.isfinite(host)]
                if finite.size:
                    col.has_finite = True
                    if not np.all(finite == np.round(finite)):
                        col.integral = False
                chunk = encode_numeric(host)
            else:
                # vectorized dictionary build: factorize the chunk (C
                # loop), then extend the cross-chunk dictionary only over
                # this chunk's O(cardinality) distinct levels
                local, uniques = pd.factorize(s)
                lut = col.lut
                if len(uniques):
                    mapping = np.array(
                        [lut.setdefault(str(u), len(lut)) for u in uniques],
                        dtype=np.int32)
                    codes = np.where(
                        local >= 0, mapping[np.clip(local, 0, None)],
                        np.int32(CAT_NA)).astype(np.int32)
                else:                      # all-NA chunk
                    codes = np.full(len(s), CAT_NA, dtype=np.int32)
                chunk = encode_codes(codes, len(lut))
            col.chunks.append(chunk)
            enc_bytes += chunk.nbytes
            stats.bytes_raw += chunk.raw_bytes
        if promote:
            raise ParsePromoted(promote)
        stats.bytes_encoded += enc_bytes
        stats.rows += df.shape[0]
        stats.chunks += 1
        stats.shrink(nb)
        _q_put(out_q, ("chunk", df.shape[0]), abort)
    _q_put(out_q, _EOF, abort)


# ---------------------------------------------------------------------------
# driver


def _assemble(columns: list[_ColumnState], nrows: int, lazy: bool):
    """Fuse each column's chunk list into one Vec. Categorical dictionaries
    are re-sorted to the parser contract (lexicographic domains) with a
    chunk-by-chunk code remap — never more than one decoded column live."""
    from h2o3_tpu.frame.vec import Vec
    from h2o3_tpu.ingest.encode import concat_chunks
    vecs = []
    for col in columns:
        if col.kind == "cat":
            domain = sorted(col.lut)
            rank = {lvl: i for i, lvl in enumerate(domain)}
            perm = np.full(max(len(col.lut), 1), CAT_NA, dtype=np.int32)
            for lvl, raw in col.lut.items():
                perm[raw] = rank[lvl]
            remapped = []
            for ch in col.chunks:
                codes = ch.decode()
                ok = codes >= 0
                codes[ok] = perm[codes[ok]]
                remapped.append(encode_codes(codes, len(domain)))
            fused = concat_chunks(remapped, is_categorical=True,
                                  cardinality=len(domain))
            vecs.append(Vec.from_compressed(fused, VecType.CAT, nrows,
                                            domain=tuple(domain)))
        else:
            fused = concat_chunks(col.chunks)
            # the eager _guess_type contract, not the achieved codec:
            # a wide integral span falls back to the f32 codec yet is
            # still an INT column
            vtype = VecType.INT if (col.has_finite and col.integral) \
                else VecType.NUM
            vecs.append(Vec.from_compressed(fused, vtype, nrows))
        col.chunks = []            # the fused chunk owns the payload now
    if not lazy:
        for v in vecs:
            _ = v.data      # materialize (per column — never O(file) host)
    return vecs


def stream_import(path: str, key: str | None = None, header: int | None = 0,
                  col_types: dict | None = None,
                  na_strings: list | None = None, sep: str | None = None,
                  chunk_rows: int | None = None, lazy: bool | None = None,
                  job=None):
    """Streaming chunked CSV parse → Frame with compressed host columns.

    ``job`` (a :class:`~h2o3_tpu.models.job.Job`) receives row/byte progress
    per chunk; cancelling it aborts every stage within one poll period."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.utils.registry import DKV

    sep = sep or ","
    chunk_rows = chunk_rows or chunk_rows_default()
    if lazy is None:
        lazy = os.environ.get("H2O3TPU_INGEST_EAGER", "0") != "1"
    from h2o3_tpu.frame.binfmt import is_gzipped
    gzipped = is_gzipped(path)       # magic bytes, never the extension
    total_bytes = os.path.getsize(path)
    na = list(na_strings) if na_strings else None
    # normalize h2o-py style col_types ("enum"/"numeric") to VecType
    forced: dict[str, VecType] = {}
    for cname, t in (col_types or {}).items():
        if isinstance(t, VecType):
            forced[cname] = t
        elif str(t).lower() in ("enum", "cat", "categorical", "factor",
                                "string"):
            forced[cname] = VecType.CAT
        else:
            forced[cname] = VecType.NUM
    stats = IngestStats()

    # promote-and-reparse is bounded by the column count (each restart
    # forces at least one NEW column categorical); the width is known only
    # after a pass has seen the header, so the bound is re-derived per
    # attempt with 64 as the pre-header floor
    restarts = 0
    while True:
        abort = threading.Event()
        depth = queue_depth_default()
        raw_q: queue.Queue = queue.Queue(maxsize=depth)
        line_q: queue.Queue = queue.Queue(maxsize=depth)
        done_q: queue.Queue = queue.Queue(maxsize=depth)
        columns: list[_ColumnState] = []
        progress = {"raw_pos": 0}
        stages = [
            _Stage("read", lambda: _read_stage(path, raw_q, abort, progress),
                   abort),
            _Stage("decompress",
                   lambda: _decompress_stage(raw_q, line_q, abort, gzipped,
                                             chunk_rows, stats,
                                             has_header=header is not None
                                             and header >= 0),
                   abort),
            _Stage("tokenize",
                   lambda: _tokenize_stage(line_q, done_q, abort, sep, na,
                                           forced, columns, stats),
                   abort),
        ]
        for s in stages:
            s.start()
        nrows = 0
        try:
            while True:
                item = _q_get(done_q, abort)
                if item is _EOF:
                    break
                nrows += item[1]
                if job is not None:
                    frac = min(progress["raw_pos"] / total_bytes, 1.0) \
                        if total_bytes else 1.0
                    job.update(0.95 * frac,
                               f"parsed {nrows} rows / "
                               f"{stats.bytes_in} bytes")
        except _Aborted:
            pass
        except BaseException:
            abort.set()
            raise
        finally:
            for s in stages:
                s.join(timeout=30.0)
        err = next((s.error for s in stages if s.error is not None), None)
        if isinstance(err, ParsePromoted):
            restarts += 1
            if restarts > max(64, len(columns)):
                raise ValueError(
                    f"parse of {path!r} exceeded {max(64, len(columns))} "
                    "type-promotion restarts")
            for cname in err.columns:
                forced[cname] = VecType.CAT
            stats.restarts += 1
            _tm.INGEST_RESTARTS.inc()
            # rewind the accounting the aborted pass accumulated (queued
            # items die with their stages, so in-flight resets too)
            stats.rows = stats.chunks = 0
            stats.bytes_in = stats.bytes_raw = stats.bytes_encoded = 0
            with stats._lock:
                stats._inflight = 0
            continue
        if err is not None:
            raise err
        break

    # throughput counters land ONCE per successful parse — per-chunk
    # increments would double-count every promote-and-reparse restart
    _tm.INGEST_CHUNKS.inc(stats.chunks)
    _tm.INGEST_ROWS.inc(stats.rows)
    _tm.INGEST_BYTES.inc(stats.bytes_in)
    _tm.INGEST_ENCODED_BYTES.inc(stats.bytes_encoded)
    vecs = _assemble(columns, nrows, lazy)
    fr = Frame([c.name for c in columns], vecs,
               key=key)
    fr._ingest_stats = stats.as_dict()
    if job is not None:
        job.update(1.0, f"parsed {nrows} rows / {stats.bytes_in} bytes")
    if key:
        DKV.put(key, fr)
    return fr
