"""Chunked columnar table with dictionary-encoded string columns.

Reference analog: server/libs/ckdb (table DDL + batched columnar inserts into
ClickHouse). Embedded design: each table holds a list of immutable chunks
(dict column-name -> np.ndarray); writers buffer rows and seal chunks; readers
snapshot the chunk list — single-writer / many-reader without locks on the
read path.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from deepflow_tpu.native import ArenaStrings
from deepflow_tpu.store.dictionary import Dictionary
from deepflow_tpu.store.segment import next_serial

_DTYPES = {
    "u8": np.uint8, "u16": np.uint16, "u32": np.uint32, "u64": np.uint64,
    "i8": np.int8, "i16": np.int16, "i32": np.int32, "i64": np.int64,
    "f32": np.float32, "f64": np.float64,
    "str": np.uint32,   # dictionary-encoded
    "enum": np.uint16,  # fixed enum mapping provided in spec
}


@dataclass(frozen=True)
class ColumnSpec:
    name: str
    kind: str                      # key of _DTYPES
    enum_values: tuple[str, ...] = ()  # for kind == "enum": index -> label
    default: object = 0

    @property
    def np_dtype(self):
        return _DTYPES[self.kind]

    def enum_of(self, label: str) -> int:
        return self.enum_values.index(label)


class _Stripe:
    """One writer thread's private buffer: per column, a list of SEGMENTS —
    python lists (converted at seal) or typed ndarrays (pass straight
    through); segment buffering lets the columnar ingest path hand over
    numpy arrays without a tolist/extend/asarray round trip."""

    __slots__ = ("lock", "buf", "rows", "seq", "mat")

    def __init__(self, names) -> None:
        self.lock = threading.Lock()
        self.buf: dict[str, list] = {n: [] for n in names}
        self.rows = 0
        # snapshot memo: (seq at materialization, chunk dict). seq is a
        # monotonic mutation counter — rows alone can repeat across a
        # seal/refill cycle and would validate a stale memo.
        self.seq = 0
        self.mat: tuple[int, dict] | None = None


class SealedChunk(dict):
    """A chunk whose rows never change again: {column -> ndarray} plus a
    serial no other chunk gets and the time extent (tmin, tmax; None
    without a time column), both fixed when it is built. A rebuilt
    chunk (flush merge, dictionary remap, load) is a new SealedChunk."""

    __slots__ = ("serial", "tmin", "tmax")

    def __init__(self, cols: dict, time_col: str | None) -> None:
        super().__init__(cols)
        self.serial = next_serial()
        t = cols.get(time_col) if time_col else None
        if t is not None and len(t) and t.dtype.kind in "iu":
            self.tmin, self.tmax = int(t.min()), int(t.max())
        else:
            self.tmin = self.tmax = None


class ColumnarTable:
    """Append-only columnar table; chunked; per-str-column dictionaries.

    Write path is STRIPED: each writer thread buffers into its own stripe
    (dictionary encodes happen outside any table lock — Dictionary is
    internally thread-safe) and only touches the shared state to bump the
    row counter and, at chunk boundaries, to seal its stripe into the
    shared chunk list. N ingest workers therefore append concurrently
    instead of serializing on one table lock; the single-writer/many-reader
    snapshot contract is kept because readers snapshot chunks + stripe
    buffers under the stripe locks. Row order across stripes is not
    guaranteed (matches the decoder workers contract).

    Lock order (deadlock-free): stripe lock(s) BEFORE self._lock, always;
    multi-stripe holders (snapshot/flush/compact) acquire stripe locks in a
    stable sort order."""

    def __init__(self, name: str, columns: list[ColumnSpec],
                 chunk_rows: int = 1 << 16) -> None:
        self.name = name
        self.columns = {c.name: c for c in columns}
        self.chunk_rows = chunk_rows
        self.dicts: dict[str, Dictionary] = {
            c.name: Dictionary(f"{name}.{c.name}")
            for c in columns if c.kind == "str"}
        self._chunks: list[dict[str, np.ndarray]] = []
        # on-disk tier (store/tiered.py TableTier), attached by
        # Database when persistent storage is enabled. Tier chunks are
        # mmap-backed and come FIRST in snapshot() (they are the oldest
        # rows); _pending_flush holds merged chunks staged for a tier
        # commit — still served from RAM until confirm_flush() so no
        # snapshot ever misses rows mid-flush.
        self.tier = None
        self._pending_flush: list[dict[str, np.ndarray]] = []
        self._stripes: dict[int, _Stripe] = {}  # thread id -> stripe
        self._lock = threading.Lock()  # guards _chunks, rows_written,
        # dicts swap (compaction) and stripe creation
        self.rows_written = 0
        self.dict_ns = 0  # ns spent dictionary-encoding (bench stage stat)
        # per-table fill overrides: the value a column takes when a write
        # omits it (and when load() backfills chunks persisted before the
        # column existed), instead of the schema default. Set once at
        # wiring time — e.g. Database(shard_id=N) stamps every row this
        # node ingests with its cluster shard identity.
        self.fills: dict[str, object] = {}
        # Write watermark: monotonic counter bumped on every mutation that
        # can change a query answer (append, trim, load). Query caches key
        # on it for exact invalidation (query/cache.py). Alongside it, a
        # per-TIME-BUCKET mark map (bucket index -> watermark at last write
        # into that bucket) lets the partial-aggregate cache re-scan only
        # the buckets that actually changed. _wide_mark is the fallback for
        # writes spanning too many buckets to mark individually — any
        # bucket's effective mark is max(bucket mark, _wide_mark).
        self.watermark = 0
        self._bucket_marks: dict[int, int] = {}
        self._wide_mark = 0
        self._time_col = "time" if any(c.name == "time" for c in columns) \
            else None
        # change listeners (query/standing.py): called OUTSIDE all table
        # locks after any mutation that moves the watermark. Listeners
        # must be cheap and non-blocking (they mark dirty + set an
        # event); heavy work happens on the subscriber's own thread.
        self._listeners: list = []
        # bucket width in the time column's native unit (ns for u64, s
        # otherwise); 60 s buckets match dashboard refresh granularity
        if self._time_col is not None:
            ns = self.columns[self._time_col].kind == "u64"
            self._bucket_div = 60 * 1_000_000_000 if ns else 60
        else:
            self._bucket_div = 0

    def _fill(self, name: str, spec: ColumnSpec):
        return self.fills.get(name, spec.default)

    # -- change tracking (query-cache invalidation) --------------------------

    def _note_span(self, tmin: int, tmax: int) -> None:
        """Mark the time buckets covered by [tmin, tmax] with the current
        watermark. Caller holds self._lock (watermark already bumped)."""
        if not self._bucket_div:
            return
        b0, b1 = int(tmin) // self._bucket_div, int(tmax) // self._bucket_div
        if b1 - b0 >= 512:  # absurd span (poisoned clock): invalidate all
            self._wide_mark = self.watermark
            return
        for b in range(b0, b1 + 1):
            self._bucket_marks[b] = self.watermark

    def _note_segment(self, seg) -> None:
        """Watermark bump + bucket marking for one appended time segment.
        Caller holds self._lock."""
        self.watermark += 1
        if not self._bucket_div or seg is None:
            return
        try:
            if isinstance(seg, np.ndarray):
                if not len(seg):
                    return
                self._note_span(int(seg.min()), int(seg.max()))
            elif seg:
                self._note_span(int(min(seg)), int(max(seg)))
        except (TypeError, ValueError, OverflowError):
            self._wide_mark = self.watermark  # unparseable time: play safe

    def add_listener(self, fn) -> None:
        """Register a change callback: fn(table) fires after any mutation
        that can change a query answer (append, flush commit, tier
        publish/evict/compact, trim, load). Fired outside all table
        locks; exceptions are swallowed (a broken listener must not
        poison the write path)."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners = self._listeners + [fn]

    def remove_listener(self, fn) -> None:
        with self._lock:
            self._listeners = [f for f in self._listeners if f is not fn]

    def _notify(self) -> None:
        for fn in self._listeners:  # list is swapped, never mutated
            try:
                fn(self)
            except Exception:  # pragma: no cover - defensive
                pass

    def bucket_marks(self) -> tuple[int, dict[int, int], int, int]:
        """(watermark, {bucket: mark}, wide_mark, bucket_div) snapshot."""
        with self._lock:
            return (self.watermark, dict(self._bucket_marks),
                    self._wide_mark, self._bucket_div)

    def sync_state(self) -> list:
        """JSON-able change token: [watermark, [[dict name, gen, len], ...]].
        Two equal tokens guarantee byte-identical query answers AND that
        previously shipped dictionary ids are still valid (dictionary
        VERSION is implied: dict growth requires a table write, which bumps
        the watermark)."""
        dicts = sorted((n, *d.sync_state()[:2]) for n, d in self.dicts.items())
        return [self.watermark, [list(t) for t in dicts]]

    # -- write path ----------------------------------------------------------

    def _stripe(self) -> _Stripe:
        tid = threading.get_ident()
        s = self._stripes.get(tid)
        if s is None:
            with self._lock:
                s = self._stripes.get(tid)
                if s is None:
                    s = self._stripes[tid] = _Stripe(self.columns)
        return s

    def _all_stripes(self) -> list[_Stripe]:
        """Stable acquisition order for multi-stripe holders."""
        return sorted(self._stripes.values(), key=id)

    def _encode_str_segment(self, name: str, v, n: int):
        """Dictionary-encode one str column value (scalar or per-row) into
        a buffer segment. Returns (dictionary used, segment) — the caller
        re-encodes if a compaction swapped the dictionary in between."""
        d = self.dicts[name]
        t0 = time.perf_counter_ns()
        if isinstance(v, ArenaStrings):
            # native decoder output: intern (arena, off, len) cells in C++
            # without materializing Python strings
            seg = d.encode_arena(v.arena, v.off, v.lens)
            if seg is None:  # native unavailable / mirror retired
                seg = d.encode_batch(v.tolist())
        elif isinstance(v, (list, np.ndarray)):
            seg = d.encode_batch(v)
        else:
            seg = np.full(n, d.encode(v), dtype=np.uint32)
        # bench stat (per-stage ingest breakdown); plain add — a lost
        # update under contention skews a counter, not data
        self.dict_ns += time.perf_counter_ns() - t0
        return d, seg

    def append_rows(self, rows: list[dict]) -> None:
        """Append a batch of row dicts. Missing columns take the default."""
        if not rows:
            return
        segs: dict[str, object] = {}
        str_raw: dict[str, tuple] = {}
        for name, spec in self.columns.items():
            if spec.kind == "str":
                dflt_s = self.fills.get(name, "")
                raw = [r.get(name, dflt_s) for r in rows]
                d, segs[name] = self._encode_str_segment(name, raw,
                                                         len(rows))
                str_raw[name] = (d, raw)
            else:
                dflt = self._fill(name, spec)
                segs[name] = [r.get(name, dflt) for r in rows]
        self._append_segments(segs, len(rows), str_raw)

    def append_columns(self, cols: dict[str, list | np.ndarray],
                       n: int | None = None) -> None:
        """Column-oriented append (fast path for decoders).

        A column value may be a SCALAR (str/int/float), meaning "this value
        for every row in the batch" — constant columns (per-agent universal
        tags) then cost one dictionary encode + one list multiply instead of
        n per-cell encodes."""
        if n is None:
            n = len(next(iter(cols.values())))
        for name, v in cols.items():
            if isinstance(v, (list, np.ndarray, ArenaStrings)) and len(v) != n:
                raise ValueError(
                    f"{self.name}: column {name!r} has {len(v)} values, "
                    f"expected {n}")
        if n == 0:
            return
        segs: dict[str, object] = {}
        str_raw: dict[str, tuple] = {}
        for name, spec in self.columns.items():
            if name in cols:
                v = cols[name]
                if spec.kind == "str":
                    d, segs[name] = self._encode_str_segment(name, v, n)
                    str_raw[name] = (d, v)
                elif not isinstance(v, (list, np.ndarray)):  # scalar
                    try:  # typed constant segment (no per-row list)
                        segs[name] = np.full(n, v, dtype=spec.np_dtype)
                    except (OverflowError, ValueError, TypeError):
                        segs[name] = [v] * n  # poisoned: seal handles
                elif isinstance(v, np.ndarray):
                    # typed segment passes through; COPY — callers
                    # (native decoder) reuse their buffers
                    segs[name] = v.astype(spec.np_dtype)
                else:
                    segs[name] = list(v)  # shallow copy: caller may reuse
            else:
                segs[name] = np.full(n, self._fill(name, spec),
                                     dtype=spec.np_dtype)
        self._append_segments(segs, n, str_raw)

    def _append_segments(self, segs: dict[str, object], n: int,
                         str_raw: dict[str, tuple] | None = None) -> None:
        """Buffer pre-encoded segments into this thread's stripe; seal to
        the shared chunk list at the chunk boundary. str_raw carries the
        (dictionary used, raw value) per str column so segments encoded
        against a dictionary that a concurrent compaction has since
        swapped are re-encoded — compaction holds every stripe lock, so
        inside our stripe lock the identity check is race-free."""
        s = self._stripe()
        with s.lock:
            if str_raw:
                for name, (d_used, raw) in str_raw.items():
                    if self.dicts[name] is not d_used:
                        _, segs[name] = self._encode_str_segment(
                            name, raw, n)
            for name, seg in segs.items():
                s.buf[name].append(seg)
            s.rows += n
            s.seq += 1
            with self._lock:
                self.rows_written += n
                self._note_segment(
                    segs.get(self._time_col) if self._time_col else None)
            if s.rows >= self.chunk_rows:
                self._seal_stripe(s)
        if self._listeners:
            self._notify()

    @staticmethod
    def _materialize(segs: list, spec) -> np.ndarray:
        if len(segs) == 1 and isinstance(segs[0], np.ndarray):
            return segs[0]
        parts = [s if isinstance(s, np.ndarray)
                 else np.asarray(s, dtype=spec.np_dtype) for s in segs]
        return (np.concatenate(parts) if parts
                else np.empty(0, dtype=spec.np_dtype))

    def _seal_stripe(self, s: _Stripe) -> None:
        """Materialize one stripe's buffer into a sealed chunk. Caller
        holds s.lock (NOT self._lock)."""
        if s.rows == 0:
            return
        chunk = {}
        try:
            for name, spec in self.columns.items():
                chunk[name] = self._materialize(s.buf[name], spec)
        except (OverflowError, ValueError, TypeError) as e:
            # a poisoned value must not wedge the table: drop the window
            dropped = s.rows
            for name in self.columns:
                s.buf[name] = []
            s.rows = 0
            s.seq += 1
            s.mat = None
            with self._lock:
                self.rows_written -= dropped
            raise ValueError(
                f"{self.name}: dropped {dropped} buffered rows — "
                f"value out of range for a column: {e}") from e
        for name in self.columns:
            s.buf[name] = []
        s.rows = 0
        s.seq += 1
        s.mat = None
        chunk = SealedChunk(chunk, self._time_col)
        with self._lock:
            self._chunks.append(chunk)

    def flush(self) -> None:
        for s in self._all_stripes():
            with s.lock:
                self._seal_stripe(s)

    # -- on-disk tier (store/tiered.py) --------------------------------------

    def attach_tier(self, tier) -> None:
        """Adopt an on-disk tier (restart recovery path): its rows join
        the table's row count and its time span marks the cache buckets,
        so change tokens move exactly as if the rows were (re)loaded."""
        with self._lock:
            self.tier = tier
            tier._columns = self.columns
            tier._fills = self.fills
            self.rows_written += tier.rows
            self.watermark += 1
            tmin, tmax = tier.span()
            if tmin is not None and tmax is not None:
                self._note_span(tmin, tmax)
            elif tier.rows:
                self._wide_mark = self.watermark
        if self._listeners:
            self._notify()

    def take_flushable(self, seal: bool = True) -> dict | None:
        """Stage every sealed RAM chunk for a tier commit.

        The chunks move atomically into _pending_flush (still visible to
        snapshot()), then merge into ONE chunk outside the table lock —
        heavy concatenation must not stall the append hot path. Returns
        the commit payload for TieredStore.commit(), or None when there
        is nothing to flush. Single flusher thread assumed (the staged
        list is private to it between take and confirm).

        ``seal=False`` takes only chunks that already sealed naturally:
        the group-commit fast path for cycles with no acks waiting on
        durability — open stripe buffers keep filling instead of being
        chopped into per-interval slivers (and their copy cost stays
        off the ingest hot path)."""
        if seal:
            self.flush()  # seal stripe buffers: durability covers them
        with self._lock:
            if self._chunks:
                self._pending_flush.extend(self._chunks)
                self._chunks = []
            parts = list(self._pending_flush)
        if not parts:
            return None
        merged = (SealedChunk({name: self._materialize(
            [ch[name] for ch in parts], spec)
            for name, spec in self.columns.items()}, self._time_col)
            if len(parts) > 1 else parts[0])
        with self._lock:
            self._pending_flush = [merged]
        rows = len(next(iter(merged.values()))) if merged else 0
        if rows == 0:
            with self._lock:
                self._pending_flush = []
            return None
        return {
            "chunk": merged, "rows": rows, "time_col": self._time_col,
            "dicts": dict(self.dicts),
            "dict_state": {n: d.sync_state()[:2]
                           for n, d in self.dicts.items()},
        }

    def confirm_flush(self, payload: dict) -> None:
        """The tier committed the staged chunk: adopt its segment into
        the scan set and stop serving the RAM copy — BOTH under this one
        lock, which snapshot() also holds while assembling its chunk
        list, so a concurrent reader sees the rows exactly once (never
        zero, never twice; rows_written is unchanged). Bumps the
        watermark — ISSUE contract: change tokens cover segment flushes
        — but leaves bucket marks alone: the answer content did not
        change, so cached per-bucket partials stay valid."""
        with self._lock:
            seg = payload.get("segment")
            if seg is not None and self.tier is not None:
                self.tier._add(seg)
            self._pending_flush = [
                ch for ch in self._pending_flush
                if ch is not payload["chunk"]]
            self.watermark += 1
        if self._listeners:
            self._notify()

    def note_tier_publish(self, rows: int, tmin=None, tmax=None) -> None:
        """Read-tier adoption bookkeeping (store/segcache.py): rows a
        remote shard published join the row count and mark the covered
        time range exactly like a local flush commit — a segment
        published at gen G moves the change token the same way
        confirm_flush + attach_tier would have."""
        with self._lock:
            self.rows_written += rows
            self.watermark += 1
            if self._bucket_div and tmin is not None and tmax is not None:
                self._note_span(int(tmin), int(tmax))
            else:
                self._wide_mark = self.watermark
        if self._listeners:
            self._notify()

    def note_tier_evict(self, rows: int, tmin=None, tmax=None) -> None:
        """Tier eviction bookkeeping: dropped rows leave the row count
        and invalidate the evicted time range (satellite fix: eviction
        must move the QueryCache change token, or a cached whole-result
        over the evicted range would keep serving dropped rows)."""
        with self._lock:
            self.rows_written -= rows
            self.watermark += 1
            if self._bucket_div and tmin is not None and tmax is not None:
                self._note_span(int(tmin), int(tmax))
            else:
                self._wide_mark = self.watermark
        if self._listeners:
            self._notify()

    def note_tier_compact(self) -> None:
        """Tier compaction bookkeeping: rows and answers are unchanged
        (merge + stable time sort preserves every aggregate), but the
        scan-unit set was rebuilt, so conservatively move the change
        token — a cached plan keyed on the old segment list must not
        pin decoded chunks of unlinked files forever."""
        with self._lock:
            self.watermark += 1
            self._wide_mark = self.watermark
        if self._listeners:
            self._notify()

    # -- read path -----------------------------------------------------------

    def snapshot(self) -> list[dict[str, np.ndarray]]:
        """Chunk list incl. every stripe's current buffer (sealed copies).
        All stripe locks are held while reading so no seal can move rows
        between the chunk list and a buffer mid-snapshot."""
        return [ch for ch, _z, _s in self.scan_units()]

    def scan_units(self) -> list[tuple[dict, dict | None, object]]:
        """snapshot() with pruning metadata: (chunk, zones, segment)
        triples under the same locking, where zones is the backing
        segment's per-column (zmin, zmax) map for tier chunks and None
        for RAM chunks (live stripes and pending flushes mutate too
        often to keep bounds); segment is the backing store Segment for
        tier chunks (its v2 bloom/bitmap skip indexes feed the planner)
        and None for RAM chunks."""
        stripes = self._all_stripes()
        units: list[tuple[dict, dict | None, object]] = []
        with contextlib.ExitStack() as stack:
            for s in stripes:
                stack.enter_context(s.lock)
            with self._lock:
                # tier chunks read under the TABLE lock: confirm_flush
                # adopts a segment and drops its _pending_flush copy
                # under the same lock, so this list can never hold both
                # (or neither) view of a flushed chunk. Lock order is
                # stripes -> table -> tier everywhere.
                if self.tier is not None:
                    units.extend(self.tier.units())
                units.extend((ch, None, None)
                             for ch in self._pending_flush)
                units.extend((ch, None, None) for ch in self._chunks)
            for s in stripes:
                if not s.rows:
                    continue
                if s.mat is not None and s.mat[0] == s.seq:
                    units.append((s.mat[1], None, None))
                    continue
                chunk = {}
                for name, spec in self.columns.items():
                    arr = self._materialize(s.buf[name], spec)
                    # collapse converted segments so the next snapshot
                    # pays asarray only for rows appended since this one
                    s.buf[name] = [arr]
                    chunk[name] = arr
                s.mat = (s.seq, chunk)
                units.append((chunk, None, None))
        return units

    def chunk_units(self) -> list[tuple[tuple, int | None,
                                        int | None, int | None]]:
        """scan_units() for the query cache's chunk partials
        (query/cache.py): the same snapshot, under the same locking and
        in the same order, as (unit, serial, tmin, tmax). A unit whose
        rows never change (a sealed or staged chunk, a tier segment)
        carries its serial and the time extent fixed when it was built;
        a live unit (an open stripe's rows) carries serial None and the
        extent of the rows it holds now. tmin/tmax are None where the
        extent is unknown: no time column, or a remote segment."""
        tc = self._time_col
        out = []
        for unit in self.scan_units():
            ch, _z, seg = unit
            if isinstance(ch, SealedChunk):
                out.append((unit, ch.serial, ch.tmin, ch.tmax))
            elif seg is not None:
                ok = getattr(seg, "time_col", None) == tc
                out.append((unit, getattr(seg, "serial", None),
                            getattr(seg, "tmin", None) if ok else None,
                            getattr(seg, "tmax", None) if ok else None))
            else:
                t = ch.get(tc) if tc else None
                known = t is not None and len(t) and t.dtype.kind in "iu"
                out.append((unit, None,
                            *((int(t.min()), int(t.max())) if known
                              else (None, None))))
        return out

    def column_concat(self, names: list[str],
                      mask_chunks: list[np.ndarray] | None = None,
                      chunks: list[dict[str, np.ndarray]] | None = None
                      ) -> dict[str, np.ndarray]:
        """Materialize selected columns (optionally per-chunk filtered).

        When mask_chunks were computed against an earlier snapshot, pass that
        snapshot via `chunks` — a writer may seal new chunks in between.
        """
        if chunks is None:
            chunks = self.snapshot()
        if mask_chunks is not None and len(mask_chunks) != len(chunks):
            raise ValueError("mask_chunks/chunks length mismatch — compute "
                             "both from the same snapshot")
        out: dict[str, np.ndarray] = {}
        for name in names:
            spec = self.columns[name]
            parts = []
            for i, ch in enumerate(chunks):
                a = ch[name]
                if mask_chunks is not None:
                    a = a[mask_chunks[i]]
                parts.append(a)
            out[name] = (np.concatenate(parts) if parts
                         else np.empty(0, dtype=spec.np_dtype))
        return out

    def __len__(self) -> int:
        return self.rows_written

    # -- retention -----------------------------------------------------------

    def trim_before(self, time_col: str, cutoff: int) -> int:
        """Drop whole sealed chunks entirely older than cutoff. Returns rows
        dropped (coarse TTL, like CK partition drops)."""
        dropped = 0
        with self._lock:
            kept = []
            for ch in self._chunks:
                t = ch.get(time_col)
                if t is not None and len(t) and t.max() < cutoff:
                    dropped += len(t)
                else:
                    kept.append(ch)
            self._chunks = kept
            self.rows_written -= dropped  # keep __len__ = live rows
            if dropped:
                self.watermark += 1
                if self._bucket_div and time_col == self._time_col:
                    cut_b = int(cutoff) // self._bucket_div
                    for b in list(self._bucket_marks):
                        if b <= cut_b:
                            self._bucket_marks[b] = self.watermark
                else:
                    self._wide_mark = self.watermark
        if dropped and self._listeners:
            self._notify()
        return dropped

    def compact_dictionaries(self, min_entries: int = 4096,
                             max_live_frac: float = 0.5) -> dict:
        """Rebuild string dictionaries down to the ids still referenced by
        live data. TTL trims drop chunks but dictionaries are append-only,
        so high-cardinality columns (log bodies, trace ids, folded stacks)
        would otherwise grow without bound (ClickHouse reclaims
        LowCardinality storage on partition drop; the embedded store needs
        this explicit pass). Only columns with >= min_entries entries of
        which <= max_live_frac are still referenced get rebuilt.

        Chunks are remapped into NEW chunk dicts and swapped together with
        the new dictionary under ALL stripe locks + the table lock — a
        writer mid-append either encoded against the old dictionary (its
        stripe lock makes it re-encode, see _append_segments) or will
        encode against the new one. A reader that snapshotted before the
        swap and decodes via self.dicts after it may mis-render strings
        for that one scan; the janitor runs this rarely (post-trim) to
        keep the window negligible."""
        if self.tier is not None:
            # on-disk segments carry dictionary ids verbatim — rebinding
            # them would corrupt every persisted chunk. Tiered tables
            # reclaim dictionary space the ClickHouse way instead: whole
            # segments (and eventually their ids' referents) age out via
            # TTL eviction.
            return {}
        stats: dict[str, dict] = {}
        stripes = self._all_stripes()
        with contextlib.ExitStack() as stack:
            for s in stripes:
                stack.enter_context(s.lock)
            with self._lock:
                for name in list(self.dicts):
                    d = self.dicts[name]
                    old_n = len(d)
                    if old_n < min_entries:
                        continue
                    used: set[int] = set()
                    for ch in self._chunks:
                        used.update(np.unique(ch[name]).tolist())
                    for s in stripes:
                        for seg in s.buf[name]:
                            used.update(np.unique(seg).tolist()
                                        if isinstance(seg, np.ndarray)
                                        else seg)
                    used.discard(0)
                    if len(used) + 1 > old_n * max_live_frac:
                        continue
                    order = sorted(used)
                    strings = [""] + [d.decode(i) for i in order]
                    lut = np.zeros(old_n, dtype=np.uint32)
                    for new_id, old_id in enumerate(order, start=1):
                        lut[old_id] = new_id
                    self._chunks = [
                        SealedChunk({**ch, name: lut[ch[name]]},
                                    self._time_col)
                        for ch in self._chunks]
                    for s in stripes:
                        s.buf[name] = [
                            lut[seg] if isinstance(seg, np.ndarray)
                            else [int(lut[i]) for i in seg]
                            for seg in s.buf[name]]
                        s.seq += 1
                        s.mat = None
                    nd = Dictionary(d.name)
                    nd._strings = strings
                    nd._str_to_id = {s: i for i, s in enumerate(strings)}
                    # id->string bindings changed: bump gen so cached
                    # encoded partials / shipped id deltas are invalidated
                    # exactly (decoded answers are unchanged, so the table
                    # watermark is NOT bumped)
                    nd.version = d.version + 1
                    nd.gen = d.gen + 1
                    self.dicts[name] = nd
                    stats[name] = {"before": old_n, "after": len(strings)}
        return stats

    # -- persistence (npz per chunk + dict json) -----------------------------

    def save(self, dirpath: str) -> None:
        """Crash-safe: write everything into a staging dir, swap it into
        place, keep the previous dir as .old until the swap completes — a
        kill at ANY point leaves either the old or the new state loadable
        (ckissu-style upgrade safety for the embedded store)."""
        import shutil
        staging = dirpath + ".staging"
        old = dirpath + ".old"
        shutil.rmtree(staging, ignore_errors=True)
        os.makedirs(staging)
        chunks = self.snapshot()
        for i, ch in enumerate(chunks):
            np.savez_compressed(
                os.path.join(staging, f"chunk_{i:06d}.npz"), **ch)
        for name, d in self.dicts.items():
            d.dump(os.path.join(staging, f"dict_{name}.json"))
        with open(os.path.join(staging, "_complete"), "w") as f:
            f.write("1")
        shutil.rmtree(old, ignore_errors=True)
        if os.path.isdir(dirpath):
            os.rename(dirpath, old)
        os.rename(staging, dirpath)
        shutil.rmtree(old, ignore_errors=True)

    @staticmethod
    def recover_dir(dirpath: str) -> str | None:
        """Pick the loadable directory after a possible mid-save crash.
        Returns the path to load from, or None when nothing exists."""
        import shutil
        old = dirpath + ".old"
        staging = dirpath + ".staging"
        shutil.rmtree(staging, ignore_errors=True)  # never trust staging
        have_dir = os.path.isdir(dirpath)
        dir_complete = have_dir and (
            os.path.exists(os.path.join(dirpath, "_complete"))
            # legacy (round-1) saves predate the marker: complete iff no
            # .old sibling suggests an interrupted swap
            or not os.path.isdir(old))
        if dir_complete:
            shutil.rmtree(old, ignore_errors=True)
            return dirpath
        if os.path.isdir(old):
            shutil.rmtree(dirpath, ignore_errors=True)
            os.rename(old, dirpath)
            return dirpath
        return dirpath if have_dir else None

    def load(self, dirpath: str, from_version: int | None = None) -> None:
        from deepflow_tpu.store import migration
        loadable = self.recover_dir(dirpath)
        if loadable is None:
            return
        dirpath = loadable
        with contextlib.ExitStack() as stack:
            for s in self._all_stripes():
                stack.enter_context(s.lock)
                s.buf = {name: [] for name in self.columns}
                s.rows = 0
                s.seq += 1
                s.mat = None
            stack.enter_context(self._lock)
            self._chunks = []
            for fn in sorted(os.listdir(dirpath)):
                if fn.startswith("chunk_") and fn.endswith(".npz"):
                    z = np.load(os.path.join(dirpath, fn))
                    ch = {k: z[k] for k in z.files}
                    if from_version is not None and \
                            from_version < migration.SCHEMA_VERSION:
                        ch = migration.migrate_chunk(self.name, ch,
                                                     from_version)
                    # additive schema compat: chunks persisted before a
                    # column existed get the column's fill (else any
                    # query touching the new column KeyErrors). Fill, not
                    # schema default: rows saved by a pre-cluster node
                    # and loaded by shard N were ingested HERE, so they
                    # take this shard's identity
                    if ch:
                        n = len(next(iter(ch.values())))
                        for name, spec in self.columns.items():
                            if name not in ch:
                                ch[name] = np.full(n,
                                                   self._fill(name, spec),
                                                   dtype=spec.np_dtype)
                    self._chunks.append(SealedChunk(ch, self._time_col))
            for name in self.dicts:
                p = os.path.join(dirpath, f"dict_{name}.json")
                if os.path.exists(p):
                    self.dicts[name] = Dictionary.load(p, name)
            self.rows_written = sum(
                len(next(iter(ch.values()))) for ch in self._chunks if ch)
            self.watermark += 1
            if self._time_col:
                for ch in self._chunks:
                    self._note_segment(ch.get(self._time_col))
            else:
                self._wide_mark = self.watermark
        if self._listeners:
            self._notify()
