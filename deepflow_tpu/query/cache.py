"""Querier-level result + partial-aggregate cache with exact invalidation.

Three layers, all validated by monotonic change tokens — never by TTL,
and never approximate: a hit is exact or it is not served.

- whole-result cache: validated against the change token (the table
  watermark + dictionary gens, ``change_token``). Any append, trim,
  load or dictionary rebuild changes it.
- per-chunk partial cache, for aggregates over a range that ends past
  the newest stored row (a dashboard's ``[start, now)``).
- per-time-bucket partial cache, for every other aggregate: queries are
  sliced into the table's 60s bucket grid; each bucket's ENCODED
  partial (engine.execute_partial(encoded=True)) is cached against that
  bucket's write mark + the dictionary gens. An append invalidates only
  the buckets it touched, and engine.combine_partials folds the slices
  back into one exact partial.

Range normalization. The top-level WHERE conjuncts that bound the time
column by an integer literal (``time >= t0``, ``time > t0``, ``time <
t1``, ``time <= t1``) are taken out of the query (``split_range``);
what is left is its SHAPE, and the bounds become the range [lo, hi).
Every query a dashboard sends on refresh has one shape, whatever its
``now``. The whole-result and chunk layers key on the shape:

- A whole result filled from one snapshot whose range left no stored
  row above it is kept under the shape key with the snapshot's time
  extent [mn, mx] and its lower bound. It answers a later query of the
  same shape while the change token is equal (no row was written,
  trimmed or rebound since the token was read, which happens BEFORE
  the snapshot) and the query's range selects the same stored rows:
  its upper bound is above mx, and its lower bound equals the entry's
  or both are at or below mn. The answer is then identical to
  engine.execute of the query's own SQL. Any other result keys on the
  whole query, as before.
- A chunk partial: a sealed chunk never changes (``ColumnarTable.
  chunk_units`` gives each a serial no other chunk gets, and its time
  extent). For an aggregate whose upper bound lies above every stored
  row, a sealed chunk wholly inside the range contributes the partial
  cached for it under (shape, serial, dictionary gens), computed once
  over that chunk alone; a chunk the lower bound cuts contributes one
  cached under the same key plus that bound; a chunk wholly below the
  range contributes nothing; the live units (open stripes) are scanned
  fresh under the full predicate. Parts fold in scan order with
  combine_partials(ascending=True), so the answer is the serial
  executor's, row for row. Trim drops chunks (their entries are
  pruned), a flush merge, dictionary remap or load builds new chunks
  with new serials, and a gen flip moves the gens every entry carries:
  a stale partial can never validate, and the worst case is a rescan.
  A miss therefore scans only the live rows plus any chunk sealed
  since the shape was last asked, not the whole store.

Queries whose upper bound cuts stored rows (historic ranges) and
standing-query windows folded by ``bucket_range`` take the bucket path
exactly as before; so does a query with no time bound, and any table
that is a view (claim/exclusion filters) rather than a ColumnarTable.
Only what the code sees decides: the query's bounds against the
chunks' extents.

The token is read BEFORE executing: a write racing the fill can only
make the stored token stale (harmless recompute next time), never let a
stale entry validate.

Tiered storage keeps these tokens exact through persistence events by
bumping the SAME table watermark the token reads: attaching a restored
tier, confirming a flush (RAM chunks swapped for mmap'd segments — the
result set is unchanged but the backing store is not), and evicting
segments (note_tier_evict, which also marks the evicted time span so
bucket partials over it invalidate) all advance it. Rollup appends are
ordinary writes to the rollup TABLE, and datasource selection swaps the
table object before the cache lookup — raw and rollup entries key
separately, so a coarser answer can never serve a raw-table hit.

Admission goes through the learned cost hook (query/costmodel.py —
"A Learned Performance Model for TPUs" motivates modeled rather than
hand-tuned plan choices): queries whose observed cold cost stays under
DF_QUERY_CACHE_MIN_NS are not worth an entry. DF_QUERY_CACHE=0 bypasses
entirely.

Self-telemetry: one ``query.cache`` hop ledger (PR 2 conventions) —
emitted=lookups, delivered=hits, dropped{miss|stale|bypass}; evictions,
``range_normalized`` (lookups whose key dropped a bound),
``chunk_hits``/``chunk_misses`` and ``rows_scanned`` (rows of the scan
units handed to scans on a lookup's behalf) are counters surfaced in
/v1/health, and the ``cache.lookup`` span carries ``range`` and
``rows_scanned``.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import OrderedDict

from deepflow_tpu.query import engine
from deepflow_tpu.query import pool as qpool
from deepflow_tpu.query import qtrace
from deepflow_tpu.query import sql as S
from deepflow_tpu.query.costmodel import KernelCostModel
from deepflow_tpu.store.table import ColumnarTable


def normalize_sql(sql: str) -> str:
    return " ".join(sql.split())


def change_token(table) -> list:
    """Result-validity token: [watermark, [[dict, gen], ...]].

    Deliberately NOT the full sync_state(): dictionary LENGTH is
    excluded because dictionaries can grow without any row write — the
    federation coordinator encodes remote shard strings into its local
    dictionaries while remapping (cluster/dictsync.py). Growth is
    append-only within a gen, and rows only ever reference ids minted by
    writes (which bump the watermark), so extra entries cannot change
    any query answer: decode of existing ids, collation order, LIKE and
    equality pushdown all come out identical. Gen flips (compaction,
    reload) rebind ids and DO invalidate."""
    wm, dicts = table.sync_state()
    return [wm, [[n, g] for n, g, _l in dicts]]


_INT_KINDS = ("u8", "u16", "u32", "u64", "i8", "i16", "i32", "i64")
# inclusive lower / exclusive upper bound from `time <op> literal`
_BOUNDS = {">=": ("lo", 0), ">": ("lo", 1), "<": ("hi", 0),
           "<=": ("hi", 1)}


def split_range(table, query: S.Select):
    """-> (shape, lo, hi): the query without its top-level conjuncts
    that bound the table's integer time column by an integer literal,
    and the range [lo, hi) they leave (None: that side is unbounded).
    With no such conjunct: (query, None, None)."""
    tc = getattr(table, "_time_col", None)
    spec = table.columns.get(tc) if tc else None
    if spec is None or spec.kind not in _INT_KINDS or query.where is None:
        return query, None, None
    lo = hi = None
    kept = []
    for c in engine.split_conjuncts(query.where):
        b = (_BOUNDS.get(c.op) if isinstance(c, S.BinOp)
             and isinstance(c.left, S.Col) and c.left.name == tc
             and isinstance(c.right, S.Lit)
             and type(c.right.value) is int else None)
        if b is None:
            kept.append(c)
            continue
        v = c.right.value + b[1]
        if b[0] == "lo":
            lo = v if lo is None else max(lo, v)
        else:
            hi = v if hi is None else min(hi, v)
    if lo is None and hi is None:
        return query, None, None
    where = None
    for c in kept:
        where = c if where is None else S.BinOp("AND", where, c)
    return dataclasses.replace(query, where=where), lo, hi


_UNKNOWN = object()  # a snapshot holding a unit of unknown time extent


def _extent(snap: list):
    """(mn, mx) over a chunk_units() snapshot; None with no rows,
    _UNKNOWN where a unit's extent is unknown."""
    if any(u[2] is None for u in snap):
        return _UNKNOWN
    if not snap:
        return None
    return min(u[2] for u in snap), max(u[3] for u in snap)


def _same_rows(ent_lo, lo, hi, ext) -> bool:
    """Whether [lo, hi) selects the stored rows of extent ``ext`` that
    an entry filled over [ent_lo, h) with h > ext's mx selected."""
    if ext is None:
        return True
    mn, mx = ext
    if hi is not None and hi <= mx:
        return False
    return lo == ent_lo or ((lo is None or lo <= mn)
                            and (ent_lo is None or ent_lo <= mn))


class _UnitsView:
    """A table that scans only the given scan units (a subset of one
    snapshot); everything else delegates, so the engines run on it
    unmodified."""

    def __init__(self, table, units: list) -> None:
        self._table = table
        self._units = units

    def scan_units(self) -> list:
        return list(self._units)

    def snapshot(self) -> list:
        return [ch for ch, _z, _s in self._units]

    def column_concat(self, names, mask_chunks=None, chunks=None):
        return self._table.column_concat(
            names, mask_chunks=mask_chunks,
            chunks=self.snapshot() if chunks is None else chunks)

    def __len__(self) -> int:
        return sum(engine._chunk_rows(ch) for ch, _z, _s in self._units)

    def __getattr__(self, name: str):
        return getattr(self._table, name)


class QueryCache:
    def __init__(self, max_entries: int = 128, max_buckets: int = 512,
                 telemetry=None) -> None:
        self.max_entries = max_entries
        self.max_buckets = max_buckets
        self._lock = threading.Lock()
        # result key -> (token, QueryResult, range entry or None); a
        # range entry (ent_lo, extent) marks a shape-keyed result
        self._results: OrderedDict[tuple, tuple] = OrderedDict()
        # (table, sql) -> OrderedDict{bucket: (mark, gens, partial)}
        self._buckets: OrderedDict[tuple, OrderedDict] = OrderedDict()
        # shape key -> {serial: (gens, cut lower bound, partial)}
        self._chunks: OrderedDict[tuple, dict] = OrderedDict()
        self.counters = {"hits": 0, "misses": 0, "stale": 0, "bypass": 0,
                         "evictions": 0, "bucket_hits": 0,
                         "bucket_misses": 0, "bucket_pruned": 0,
                         "dist_hits": 0, "range_normalized": 0,
                         "chunk_hits": 0, "chunk_misses": 0,
                         "rows_scanned": 0}
        self._hop = telemetry.hop("query.cache") if telemetry else None
        # distributed partial-cache hook (cluster/partialcache.py):
        # dist(table, key, [bucket, ...], gens) -> {bucket: partial} of
        # slices a warm peer already computed, remapped into LOCAL
        # dictionary ids — they slot into the bucket store exactly like
        # a local scan's output. None = single-node, zero overhead.
        self.dist = None
        # learned cold-cost per cached query shape (admission hook)
        self.cost = KernelCostModel(kernels=("cold", "warm"))

    # -- helpers -------------------------------------------------------------

    _OUTCOME_KEY = {"hit": "hits", "miss": "misses", "stale": "stale",
                    "bypass": "bypass"}

    def _account(self, outcome: str) -> None:
        with self._lock:
            self.counters[self._OUTCOME_KEY[outcome]] += 1
        if self._hop is not None:
            if outcome == "hit":
                self._hop.account(emitted=1, delivered=1)
            else:
                self._hop.account(emitted=1, dropped=1, reason=outcome)

    def _bump(self, **counts) -> None:
        with self._lock:
            for k, n in counts.items():
                self.counters[k] += n

    def enabled(self) -> bool:
        return os.environ.get("DF_QUERY_CACHE", "1") != "0"

    def _min_ns(self) -> float:
        try:
            return float(os.environ.get("DF_QUERY_CACHE_MIN_NS", "0"))
        except ValueError:
            return 0.0

    @staticmethod
    def _copy_result(res: engine.QueryResult) -> engine.QueryResult:
        return engine.QueryResult(columns=list(res.columns),
                                  values=[list(r) for r in res.values])

    def _lru_put(self, store: OrderedDict, key, value) -> None:
        """Insert under self._lock, evicting the oldest past max_entries."""
        store[key] = value
        store.move_to_end(key)
        while len(store) > self.max_entries:
            store.popitem(last=False)
            self.counters["evictions"] += 1

    # -- whole-result layer --------------------------------------------------

    def execute(self, table, sql: str, *, select=None,
                extra_key=None) -> engine.QueryResult:
        """engine.execute() through the cache. `select` is an optional
        pre-parsed (possibly rewritten — org scoping) AST to run instead
        of parsing `sql`; any rewrite not visible in the SQL text must be
        reflected in `extra_key` or rewritten variants would collide."""
        if not self.enabled():
            self._account("bypass")
            qtrace.span("cache.lookup", layer="result",
                        outcome="bypass").finish()
            return engine.execute(table, select if select is not None
                                  else sql)
        token = change_token(table)  # BEFORE executing: stale-safe
        query = select if select is not None else S.parse(sql)
        shape, lo, hi = split_range(table, query)
        ranged = shape is not query
        full_key = (table.name, repr(query), extra_key)
        shape_key = ((table.name, "range", repr(shape), extra_key)
                     if ranged else None)
        with self._lock:
            ents = [(k, self._results.get(k)) for k in (shape_key,
                                                         full_key)
                    if k is not None]
        hit = next((e for k, e in ents if e is not None and e[0] == token
                    and (e[2] is None
                         or _same_rows(e[2][0], lo, hi, e[2][1]))), None)
        if ranged:
            self._bump(range_normalized=1)
        rng = "normalized" if ranged else "kept"
        if hit is not None:
            self._account("hit")
            self.cost.observe("warm", 1, 1.0)
            qtrace.span("cache.lookup", layer="result", outcome="hit",
                        range=rng, rows_scanned=0).finish()
            return self._copy_result(hit[1])
        outcome = ("stale" if any(e is not None for _k, e in ents)
                   else "miss")
        self._account(outcome)
        sp = qtrace.span("cache.lookup", layer="result", outcome=outcome,
                         range=rng)
        sp.finish()
        t0 = time.perf_counter_ns()
        res, ext, scanned = self._execute_cold(
            table, query, (table.name, normalize_sql(sql), extra_key),
            (shape_key, lo, hi))
        cold_ns = time.perf_counter_ns() - t0
        self._bump(rows_scanned=scanned)
        sp.annotate(rows_scanned=scanned)  # attrs serialize at trace end
        self.cost.observe("cold", 1, cold_ns)
        if cold_ns >= self._min_ns():
            shaped = (ranged and ext is not _UNKNOWN
                      and (hi is None or ext is None or hi > ext[1]))
            ent = (token, self._copy_result(res),
                   (lo, ext) if shaped else None)
            with self._lock:
                self._lru_put(self._results,
                              shape_key if shaped else full_key, ent)
        return res

    def _execute_cold(self, table, query: S.Select, key: tuple,
                      rng: tuple):
        """-> (result, extent, rows scanned). A ranged query on a
        ColumnarTable runs over one chunk_units() snapshot, whose time
        extent comes back (_UNKNOWN otherwise); aggregates take the
        partial layers, everything else a plain scan."""
        snap = (table.chunk_units()
                if rng[0] is not None and isinstance(table, ColumnarTable)
                else None)
        ext = _UNKNOWN if snap is None else _extent(snap)
        try:
            got = self._partials(table, query, key, rng, snap, ext)
            if got is not None:
                parts, ascending, scanned = got
                combined = engine.combine_partials(table, query, parts,
                                                   ascending=ascending)
                return (engine.merge_partials(table, query, [combined]),
                        ext, scanned)
        except engine._FastUnsupported:
            self._drop_buckets(key)
        except engine.QueryError:
            raise
        except Exception:
            self._drop_buckets(key)
        if snap is None:
            return engine.execute(table, query), _UNKNOWN, len(table)
        view = _UnitsView(table, [u[0] for u in snap])
        return engine.execute(view, query), ext, len(view)

    def _partials(self, table, query: S.Select, key: tuple, rng: tuple,
                  snap, ext, bucket_range=None, stats=None):
        """The one planner behind execute, partial and standing_fold:
        -> (encoded partials, ascending, rows scanned), or None when the
        query is not bucketable. A ranged aggregate whose upper bound
        lies above every row of the snapshot folds chunk partials; every
        other one folds 60s bucket partials (and the dist fetch)."""
        if not self._bucketable(table, query):
            return None
        shape_key, lo, hi = rng
        if (snap is not None and bucket_range is None
                and ext is not _UNKNOWN
                and (hi is None or ext is None or hi > ext[1])):
            got = self._chunk_partials(table, query, shape_key, lo, snap,
                                       stats)
            if got is not None:
                return got
        bstats = stats if stats is not None else {}
        parts = self._bucket_partials(table, query, key,
                                      bucket_range=bucket_range,
                                      stats=bstats)
        if parts is None:
            return None
        return parts, False, bstats.get("scanned", 0) * len(table)

    # -- chunk partial layer -------------------------------------------------

    def _chunk_partials(self, table, query: S.Select, skey: tuple, lo,
                        snap: list, stats: dict | None = None):
        """Encoded partials over a chunk_units() snapshot, in its order:
        each sealed chunk's cached under skey (computed once), the live
        units scanned fresh. -> (parts, True, rows scanned), or None when
        the snapshot holds more sealed chunks than max_buckets or a
        dictionary gen moved while folding."""
        serials = {u[1] for u in snap if u[1] is not None}
        if len(serials) > self.max_buckets:
            return None
        gens = tuple((n, g) for n, g, _l in table.sync_state()[1])
        with self._lock:
            store = self._chunks.get(skey)
            if store is None:
                store = {}
                self._lru_put(self._chunks, skey, store)
            else:
                self._chunks.move_to_end(skey)
            for s in [s for s in store if s not in serials]:
                del store[s]  # trimmed or rebuilt: never valid again
            cached = dict(store)
        slots: list = []   # partial, or the index of a scan task
        tasks: list = []   # (units, serial, cut lower bound)
        fresh: list = []
        hits = 0
        for unit, serial, tmin, tmax in snap:
            if serial is None:
                fresh.append(unit)
                continue
            if lo is not None and tmax < lo:
                continue  # wholly below the range: contributes nothing
            cut = lo if lo is not None and tmin < lo else None
            ent = cached.get(serial)
            if fresh:
                slots.append(len(tasks))
                tasks.append((fresh, None, None))
                fresh = []
            if ent is not None and ent[0] == gens and ent[1] == cut:
                hits += 1
                slots.append(ent[2])
            else:
                slots.append(len(tasks))
                tasks.append(([unit], serial, cut))
        if fresh or not slots:
            slots.append(len(tasks))
            tasks.append((fresh, None, None))

        def _scan(task):
            return engine.execute_partial(_UnitsView(table, task[0]),
                                          query, encoded=True)

        p = qpool.get_pool()
        outs = (p.map(_scan, tasks) if p is not None and len(tasks) > 1
                else [_scan(t) for t in tasks])
        if any(o.get("kind") != "agg" for o in outs) or gens != tuple(
                (n, g) for n, g, _l in table.sync_state()[1]):
            return None
        misses = sum(1 for t in tasks if t[1] is not None)
        scanned = sum(engine._chunk_rows(u[0]) for t in tasks
                      for u in t[0])
        with self._lock:
            for (_units, serial, cut), part in zip(tasks, outs):
                if serial is not None:
                    store[serial] = (gens, cut, part)
            self.counters["chunk_hits"] += hits
            self.counters["chunk_misses"] += misses
        qtrace.annotate(chunks=len(serials), chunk_hits=hits,
                        chunk_misses=misses)
        if stats is not None:
            stats["buckets"] = len(slots)
            stats["bucket_hits"] = hits
            stats["scanned"] = len(tasks)
        return ([outs[s] if isinstance(s, int) else s for s in slots],
                True, scanned)

    # -- bucketed partial layer ----------------------------------------------

    def _bucketable(self, table, query: S.Select) -> bool:
        if os.environ.get("DF_QUERY_ENCODED", "1") == "0":
            return False
        norm = engine._normalize(table, query)
        if not engine._is_agg_query(norm):
            return False
        # PERCENTILE: the local scan uses exact np.percentile while the
        # partial form is a sketch — caching would change answers. LAST:
        # cross-bucket timestamp ties could resolve differently.
        if any(s.name in ("PERCENTILE", "LAST")
               for s in engine._agg_sites(norm)):
            return False
        return True

    def _bucket_partials(self, table, query: S.Select, key: tuple,
                         bucket_range: tuple[int, int] | None = None,
                         stats: dict | None = None):
        """Per-bucket encoded partials for an eligible aggregate query,
        reusing every bucket whose (write mark, dict gens) is unchanged.
        None when the query/table isn't bucketable. bucket_range=(lo, hi)
        folds only buckets with lo <= b < hi (the standing-query window
        slice); stats, when given, is filled with bucket reuse counts."""
        if not self._bucketable(table, query):
            return None
        wm, marks, wide, div = table.bucket_marks()
        tc = getattr(table, "_time_col", None)
        if div <= 0 or tc is None or wide:
            return None
        sub = marks
        if bucket_range is not None:
            lo_b, hi_b = bucket_range
            sub = {b: m for b, m in marks.items() if lo_b <= b < hi_b}
        if len(sub) > self.max_buckets:
            return None
        gens = tuple((n, g) for n, g, _l in table.sync_state()[1])
        with self._lock:
            store = self._buckets.get(key)
            if store is None:
                store = OrderedDict()
                self._lru_put(self._buckets, key, store)
            # buckets trimmed off the grid can never validate again —
            # pruned against the FULL mark grid, so a windowed fold never
            # evicts slices another caller of the same key still wants
            for b in [b for b in store if b not in marks]:
                del store[b]
                self.counters["bucket_pruned"] += 1
        ordered = sorted(sub.items())
        slot: dict[int, dict] = {}
        stale: list[tuple[int, int]] = []
        for b, mark in ordered:
            with self._lock:
                ent = store.get(b)
            if ent is not None and ent[0] == mark and ent[1] == gens:
                with self._lock:
                    self.counters["bucket_hits"] += 1
                slot[b] = ent[2]
            else:
                stale.append((b, mark))
        qtrace.annotate(buckets=len(ordered), bucket_stale=len(stale))
        if stats is not None:
            stats["buckets"] = len(ordered)
            stats["bucket_hits"] = len(ordered) - len(stale)
        if stale and self.dist is not None:
            # ask a warm peer before scanning: each (mark, gens) was
            # captured BEFORE the fetch, so a write racing the network
            # round-trip can only make the stored entry stale (same
            # safety argument as the local fill path)
            try:
                got = self.dist(table, key, [b for b, _m in stale], gens)
            except Exception:
                got = {}
            if got:
                still = []
                for b, mark in stale:
                    part = got.get(b)
                    if part is not None and part.get("kind") == "agg":
                        with self._lock:
                            self.counters["dist_hits"] += 1
                            store[b] = (mark, gens, part)
                        slot[b] = part
                    else:
                        still.append((b, mark))
                if stats is not None:
                    stats["dist_hits"] = len(stale) - len(still)
                stale = still
        if stats is not None:
            stats["scanned"] = len(stale)
        if stale:
            def _scan(bm):
                b, _mark = bm
                bq = self._bucket_query(query, tc, b * div, (b + 1) * div)
                return engine.execute_partial(table, bq, encoded=True)
            # stale buckets recompute on the shared scan pool (each
            # bucket's execute_partial runs serially inside its worker —
            # the in_worker guard stops nested fan-out)
            p = qpool.get_pool()
            if p is not None and len(stale) > 1:
                outs = p.map(_scan, stale)
            else:
                outs = [_scan(bm) for bm in stale]
            for (b, mark), part in zip(stale, outs):
                if part.get("kind") != "agg":
                    return None
                with self._lock:
                    self.counters["bucket_misses"] += 1
                    store[b] = (mark, gens, part)
                slot[b] = part
        return [slot[b] for b, _m in ordered]

    @staticmethod
    def _bucket_query(query: S.Select, tc: str, lo: int,
                      hi: int) -> S.Select:
        rng = S.BinOp("AND",
                      S.BinOp(">=", S.Col(tc), S.Lit(int(lo))),
                      S.BinOp("<", S.Col(tc), S.Lit(int(hi))))
        where = rng if query.where is None else \
            S.BinOp("AND", query.where, rng)
        # ORDER BY/LIMIT apply at the merge, not per slice; HAVING rides
        # along so its aggregate sites ship in the partial (it is only
        # APPLIED at the merge)
        return S.Select(items=query.items, table=query.table, where=where,
                        group_by=query.group_by, having=query.having,
                        order_by=[], limit=None)

    def _fold(self, table, query: S.Select, key: tuple, extra_key,
              bucket_range=None, stats=None):
        """_partials() for partial() and standing_fold(): range-split
        the query and snapshot the table where the chunk layer may
        apply."""
        shape, lo, hi = split_range(table, query)
        rng = ((table.name, "range", repr(shape), extra_key)
               if shape is not query else None, lo, hi)
        snap = (table.chunk_units()
                if rng[0] is not None and bucket_range is None
                and isinstance(table, ColumnarTable) else None)
        got = self._partials(table, query, key, rng, snap,
                             _UNKNOWN if snap is None else _extent(snap),
                             bucket_range=bucket_range, stats=stats)
        if got is not None:
            self._bump(rows_scanned=got[2])
        return got

    def partial(self, table, sql: str, *, select=None,
                extra_key=None) -> dict:
        """engine.execute_partial(encoded=True) through the partial
        layers: a warm shard answers a scatter by folding cached chunk or
        bucket slices instead of rescanning (shard-side half of
        federated caching)."""
        query = select if select is not None else sql
        if not self.enabled():
            return engine.execute_partial(table, query, encoded=True)
        key = (table.name, normalize_sql(sql), extra_key)
        try:
            if isinstance(query, str):
                query = S.parse(query)
            got = self._fold(table, query, key, extra_key)
            if got is not None:
                return engine.combine_partials(table, query, got[0],
                                               ascending=got[1])
        except engine._FastUnsupported:
            self._drop_buckets(key)
        except engine.QueryError:
            raise
        except Exception:
            self._drop_buckets(key)
        return engine.execute_partial(table, query, encoded=True)

    def standing_fold(self, table, sql: str, *, select=None,
                      extra_key=None,
                      bucket_range: tuple[int, int] | None = None
                      ) -> tuple:
        """Windowed incremental fold for standing queries
        (query/standing.py): fold ONLY the 60s buckets inside
        ``bucket_range``, reusing every cached slice (and the
        distributed partial cache via the dist hook); with no window, a
        query ranged to now folds chunk partials. Keys on the SAME
        (table, sql, extra_key) or shape as execute()/partial(), so
        standing and ad-hoc evaluations of one query share warm slices.
        Returns (QueryResult | None, stats): None when the query isn't
        bucketable or the window holds no marked buckets — the caller
        falls back to a from-scratch execute. stats counts slices:
        folded, reused from the cache, and scanned."""
        stats = {"buckets": 0, "bucket_hits": 0, "dist_hits": 0,
                 "scanned": 0}
        if not self.enabled():
            return None, stats
        key = (table.name, normalize_sql(sql), extra_key)
        try:
            query = select if select is not None else S.parse(sql)
            got = self._fold(table, query, key, extra_key,
                             bucket_range=bucket_range, stats=stats)
            if not got or not got[0]:
                return None, stats
            combined = engine.combine_partials(table, query, got[0],
                                               ascending=got[1])
            return engine.merge_partials(table, query, [combined]), stats
        except engine._FastUnsupported:
            self._drop_buckets(key)
        except engine.QueryError:
            raise
        except Exception:
            self._drop_buckets(key)
        return None, stats

    def _drop_buckets(self, key: tuple) -> None:
        with self._lock:
            self._buckets.pop(key, None)

    # -- distributed partial-cache surface ------------------------------------

    def warm_keys(self) -> list[tuple]:
        """Bucket-store keys holding at least one slice — the advert
        source for the cluster-wide partial cache (membership gossips
        digests of the shareable ones)."""
        with self._lock:
            return [k for k, v in self._buckets.items() if v]

    def peek_buckets(self, table, sql: str, extra_keys: list,
                     wanted: list) -> dict:
        """CURRENTLY-valid cached slices for the wanted buckets, under
        any of the candidate cache-key variants (the org-equivalent
        extra_key forms). Validation is against this node's own marks
        and dictionary gens — the caller (cluster/partialcache.py)
        established content equivalence with the requester separately,
        via the read-tier publish token."""
        wm, marks, wide, div = table.bucket_marks()
        if div <= 0 or wide:
            return {}
        gens = tuple((n, g) for n, g, _l in table.sync_state()[1])
        norm = normalize_sql(sql)
        out: dict[int, dict] = {}
        with self._lock:
            for ek in extra_keys:
                store = self._buckets.get((table.name, norm, ek))
                if not store:
                    continue
                for b in wanted:
                    if b in out or b not in marks:
                        continue
                    ent = store.get(b)
                    if ent is not None and ent[0] == marks[b] \
                            and ent[1] == gens:
                        out[b] = ent[2]
        return out

    # -- introspection -------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            return {"entries": len(self._results),
                    "bucket_queries": len(self._buckets),
                    "bucket_slices": sum(len(v)
                                         for v in self._buckets.values()),
                    "chunk_shapes": len(self._chunks),
                    "chunk_parts": sum(len(v)
                                       for v in self._chunks.values()),
                    **self.counters,
                    "cost": self.cost.snapshot()}
