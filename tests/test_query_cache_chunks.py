"""The query cache's range normalization and per-chunk partials.

A dashboard asks for [start, now): every refresh has a new `now`, and
the store grows between refreshes. The cache keys such queries on their
shape (the query without its time bounds), serves whole results while
no row was written, and otherwise folds one cached partial per sealed
chunk with a fresh scan of the live rows. These tests hold every cached
answer to engine.execute with the cache off, bound what a miss scans,
and check that rows racing a fill are never served by a later hit.
"""

import os
import random

import numpy as np
import pytest

from deepflow_tpu.query import engine
from deepflow_tpu.query.cache import QueryCache, split_range
from deepflow_tpu.query import sql as S
from deepflow_tpu.store.db import Database
from deepflow_tpu.store.schema import TPU_SPAN_KINDS

pytestmark = pytest.mark.skipif(
    os.environ.get("DF_QUERY_ENCODED") == "0",
    reason="encoded execution disabled via DF_QUERY_ENCODED=0")

T0 = 1_792_000_000_000_000_000  # ns, the epoch of a recent job
OPS = ["", "fusion.1", "fusion.2", "dot.3", "copy.4", "all-reduce.5",
       "while.6"]
MODULES = ["jit_train_step", "jit_init", "jit_eval"]

SQLS = {
    "sum_count_group_by": (
        "SELECT hlo_op, Sum(duration_ns) AS d, Count() AS n "
        "FROM tpu_hlo_span WHERE hlo_op != '' AND time >= {t0} "
        "AND time < {t1} GROUP BY hlo_op"),
    "time_per_second": (
        "SELECT time(time, 1) AS t, Sum(duration_ns) AS d "
        "FROM tpu_hlo_span WHERE hlo_op != '' AND device_id = 0 "
        "AND time >= {t0} AND time < {t1} GROUP BY t ORDER BY t"),
    "top_ops": (
        "SELECT hlo_op, Sum(duration_ns) AS d FROM tpu_hlo_span "
        "WHERE hlo_op != '' AND time >= {t0} AND time < {t1} "
        "GROUP BY hlo_op ORDER BY d DESC LIMIT 3"),
    "inclusive_end": (
        "SELECT hlo_module, hlo_op, Sum(bytes_accessed) AS b FROM "
        "tpu_hlo_span WHERE time >= {t0} AND time <= {t1} "
        "GROUP BY hlo_module, hlo_op ORDER BY b DESC LIMIT 4"),
    "count_only": (
        "SELECT Count() AS n FROM tpu_hlo_span "
        "WHERE time >= {t0} AND time < {t1}"),
    "live_tail": (
        "SELECT run_id, time, duration_ns FROM tpu_hlo_span "
        "WHERE device_id = 0 AND hlo_op = '' AND time >= {t0} "
        "AND time < {t1}"),
}


def _db(chunk_rows: int) -> Database:
    return Database(chunk_rows=chunk_rows)


def _spans(rng: random.Random, n: int, t_lo: int, run0: int) -> dict:
    """n span rows whose times fall in [t_lo, t_lo + 2 s), out of
    order within the batch, so sealed chunks overlap in time."""
    t = np.array(sorted(t_lo + rng.randrange(2_000_000_000)
                        for _ in range(n)), dtype=np.uint64)
    swap = rng.sample(range(n), k=n // 5)
    t[swap] = t[sorted(swap)]
    return {
        "time": t,
        "duration_ns": np.array([rng.choice((0, rng.randrange(1, 10**7)))
                                 for _ in range(n)], dtype=np.uint64),
        "device_id": np.array([rng.randrange(2) for _ in range(n)],
                              dtype=np.uint16),
        "kind": np.array([rng.randrange(len(TPU_SPAN_KINDS))
                          for _ in range(n)], dtype=np.uint16),
        "hlo_module": [rng.choice(MODULES) for _ in range(n)],
        "hlo_op": [rng.choice(OPS) for _ in range(n)],
        "hlo_category": [rng.choice(("", "matmul", "loop"))
                         for _ in range(n)],
        "bytes_accessed": np.array([rng.choice((0, rng.randrange(1, 10**9)))
                                    for _ in range(n)], dtype=np.uint64),
        "run_id": np.arange(run0, run0 + n, dtype=np.uint32),
    }


def _feed(db, rng, batches: int, size=(40, 160), t_lo=T0):
    """Append batches of spans, 1 s of job time apart; -> next t_lo."""
    table = db.table("profile.tpu_hlo_span")
    mem = db.table("profile.tpu_memory")
    for _ in range(batches):
        n = rng.randrange(*size)
        table.append_columns(_spans(rng, n, t_lo, len(table)), n)
        mem.append_rows([{"time": t_lo + i * 400_000_000, "device_id": d,
                          "bytes_in_use": rng.randrange(1, 10**9),
                          "peak_bytes_in_use": 10**9,
                          "bytes_limit": 16 * 10**9,
                          "largest_free_block": 10**8}
                         for i in range(3) for d in range(2)])
        t_lo += 1_000_000_000
    return t_lo


def _times(table) -> np.ndarray:
    return np.sort(table.column_concat(["time"])["time"]).astype(np.int64)


def _ranges(rng: random.Random, table) -> list:
    """(t0, t1) pairs: t1 beyond the newest row, inside the data and
    equal to a row's time; t0 before and inside the data."""
    t = _times(table)
    lo, hi = int(t[0]), int(t[-1])
    starts = [lo - rng.randrange(1, 10**9), lo,
              int(t[rng.randrange(len(t))])]
    ends = [hi + 1, hi + rng.randrange(2, 10**10),
            int(t[rng.randrange(len(t))]),
            int(t[rng.randrange(len(t))]) + rng.randrange(-5, 5)]
    return [(a, b) for a in starts for b in ends]


def _canon(res) -> tuple:
    return (list(res.columns), [list(r) for r in res.values])


def _reference(table, sql: str, monkeypatch) -> tuple:
    monkeypatch.setenv("DF_QUERY_CACHE", "0")
    try:
        return _canon(QueryCache().execute(table, sql))
    finally:
        monkeypatch.delenv("DF_QUERY_CACHE")


@pytest.mark.parametrize("seed", [7, 2**31 + 11, 123456789])
def test_cached_answers_equal_execute(seed, monkeypatch):
    """Random appends seal several chunks; after each, every query
    shape over random ranges answers through the cache exactly as
    engine.execute does with the cache off."""
    rng = random.Random(seed)
    db = _db(chunk_rows=256)
    table = db.table("profile.tpu_hlo_span")
    qc = QueryCache()
    t_lo = T0
    for _step in range(6):
        t_lo = _feed(db, rng, rng.randrange(1, 4), t_lo=t_lo)
        for t0, t1 in rng.sample(_ranges(rng, table), 6):
            for name, tmpl in SQLS.items():
                sql = tmpl.format(t0=t0, t1=t1)
                got = _canon(qc.execute(table, sql))
                assert got == _reference(table, sql, monkeypatch), \
                    (name, t0 - T0, t1 - T0)
    c = qc.counters
    assert c["range_normalized"] > 0 and c["hits"] > 0
    assert c["chunk_hits"] > 0 and c["chunk_misses"] > 0
    assert c["bucket_misses"] > 0, "ends inside the data take the " \
        "bucket path"
    assert len(table._chunks) >= 4


@pytest.mark.parametrize("route", ["tpu_flame", "tpu_memory"])
def test_flame_and_memory_routes_equal_uncached(route, monkeypatch):
    from deepflow_tpu.server.querier import QuerierAPI
    rng = random.Random(5)
    db = _db(chunk_rows=200)
    api = QuerierAPI(db)
    t_lo = T0
    for _step in range(4):
        t_lo = _feed(db, rng, 2, t_lo=t_lo)
        for t0, t1 in rng.sample(_ranges(rng, db.table(
                "profile.tpu_hlo_span")), 5):
            body = {"time_start": t0, "time_end": t1}
            got = getattr(api, route)(dict(body))
            monkeypatch.setenv("DF_QUERY_CACHE", "0")
            want = getattr(api, route)(dict(body))
            monkeypatch.delenv("DF_QUERY_CACHE")
            assert got == want, (route, t0 - T0, t1 - T0)
    assert api.query_cache.counters["chunk_hits"] > 0


@pytest.mark.parametrize("when", ["before_snapshot", "after_snapshot"])
def test_rows_racing_a_fill_are_never_served(when, monkeypatch):
    """Rows appended between the token read and the fill make the entry
    stale: the next lookup recomputes and sees them."""
    rng = random.Random(3)
    db = _db(chunk_rows=128)
    table = db.table("profile.tpu_hlo_span")
    t_lo = _feed(db, rng, 4)
    qc = QueryCache()
    sql = SQLS["sum_count_group_by"]
    tail = SQLS["live_tail"]
    orig = table.chunk_units
    raced = []

    def racing():
        if when == "after_snapshot":
            snap = orig()
        if not raced:
            raced.append(1)
            n = 50
            table.append_columns(_spans(rng, n, t_lo, 10**6), n)
        return orig() if when == "before_snapshot" else snap

    monkeypatch.setattr(table, "chunk_units", racing)
    end = t_lo + 10**12
    for tmpl in (sql, tail):
        raced.clear()
        qc.execute(table, tmpl.format(t0=T0, t1=end))
        hits = qc.counters["hits"]
        got = _canon(qc.execute(table, tmpl.format(t0=T0, t1=end + 1)))
        assert qc.counters["hits"] == hits, "a racing write was served"
        assert got == _reference(table, tmpl.format(t0=T0, t1=end + 1),
                                 monkeypatch)


def test_a_miss_scans_only_live_rows_and_new_chunks():
    rng = random.Random(11)
    db = _db(chunk_rows=1000)
    table = db.table("profile.tpu_hlo_span")
    t_lo = T0
    for _ in range(5):  # five sealed chunks
        table.append_columns(_spans(rng, 1000, t_lo, len(table)), 1000)
        t_lo += 1_000_000_000
    table.append_columns(_spans(rng, 300, t_lo, len(table)), 300)
    qc = QueryCache()
    sql = SQLS["top_ops"]
    now = t_lo + 10**10

    def ask():
        nonlocal now
        now += 1_000_000
        return qc.execute(table, sql.format(t0=T0, t1=now))

    ask()
    c = qc.counters
    assert c["chunk_misses"] == 5 and c["misses"] == 1
    for i in range(4):  # the end moves, nothing is written
        ask()
        assert c["hits"] == i + 1
    assert c["range_normalized"] == 5

    table.append_columns(_spans(rng, 100, t_lo, len(table)), 100)
    before = c["rows_scanned"]
    ask()
    assert c["stale"] == 1 and c["chunk_misses"] == 5
    assert c["chunk_hits"] == 5
    assert c["rows_scanned"] - before <= 400, "a miss scans live rows"

    # seal a sixth chunk: it is scanned once, then served from cache
    table.append_columns(_spans(rng, 700, t_lo, len(table)), 700)
    assert len(table._chunks) == 6
    ask()
    assert c["chunk_misses"] == 6
    for _ in range(3):
        table.append_columns(_spans(rng, 10, t_lo, len(table)), 10)
        ask()
    assert c["chunk_misses"] == 6, "each sealed chunk scanned once"


def test_standing_fold_agrees_with_adhoc_query(monkeypatch):
    rng = random.Random(13)
    db = _db(chunk_rows=200)
    table = db.table("profile.tpu_hlo_span")
    qc = QueryCache()
    t_lo = T0
    for _ in range(4):
        t_lo = _feed(db, rng, 2, t_lo=t_lo)
        sql = SQLS["time_per_second"].format(t0=T0, t1=t_lo + 10**9)
        res, stats = qc.standing_fold(table, sql)
        assert res is not None and stats["buckets"] >= 1
        adhoc = _canon(qc.execute(table, sql))
        assert _canon(res) == adhoc == _reference(table, sql, monkeypatch)


def test_trim_and_dictionary_compaction_invalidate(monkeypatch):
    rng = random.Random(17)
    db = _db(chunk_rows=150)
    table = db.table("profile.tpu_hlo_span")
    t_lo = _feed(db, rng, 6)
    qc = QueryCache()
    sql = SQLS["sum_count_group_by"].format(t0=T0 - 1, t1=t_lo + 10**9)
    qc.execute(table, sql)
    table.trim_before("time", T0 + 2_500_000_000)
    assert _canon(qc.execute(table, sql)) == \
        _reference(table, sql, monkeypatch)
    stats = table.compact_dictionaries(min_entries=1, max_live_frac=1.0)
    assert stats, "compaction rebuilt a dictionary"
    assert _canon(qc.execute(table, sql)) == \
        _reference(table, sql, monkeypatch)


def test_tier_segments_fold_as_sealed_chunks(tmp_path, monkeypatch):
    """A flush moves sealed rows into a segment, a unit with a serial of
    its own: answers stay exact across the move, and a segment's
    partial is reused once computed."""
    rng = random.Random(19)
    db = Database(data_dir=str(tmp_path), chunk_rows=150, storage=True)
    table = db.table("profile.tpu_hlo_span")
    qc = QueryCache()
    t_lo = T0
    for _ in range(3):
        t_lo = _feed(db, rng, 3, t_lo=t_lo)
        for _ in range(2):
            sql = SQLS["top_ops"].format(t0=T0, t1=t_lo + 10**9)
            assert _canon(qc.execute(table, sql)) == \
                _reference(table, sql, monkeypatch)
            db.flush_to_tier()
    assert table.tier is not None and table.tier.segment_count() >= 3
    misses = qc.counters["chunk_misses"]
    table.append_columns(_spans(rng, 20, t_lo, 10**6), 20)
    sql = SQLS["top_ops"].format(t0=T0, t1=t_lo + 10**10)
    assert _canon(qc.execute(table, sql)) == \
        _reference(table, sql, monkeypatch)
    assert qc.counters["chunk_misses"] == misses
    assert qc.counters["chunk_hits"] >= 3


def test_split_range_forms():
    db = _db(chunk_rows=100)
    table = db.table("profile.tpu_hlo_span")
    q = S.parse("SELECT Count() AS n FROM tpu_hlo_span WHERE time > 5 "
                "AND hlo_op != '' AND time <= 9 AND time >= 3")
    shape, lo, hi = split_range(table, q)
    assert (lo, hi) == (6, 10)
    assert shape.where == S.BinOp("!=", S.Col("hlo_op"), S.Lit(""))
    # float literals and non-time columns stay in the shape
    q2 = S.parse("SELECT Count() AS n FROM tpu_hlo_span "
                 "WHERE time < 5.5 AND duration_ns > 3")
    assert split_range(table, q2) == (q2, None, None)
    assert engine.split_conjuncts(q2.where)[0].right.value == 5.5
