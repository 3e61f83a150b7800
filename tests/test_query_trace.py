"""Dogfooded query tracing: span trees, cross-shard stitching, EXPLAIN.

Acceptance tests for the query-trace PR: a federated 3-shard DF-SQL
query must stitch into exactly ONE trace readable through the system's
own Tempo API, tracing must never change query results, the
``query.trace`` hop ledger must conserve like every frame hop, and
EXPLAIN ANALYZE stage timings must account for the observed end-to-end
latency.
"""

import json
import threading
import time
import urllib.parse
import urllib.request

import pytest

from deepflow_tpu.query import qtrace
from deepflow_tpu.query.flamegraph import build_flame_tree, trace_flame_stacks
from deepflow_tpu.telemetry import Telemetry


def _get(port: int, path: str, params: dict | None = None) -> dict:
    q = ("?" + urllib.parse.urlencode(params)) if params else ""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}{q}", timeout=10) as resp:
        return json.loads(resp.read())


def _post(port: int, path: str, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())


def _canon(x) -> str:
    return json.dumps(x, sort_keys=True)


# -- unit: tracer core -------------------------------------------------------

def test_span_tree_shapes_and_parenting():
    tr = qtrace.QueryTracer(Telemetry(), service="svc-t", shard_id=7,
                            sink=None)
    with tr.start_trace("query", kind="sql", capture=True) as root:
        with qtrace.span("plan"):
            pass
        with qtrace.span("execute") as ex:
            ex.annotate(rows=3)
            with qtrace.span("scan t"):
                qtrace.bump("segcache_hits")
    spans = {d["name"]: d for d in root.trace_spans()}
    assert set(spans) == {"query", "plan", "execute", "scan t"}
    assert spans["query"]["parent_span_id"] == ""
    assert spans["plan"]["parent_span_id"] == spans["query"]["span_id"]
    assert spans["execute"]["parent_span_id"] == spans["query"]["span_id"]
    assert spans["scan t"]["parent_span_id"] == spans["execute"]["span_id"]
    assert spans["execute"]["attrs"]["rows"] == 3
    assert spans["scan t"]["attrs"]["segcache_hits"] == 1
    assert all(d["service"] == "svc-t" for d in spans.values())
    assert len({d["trace_id"] for d in spans.values()}) == 1
    # no active trace afterwards: instrumentation reverts to no-ops
    assert not qtrace.active()
    assert qtrace.span("orphan") is qtrace._NULL_SPAN


def test_ledger_conservation_with_sampling_and_sink_errors(monkeypatch):
    """emitted == delivered + dropped(reason) + in_flight on the
    query.trace hop — the same conservation law test_selfmon proves for
    frame hops, here for spans across keep/sample-out/sink-error."""
    monkeypatch.setenv("DF_QUERY_TRACE", "1")
    monkeypatch.setenv("DF_QUERY_TRACE_SAMPLE", "2")
    monkeypatch.setenv("DF_QUERY_TRACE_SLOW_MS", "60000")
    fail = {"on": False}
    written = []

    def sink(spans):
        if fail["on"]:
            raise OSError("disk gone")
        written.extend(spans)

    tel = Telemetry()
    tr = qtrace.QueryTracer(tel, service="svc", shard_id=1, sink=sink)
    for _ in range(40):
        with tr.start_trace("query"):
            with qtrace.span("execute"):
                pass
    tr.flush()
    snap = tr.snapshot()
    led = snap["ledger"]
    assert led["emitted"] == 80  # 40 traces x 2 spans
    assert led["dropped"].get("sampled_out", 0) > 0
    assert led["emitted"] == (led["delivered"] + led["dropped_total"]
                              + led["in_flight"])
    assert led["in_flight"] == snap["pending"] == 0
    n_ok = len(written)
    assert n_ok == led["delivered"]

    fail["on"] = True
    with tr.start_trace("query", trace_id="00" * 16):  # head-kept (h%2==0)
        pass
    assert tr.flush() == 0
    led = tr.snapshot()["ledger"]
    # the failed batch moved delivered -> dropped(sink_error): conserved
    assert led["dropped"].get("sink_error", 0) >= 1
    assert led["emitted"] == (led["delivered"] + led["dropped_total"]
                              + led["in_flight"])
    fail["on"] = False


def test_kill_switch_and_tail_keep(monkeypatch):
    monkeypatch.setenv("DF_QUERY_TRACE", "0")
    tr = qtrace.QueryTracer(Telemetry(), sink=None)
    with tr.start_trace("query") as root:
        assert root is qtrace._NULL_SPAN
        assert qtrace.span("x") is qtrace._NULL_SPAN
    assert tr.snapshot()["traces"] == 0
    assert tr.snapshot()["enabled"] is False

    # tail sampling: a sampled-out trace is upgraded when it errors
    monkeypatch.setenv("DF_QUERY_TRACE", "1")
    monkeypatch.setenv("DF_QUERY_TRACE_SAMPLE", "1000000")
    kept = []
    tr = qtrace.QueryTracer(Telemetry(), sink=kept.extend)
    with tr.start_trace("query"):
        pass
    with pytest.raises(ValueError):
        with tr.start_trace("query"):
            raise ValueError("boom")
    tr.flush()
    assert {d["status"] for d in kept} == {"error"}, \
        "errored trace must be tail-kept, quiet one sampled out"


def test_worker_thread_reattaches_via_use_buf():
    tr = qtrace.QueryTracer(Telemetry(), sink=None)
    out = {}
    with tr.start_trace("query", capture=True) as root:
        with qtrace.span("execute") as ex:
            buf, sid = qtrace.current_buf(), qtrace.current_span_id()

            def worker():
                with qtrace.use_buf(buf, sid):
                    with qtrace.span("morsel"):
                        qtrace.annotate(part=1)
                out["done"] = True

            th = threading.Thread(target=worker)
            th.start()
            th.join()
        assert out["done"]
    spans = {d["name"]: d for d in root.trace_spans()}
    assert spans["morsel"]["parent_span_id"] == spans["execute"]["span_id"]


def test_wire_ctx_adopt_joins_trace():
    from deepflow_tpu.cluster import wire
    tr_a = qtrace.QueryTracer(Telemetry(), service="coord", sink=None)
    tr_b = qtrace.QueryTracer(Telemetry(), service="shard", sink=None)
    with tr_a.start_trace("query", capture=True) as root:
        with qtrace.span("shard.call") as call:
            body = wire.inject_ctx({"op": "sql"})
            call_sid = call.to_dict(call._buf)["span_id"]
        # shard side: a different tracer (different process in prod)
        ctx = wire.extract_ctx(body)
        with tr_b.start_trace("unused", capture=True):
            pass  # an unrelated active trace must not confuse adopt
        with tr_b.adopt(ctx, "shard.exec") as sexec:
            sdict = sexec.to_dict(sexec._buf)
    assert sdict["trace_id"] == root.trace_id
    assert sdict["parent_span_id"] == call_sid
    # a body without ctx (old coordinator) is a traced no-op
    assert tr_b.adopt(wire.extract_ctx({"op": "sql"}), "shard.exec") \
        is qtrace._NULL_SPAN


def test_rows_roundtrip():
    tr = qtrace.QueryTracer(Telemetry(), sink=None)
    with tr.start_trace("query", capture=True, kind="sql") as root:
        with qtrace.span("execute", rows=5):
            pass
    spans = root.trace_spans()
    back = qtrace.spans_from_rows(qtrace.rows_from_spans(spans))
    a = {d["span_id"]: d for d in spans}
    b = {d["span_id"]: d for d in back}
    assert set(a) == set(b)
    for sid, d in b.items():
        assert d["trace_id"] == a[sid]["trace_id"]
        assert d["name"] == a[sid]["name"]
        assert d["start_ns"] == a[sid]["start_ns"]
        assert d["duration_ns"] == a[sid]["duration_ns"]
        assert d["attrs"] == {k: v for k, v in a[sid]["attrs"].items()}
        assert d["kind"] == "query"


# -- segcache fetch spans ----------------------------------------------------

def test_segcache_fetch_and_hit_land_in_trace(tmp_path):
    from types import SimpleNamespace

    from deepflow_tpu.store import objstore as objstore_mod
    from deepflow_tpu.store.db import Database
    from deepflow_tpu.store.objstore import ObjStore, SegmentPublisher
    from deepflow_tpu.store.segcache import SegmentCache

    tbl = "flow_log.l7_flow_log"
    db = Database(data_dir=str(tmp_path / "ing"), shard_id=1, storage=True)
    db.table(tbl).append_rows(
        [{"time": 1000 + i, "flow_id": i} for i in range(8)])
    assert db.flush_to_tier() == 8
    SegmentPublisher(ObjStore(str(tmp_path / "obj")), 1) \
        .publish(db.tier_store)
    store = ObjStore(str(tmp_path / "obj"))
    doc = store.get_pointer(objstore_mod.pointer_name(1))
    seg = doc["tables"][tbl]["segments"][0]
    cache = SegmentCache(str(tmp_path / "cache"), store)
    rseg = SimpleNamespace(key=(1, tbl, seg["fn"]), shard=1, table=tbl,
                           fn=seg["fn"])

    class _Holder:
        pass

    tr = qtrace.QueryTracer(Telemetry(), sink=None)
    holder = _Holder()
    with tr.start_trace("query", capture=True) as root:
        with qtrace.span("scan"):
            cache.pin(rseg, holder)   # cold: fetch span
            cache.pin(rseg, holder)   # warm: hit bump
    spans = {d["name"]: d for d in root.trace_spans()}
    assert "segcache.fetch" in spans
    assert spans["segcache.fetch"]["parent_span_id"] \
        == spans["scan"]["span_id"]
    assert spans["segcache.fetch"]["attrs"]["table"] == tbl
    assert spans["scan"]["attrs"]["segcache_hits"] == 1


# -- server: EXPLAIN / EXPLAIN ANALYZE ---------------------------------------

@pytest.fixture
def solo_server(monkeypatch):
    from deepflow_tpu.server import Server
    monkeypatch.setenv("DF_QUERY_TRACE", "1")
    monkeypatch.setenv("DF_QUERY_TRACE_SAMPLE", "1")
    s = Server(host="127.0.0.1", ingest_port=0, query_port=0,
               sync_port=0).start()
    rows = [{"time": 10 ** 9 * (1000 + i), "app_service": f"svc-{i % 5}",
             "endpoint": f"/e{i % 17}", "response_duration": 10 * i,
             "response_code": 200 + (i % 3)} for i in range(3000)]
    s.db.table("flow_log.l7_flow_log").append_rows(rows)
    yield s
    s.stop()


def test_explain_analyze_stage_sum_within_20pct(solo_server):
    s = solo_server
    out = _post(s.query_port, "/v1/query", {
        "db": "flow_log",
        "sql": "EXPLAIN ANALYZE SELECT app_service, Count(*) AS n, "
               "Sum(response_duration) AS d FROM l7_flow_log "
               "GROUP BY app_service ORDER BY app_service"})
    ex = out["explain"]
    assert ex["analyze"] is True and ex["trace_id"]
    assert ex["plan"]["table"] == "flow_log.l7_flow_log"
    assert "prune" in ex["plan"]
    stage_sum = sum(st["wall_ms"] for st in ex["stages"])
    assert ex["total_ms"] > 0
    assert abs(stage_sum - ex["total_ms"]) / ex["total_ms"] <= 0.20, \
        (stage_sum, ex["total_ms"], ex["stages"])
    # observed stage timings feed the planner cost model
    cm = s.api.stage_cost.snapshot()
    assert cm["ns_per_row"]["plan"] is not None
    assert cm["ns_per_row"]["execute"] is not None
    # result rows come back alongside the plan
    cols = out["result"]["columns"]
    assert cols == ["stage", "wall_ms", "cpu_ms", "detail"]


def test_explain_plain_is_plan_only(solo_server):
    s = solo_server
    out = _post(s.query_port, "/v1/query", {
        "db": "flow_log",
        "sql": "EXPLAIN SELECT Count(*) FROM l7_flow_log"})
    ex = out["explain"]
    assert ex["analyze"] is False
    assert ex["plan"]["table"] == "flow_log.l7_flow_log"
    assert "rows_returned" not in ex
    # EXPLAIN is a soft keyword: a column named explain still works
    t = solo_server.db.table("deepflow_system.query_trace")
    assert t is not None


def test_results_byte_identical_tracing_on_off(solo_server, monkeypatch):
    s = solo_server
    sql = ("SELECT app_service, Count(*) AS n, Avg(response_duration) "
           "AS a FROM l7_flow_log GROUP BY app_service "
           "ORDER BY app_service")
    monkeypatch.setenv("DF_QUERY_CACHE", "0")
    monkeypatch.setenv("DF_QUERY_TRACE", "0")
    off = _post(s.query_port, "/v1/query", {"db": "flow_log", "sql": sql})
    monkeypatch.setenv("DF_QUERY_TRACE", "1")
    on = _post(s.query_port, "/v1/query", {"db": "flow_log", "sql": sql})
    assert _canon(off["result"]) == _canon(on["result"])
    # off really was off; on really wrote spans. The request's root
    # span closes just after the response is written, so poll for it
    from deepflow_tpu.query import engine
    deadline = time.monotonic() + 5
    while True:
        s.api.qtracer.flush()
        res = engine.execute(s.db.table("deepflow_system.query_trace"),
                             "SELECT name, status FROM t")
        seen = {(v[0], v[1]) for v in res.values}
        if ("execute", "ok") in seen or time.monotonic() > deadline:
            break
        time.sleep(0.02)
    assert ("execute", "ok") in seen


def test_health_query_trace_block(solo_server):
    s = solo_server
    _post(s.query_port, "/v1/query",
          {"db": "flow_log",
           "sql": "SELECT Count(*) FROM l7_flow_log"})
    h = _get(s.query_port, "/v1/health")
    qt = h["query_trace"]
    assert qt["enabled"] is True
    assert qt["traces"] >= 1 and qt["spans"] >= 1
    led = qt["ledger"]
    assert led["hop"] == "query.trace"
    assert led["emitted"] == (led["delivered"] + led["dropped_total"]
                              + led["in_flight"])
    assert led["in_flight"] == qt["pending"]


# -- cluster: one stitched trace, read back through the Tempo API ------------

def test_federated_query_stitches_one_trace(monkeypatch):
    from deepflow_tpu.server import Server
    monkeypatch.setenv("DF_QUERY_TRACE", "1")
    monkeypatch.setenv("DF_QUERY_TRACE_SAMPLE", "1")
    monkeypatch.setenv("DF_QUERY_CACHE", "0")

    rows = [{"time": 10 ** 9 * (1000 + i), "app_service": f"svc-{i % 3}",
             "endpoint": f"/e{i}", "response_duration": 10 * i,
             "response_code": 200} for i in range(24)]
    sql = ("SELECT app_service, Count(*) AS n, Sum(response_duration) "
           "AS s FROM l7_flow_log GROUP BY app_service "
           "ORDER BY app_service")

    # 1-shard reference run, tracing OFF: the byte-identity baseline
    monkeypatch.setenv("DF_QUERY_TRACE", "0")
    solo = Server(host="127.0.0.1", ingest_port=0, query_port=0,
                  sync_port=0).start()
    try:
        solo.db.table("flow_log.l7_flow_log").append_rows(rows)
        want = _post(solo.query_port, "/v1/query",
                     {"db": "flow_log", "sql": sql})["result"]
    finally:
        solo.stop()

    monkeypatch.setenv("DF_QUERY_TRACE", "1")
    seed = Server(host="127.0.0.1", ingest_port=0, query_port=0,
                  sync_port=0, shard_id=1, cluster_advertise="").start()
    shards = [seed]
    try:
        seed_addr = f"127.0.0.1:{seed.query_port}"
        for sid in (2, 3):
            shards.append(Server(
                host="127.0.0.1", ingest_port=0, query_port=0,
                sync_port=0, shard_id=sid,
                cluster_seed=seed_addr).start())
        for i, row in enumerate(rows):
            shards[i % 3].db.table("flow_log.l7_flow_log") \
                .append_rows([row])
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if len(seed.api.federation.remote_peers()) == 2:
                break
            time.sleep(0.05)
        assert len(seed.api.federation.remote_peers()) == 2

        got = _post(seed.query_port, "/v1/query",
                    {"db": "flow_log", "sql": sql})
        assert got["federation"]["shards"] == 3
        assert _canon(got["result"]) == _canon(want), \
            "tracing must not change federated results"

        for s in shards:
            s.api.qtracer.flush()
        from deepflow_tpu.query import engine
        res = engine.execute(
            seed.db.table("deepflow_system.query_trace"),
            "SELECT trace_id, parent_span_id, name FROM t")
        # the HTTP request is the root; the query tree hangs under it
        tids = {v[0] for v in res.values if v[1] == ""
                and v[2] == "http.request"}
        assert len(tids) == 1, "exactly one coordinator root trace"
        tid = tids.pop()

        # every shard executed under THIS trace, parented under its own
        # coordinator shard.call span
        res_full = engine.execute(
            seed.db.table("deepflow_system.query_trace"),
            "SELECT trace_id, span_id, parent_span_id, name FROM t")
        calls = {v[1] for v in res_full.values
                 if v[0] == tid and v[3] == "shard.call"}
        assert len(calls) == 2   # two remote peers
        for s in shards[1:]:
            r = engine.execute(
                s.db.table("deepflow_system.query_trace"),
                "SELECT trace_id, parent_span_id, name FROM t")
            execs = [v for v in r.values
                     if v[0] == tid and v[2] == "shard.exec"]
            assert execs, f"shard {s.api.shard_id} has no shard.exec"
            assert all(v[1] in calls for v in execs), \
                "shard.exec must parent under a coordinator shard.call"
            assert any(v[0] == tid and v[2].startswith("prune")
                       for v in r.values), "prune decision span missing"

        # the system's OWN Tempo API returns the stitched trace
        tr = _get(seed.query_port, f"/api/traces/{tid}")
        spans = tr["batches"][0]["spans"]
        names = {sp["operationName"] for sp in spans}
        services = {sp["serviceName"] for sp in spans}
        assert {"http.request", "query", "scatter", "shard.call",
                "shard.exec", "merge"} <= names
        assert any(n.startswith("prune") for n in names)
        assert {"deepflow-querier-1", "deepflow-querier-2",
                "deepflow-querier-3"} <= services
        roots = [sp for sp in spans if sp["parentSpanID"] == ""]
        assert len(roots) == 1 \
            and roots[0]["operationName"] == "http.request"
        query = [sp for sp in spans if sp["operationName"] == "query"]
        assert [sp["parentSpanID"] for sp in query] == [roots[0]["spanID"]]

        # Tempo search surfaces it; flamegraph assembler renders it
        now_s = int(time.time())
        sr = _get(seed.query_port, "/api/search",
                  {"start": now_s - 3600, "end": now_s + 3600,
                   "limit": 50})
        assert tid in {t["traceID"] for t in sr["traces"]}
        tree = _post(seed.query_port, "/v1/trace/Tracing",
                     {"trace_id": tid})["result"]
        stacks, values = trace_flame_stacks(tree)
        flame = build_flame_tree(stacks, values)
        assert flame.total_value > 0
        folded = "\n".join(stacks)
        assert "shard.exec" in folded and "prune" in folded

        # conserved hop ledger on the coordinator after the run
        h = _get(seed.query_port, "/v1/health")
        led = h["query_trace"]["ledger"]
        assert led["emitted"] == (led["delivered"] + led["dropped_total"]
                                  + led["in_flight"])
    finally:
        for s in shards:
            s.stop()
