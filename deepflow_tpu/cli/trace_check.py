"""trace-check: brief e2e run proving dogfooded query tracing works.

Spins a real 3-shard cluster in-process, runs a federated DF-SQL query,
then fails (exit 1) unless:

  * the query stitches into exactly ONE trace retrievable through the
    system's own Tempo API, naming the coordinator, every shard's
    `shard.exec` and at least one prune decision, with shard spans
    parented under their own coordinator `shard.call` span,
  * the federated result is byte-identical with tracing on and off,
  * `EXPLAIN ANALYZE` stage wall times sum to within 20% of the
    measured end-to-end latency,
  * every node's `query.trace` hop ledger conserves
    (emitted == delivered + dropped + in_flight), and
  * `DF_QUERY_TRACE=0` really kills the writer (no new spans).

Wired as `make trace-check` — cheap enough for CI, real enough to catch
a hop that stops propagating context or a span writer that changes
query results.
"""

from __future__ import annotations

import json
import os
import sys
import time
import urllib.parse
import urllib.request


def _fail(msg: str) -> None:
    print(f"trace-check: FAIL: {msg}")
    sys.exit(1)


def _post(port: int, path: str, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=15) as resp:
        return json.loads(resp.read())


def _get(port: int, path: str, params: dict | None = None) -> dict:
    q = ("?" + urllib.parse.urlencode(params)) if params else ""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}{q}", timeout=15) as resp:
        return json.loads(resp.read())


def _canon(x) -> str:
    return json.dumps(x, sort_keys=True)


def _check_ledger(where: str, led: dict) -> None:
    if led["emitted"] != (led["delivered"] + led["dropped_total"]
                          + led["in_flight"]):
        _fail(f"{where}: query.trace ledger does not conserve: {led}")


def main() -> int:
    from deepflow_tpu.query import engine
    from deepflow_tpu.server import Server

    os.environ["DF_QUERY_TRACE"] = "1"
    os.environ["DF_QUERY_TRACE_SAMPLE"] = "1"
    os.environ["DF_QUERY_CACHE"] = "0"

    rows = [{"time": 10 ** 9 * (1000 + i),
             "app_service": f"svc-{i % 4}", "endpoint": f"/e{i % 7}",
             "response_duration": 10 * i, "response_code": 200}
            for i in range(240)]
    sql = ("SELECT app_service, Count(*) AS n, Sum(response_duration) "
           "AS s, Avg(response_duration) AS a FROM l7_flow_log "
           "GROUP BY app_service ORDER BY app_service")

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
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if len(seed.api.federation.remote_peers()) == 2:
                break
            time.sleep(0.05)
        if len(seed.api.federation.remote_peers()) != 2:
            _fail("joiners never registered with the seed")

        # -- byte identity: tracing off, then on --------------------------
        os.environ["DF_QUERY_TRACE"] = "0"
        off = _post(seed.query_port, "/v1/query",
                    {"db": "flow_log", "sql": sql})
        n_off = len(seed.db.table("deepflow_system.query_trace"))
        seed.api.qtracer.flush()
        if len(seed.db.table("deepflow_system.query_trace")) != n_off:
            _fail("kill-switch DF_QUERY_TRACE=0 still wrote spans")
        os.environ["DF_QUERY_TRACE"] = "1"
        on = _post(seed.query_port, "/v1/query",
                   {"db": "flow_log", "sql": sql})
        if on["federation"]["shards"] != 3 or \
                on["federation"]["missing_shards"]:
            _fail(f"federation incomplete: {on['federation']}")
        if _canon(off["result"]) != _canon(on["result"]):
            _fail("tracing changed the federated query result")
        print(f"trace-check: byte-identical federated result over "
              f"{sum(r[1] for r in on['result']['values'])} rows, "
              f"kill-switch honored")

        # -- one stitched trace through the Tempo API ---------------------
        for s in shards:
            s.api.qtracer.flush()
        res = engine.execute(
            seed.db.table("deepflow_system.query_trace"),
            "SELECT trace_id, span_id, parent_span_id, name FROM t")
        tids = {v[0] for v in res.values
                if v[2] == "" and v[3] == "http.request"}
        if len(tids) != 1:
            _fail(f"expected exactly one root trace, got {len(tids)}")
        tid = tids.pop()
        calls = {v[1] for v in res.values
                 if v[0] == tid and v[3] == "shard.call"}
        if len(calls) != 2:
            _fail(f"expected 2 shard.call spans, got {len(calls)}")
        for s in shards[1:]:
            r = engine.execute(
                s.db.table("deepflow_system.query_trace"),
                "SELECT trace_id, parent_span_id, name FROM t")
            execs = [v for v in r.values
                     if v[0] == tid and v[2] == "shard.exec"]
            if not execs:
                _fail(f"shard {s.api.shard_id}: no shard.exec in trace")
            if not all(v[1] in calls for v in execs):
                _fail(f"shard {s.api.shard_id}: shard.exec not parented "
                      "under a coordinator shard.call")

        tr = _get(seed.query_port, f"/api/traces/{tid}")
        spans = tr["batches"][0]["spans"]
        names = {sp["operationName"] for sp in spans}
        services = {sp["serviceName"] for sp in spans}
        need = {"http.request", "query", "scatter", "shard.call",
                "shard.exec", "merge"}
        if not need <= names:
            _fail(f"Tempo trace missing spans: {sorted(need - names)}")
        if not any(n.startswith("prune") for n in names):
            _fail("no prune decision span in the trace")
        want_svcs = {f"deepflow-querier-{i}" for i in (1, 2, 3)}
        if not want_svcs <= services:
            _fail(f"trace missing shard services: "
                  f"{sorted(want_svcs - services)}")
        roots = [sp for sp in spans if sp["parentSpanID"] == ""]
        if len(roots) != 1:
            _fail(f"Tempo trace has {len(roots)} roots, want 1")
        now_s = int(time.time())
        found = _get(seed.query_port, "/api/search",
                     {"start": now_s - 3600, "end": now_s + 3600,
                      "limit": 100})
        if tid not in {t["traceID"] for t in found["traces"]}:
            _fail("Tempo search does not surface the query trace")
        print(f"trace-check: ONE stitched trace {tid} "
              f"({len(spans)} spans across {len(services)} services), "
              f"searchable via /api/search")

        # -- flame rendering ----------------------------------------------
        from deepflow_tpu.query.flamegraph import (build_flame_tree,
                                                   trace_flame_stacks)
        tree = _post(seed.query_port, "/v1/trace/Tracing",
                     {"trace_id": tid})["result"]
        stacks, values = trace_flame_stacks(tree)
        flame = build_flame_tree(stacks, values)
        if flame.total_value <= 0 or "shard.exec" not in "\n".join(stacks):
            _fail("flame assembler could not render the query trace")

        # -- EXPLAIN ANALYZE stage accounting ------------------------------
        ex = _post(seed.query_port, "/v1/query",
                   {"db": "flow_log",
                    "sql": f"EXPLAIN ANALYZE {sql}"})["explain"]
        stage_sum = sum(st["wall_ms"] for st in ex["stages"])
        total = ex["total_ms"]
        if total <= 0:
            _fail("EXPLAIN ANALYZE total_ms <= 0")
        gap = abs(stage_sum - total) / total
        if gap > 0.20:
            _fail(f"EXPLAIN ANALYZE stages ({stage_sum:.3f}ms) vs "
                  f"e2e ({total:.3f}ms): {gap:.0%} gap > 20%")
        print(f"trace-check: EXPLAIN ANALYZE stages {stage_sum:.3f}ms "
              f"vs e2e {total:.3f}ms ({gap:.1%} gap)")

        # -- conserved ledgers everywhere ----------------------------------
        for s in shards:
            h = _get(s.query_port, "/v1/health")
            qt = h.get("query_trace")
            if qt is None:
                _fail(f"shard {s.api.shard_id}: no query_trace health "
                      "block")
            _check_ledger(f"shard {s.api.shard_id}", qt["ledger"])
            if qt["ledger"]["in_flight"] != qt["pending"]:
                _fail(f"shard {s.api.shard_id}: in_flight "
                      f"{qt['ledger']['in_flight']} != pending "
                      f"{qt['pending']}")
        print("trace-check: query.trace ledgers conserve on all 3 shards")
        print("trace-check: OK")
        return 0
    finally:
        for s in shards:
            s.stop()


if __name__ == "__main__":
    sys.exit(main())
