"""Querier HTTP API.

Reference analog: server/querier/router/query.go:30 (POST /v1/query/) and
server/querier/profile/router/query.go:33 (POST /v1/profile/ProfileTracing).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import yaml

from deepflow_tpu.query import engine as qengine
from deepflow_tpu.query import qtrace
from deepflow_tpu.query import sql as qsql
from deepflow_tpu.query.flamegraph import profile_flame_tree
from deepflow_tpu.store.db import Database

log = logging.getLogger("df.querier")

# dd-trace agent API paths: accepted on POST and (dd default) PUT
_DD_TRACE_PATHS = ("/v0.3/traces", "/v0.4/traces")


class AuthError(Exception):
    """Missing/invalid API token on a gated endpoint (HTTP 403)."""


class QuerierAPI:
    """Route logic, separated from HTTP plumbing for in-process use."""

    def __init__(self, db: Database, stats_provider=None,
                 controller=None, exporters=None, alerts=None,
                 trace_trees=None, telemetry=None,
                 api_token: str | None = None,
                 membership=None, federation=None,
                 shard_id: int = 0, storage_provider=None,
                 rollup=None) -> None:
        self.db = db
        self.stats_provider = stats_provider or (lambda: {})
        # tiered storage health block (server._storage_stats) + the
        # rollup job whose horizons gate transparent datasource selection
        self.storage_provider = storage_provider
        self.rollup = rollup
        self.controller = controller
        self.exporters = exporters
        self.alerts = alerts
        self.trace_trees = trace_trees  # TraceTreeBuilder (optional)
        self.telemetry = telemetry  # server-side Telemetry (optional)
        # cluster federation (optional): ClusterMembership +
        # FederationCoordinator — when peers are alive, queries scatter
        # over /v1/shard/exec and merge here (see cluster/federation.py)
        self.membership = membership
        self.federation = federation
        self.shard_id = shard_id
        # shared token gating the mutating control-plane surface
        # (/v1/repo upload, the OTA `upgrade` exec). Empty/None = open:
        # the default deployment binds the querier to localhost, and the
        # trust boundary is documented in docs/SECURITY.md.
        import os as _os
        self.api_token = (api_token if api_token is not None
                          else _os.environ.get("DF_API_TOKEN", ""))
        from deepflow_tpu.server.integration import IntegrationAPI
        # combined binary: ingest shares the controller's authoritative
        # SmartEncoding allocator; standalone: process-local allocator
        self.integration = IntegrationAPI(
            db, exporters=exporters,
            prom_encoder=getattr(controller, "prom_encoder", None),
            trace_trees=trace_trees)
        from deepflow_tpu.server.mcp import McpServer
        self.mcp = McpServer(self)
        from deepflow_tpu.query.tracing_adapter import AdapterRegistry
        self.trace_adapters = AdapterRegistry()
        # result + partial-aggregate cache (query/cache.py): serves the
        # local /v1/query path and the shard half of federated scatters
        from deepflow_tpu.query.cache import QueryCache
        self.query_cache = QueryCache(telemetry=telemetry)
        # read-tier wiring (set by server/server.py when storage is
        # disaggregated): the shard-side SegmentPublisher (for the
        # publish-gen exclusion handshake), the querier-side ReadTier,
        # and the cluster-wide partial-aggregate cache
        self.publisher = None
        self.readtier = None
        self.partial_cache = None
        # background integrity scrubber (store/scrub.py), set by
        # server.py: backs /v1/fsck repair and the health scrub block
        self.scrubber = None
        # closed-loop QoS (deepflow_tpu/qos): the facade + the
        # receiver's per-tenant drop attribution, set by server.py on
        # ingest nodes (querier replicas take no agent traffic)
        self.qos = None
        self.drop_attribution = None
        # standing-query registry (query/standing.py), set by server.py:
        # backs /v1/subscribe and the push-evaluated alert path
        self.standing = None
        # zone-map pruning accounting flows into the same hop ledger the
        # rest of the pipeline reports through (query.scan hop)
        from deepflow_tpu.query import engine as _qengine
        _qengine.set_scan_telemetry(telemetry)
        # dogfooded query tracing: every served query writes its span
        # tree into deepflow_system.query_trace through this tracer
        # (query/qtrace.py); the sink is the system table itself, so the
        # Tempo API + flame assembler render the querier's own internals
        from deepflow_tpu.query import qtrace as _qtrace
        self.qtracer = _qtrace.QueryTracer(
            telemetry, service=f"deepflow-querier-{shard_id}",
            shard_id=shard_id, sink=self._qtrace_sink)
        # per-stage observed costs from EXPLAIN ANALYZE runs feed the
        # same EWMA cost-model machinery the kernel/degree choosers use
        from deepflow_tpu.query.costmodel import KernelCostModel
        self.stage_cost = KernelCostModel(
            ("parse", "plan", "execute", "scatter", "merge"))

    def _qtrace_sink(self, spans: list[dict]) -> None:
        from deepflow_tpu.query import qtrace as _qtrace
        self.db.table("deepflow_system.query_trace") \
            .append_rows(_qtrace.rows_from_spans(spans))

    def alerts_api(self, method: str, body: dict) -> dict:
        if self.alerts is None:
            raise qengine.QueryError("alerting not running")
        if method == "list":
            return {"rules": self.alerts.list()}
        if method == "upsert":
            return {"rule": self.alerts.upsert(body).to_dict()}
        if method == "delete":
            return {"deleted": self.alerts.delete(str(body.get("name", "")))}
        raise qengine.QueryError(f"unknown alerts action {method!r}")

    def exporters_api(self, body: dict) -> dict:
        if self.exporters is None:
            raise qengine.QueryError("exporters not running")
        from deepflow_tpu.server.exporters import (
            JsonLinesExporter, KafkaExporter, OtlpJsonExporter,
            RemoteWriteExporter)
        etype = body.get("type", "")
        endpoint = body.get("endpoint", "")
        if not endpoint:
            raise qengine.QueryError("endpoint required")
        if etype == "json-lines":
            exp = JsonLinesExporter(endpoint,
                                    tables=tuple(body.get("tables", [])))
        elif etype == "otlp-json":
            exp = OtlpJsonExporter(endpoint)
        elif etype == "remote-write":
            exp = RemoteWriteExporter(endpoint)
        elif etype == "kafka":
            try:
                exp = KafkaExporter(endpoint,
                                    tables=tuple(body.get("tables", [])))
            except ValueError as e:
                raise qengine.QueryError(str(e))
        else:
            raise qengine.QueryError(
                "type must be json-lines|otlp-json|remote-write|kafka")
        self.exporters.add(exp)  # idempotent on (type, endpoint)
        return {"added": etype, "endpoint": endpoint,
                "exporters": self.exporters.stats()}

    def subscribe_api(self, body: dict) -> dict:
        """POST /v1/subscribe — the standing-query control surface:
        register/unregister queries, create subscribers, long-poll
        drain. The GET side of the same path streams SSE."""
        if self.standing is None:
            raise qengine.QueryError("standing queries not running")
        action = body.get("action", "list")
        if action == "register":
            sql = body.get("sql", "")
            if not sql:
                raise qengine.QueryError("sql required")
            try:
                window_s = float(body.get("window_s", 0) or 0)
            except (TypeError, ValueError):
                raise qengine.QueryError("window_s must be a number")
            return {"registered": self.standing.register(
                sql, name=body.get("name") or None,
                table=body.get("table") or None,
                window_s=window_s, org_id=body.get("org_id"),
                verify=bool(body.get("verify", False)))}
        if action == "unregister":
            return {"unregistered": self.standing.unregister(
                str(body.get("name", "")))}
        if action == "list":
            return {"queries": self.standing.list()}
        if action == "subscribe":
            names = body.get("queries")
            return self.standing.subscribe(
                [str(n) for n in names] if names else None)
        if action == "poll":
            return self.standing.poll(
                str(body.get("subscriber", "")),
                timeout_s=float(body.get("timeout_s", 25.0) or 25.0),
                max_items=int(body.get("max", 64) or 64))
        if action == "unsubscribe":
            return {"unsubscribed": self.standing.unsubscribe(
                str(body.get("subscriber", "")))}
        raise qengine.QueryError(f"unknown subscribe action {action!r}")

    def exporters_delete(self, body: dict) -> dict:
        if self.exporters is None:
            raise qengine.QueryError("exporters not running")
        endpoint = body.get("endpoint", "")
        return {"removed": self.exporters.remove(endpoint)}

    def _resolve_table(self, table_name: str, db_name: str = ""):
        # resolution order: as-given, db-prefixed, then with the default
        # interval suffix (flow_metrics tables are <name>.<interval>)
        candidates = [table_name, f"{table_name}.1s"]
        if db_name:
            candidates = [f"{db_name}.{table_name}",
                          f"{db_name}.{table_name}.1s"] + candidates
        for cand in candidates:
            try:
                return self.db.table(cand)
            except KeyError:
                continue
        raise qengine.QueryError(
            f"no such table {table_name!r}; known: {self.db.tables()}")

    @staticmethod
    def _org_scope(select: qsql.Select, table, org) -> None:
        if "org_id" not in table.columns:
            # silently dropping the filter would hand one tenant
            # another tenant's rows — refuse instead
            raise qengine.QueryError(
                f"table {table.name!r} has no org scoping; "
                "query it without org_id")
        # cooperative VIEW filter, not a security boundary: the
        # caller names the org it wants and nothing verifies it may
        # (see docs/SECURITY.md). ANDed into the parsed AST rather
        # than the SQL text so the filter can't be quoted away.
        cond = qsql.BinOp("=", qsql.Col("org_id"),
                          qsql.Lit(int(org)))
        select.where = (cond if select.where is None
                        else qsql.BinOp("AND", select.where, cond))

    def _fed(self):
        """The FederationCoordinator iff remote peers are alive right
        now — otherwise every query takes the plain local path."""
        if self.federation is not None and self.federation.active():
            return self.federation
        return None

    def query(self, body: dict) -> dict:
        sql_text = body.get("sql", "")
        db_name = body.get("db", "")
        # parse before the trace opens: it decides WHICH trace to open
        # (EXPLAIN runs captured; SHOW is catalog introspection, never
        # traced).  The parse cost is re-attributed as a span below.
        t0, c0 = time.time_ns(), time.thread_time_ns()
        select = qsql.parse_statement(sql_text)
        parse_t1, parse_c1 = time.time_ns(), time.thread_time_ns()
        if isinstance(select, qsql.Show):
            from deepflow_tpu.query import catalog
            try:
                result = catalog.show(select.what, select.table)
            except KeyError as e:
                raise qengine.QueryError(
                    f"no such table {e.args[0]!r} for SHOW") from None
            return {"result": result, "debug": {"show": select.what}}
        if isinstance(select, qsql.Explain):
            return self._explain(body, select, db_name)
        with self.qtracer.start_trace("query", kind="sql",
                                      sql=sql_text[:200]):
            self._parse_span(t0, c0, parse_t1, parse_c1)
            return self._run_select(body, select, sql_text, db_name)

    @staticmethod
    def _parse_span(t0: int, c0: int, t1: int, c1: int) -> None:
        """Re-attribute a parse that happened just before the trace
        opened (statement routing needs the AST first)."""
        sp = qtrace.span("parse")
        if isinstance(sp, qtrace.Span):
            sp.start_ns, sp.cpu_start_ns = t0, c0
            sp.end_ns = t1
            sp.cpu_ns = c1 - c0
            sp._buf.add(sp)

    def _run_select(self, body: dict, select: qsql.Select, sql_text: str,
                    db_name: str) -> dict:
        """Plan + execute one SELECT (the body of ``query()``, shared
        with the EXPLAIN path, running under whatever trace is open)."""
        org = body.get("org_id")
        debug: dict = {}
        with qtrace.span("plan") as pl:
            table = self._resolve_table(select.table, db_name)
            if org is not None:
                self._org_scope(select, table, org)
            fed = self._fed()
            debug["table"] = table.name
            sketch = None
            if fed is None and self.rollup is not None:
                # transparent rollup datasource selection: when the
                # query is an aligned aggregate a coarser tier answers
                # exactly, swap the table (rollup tables share column
                # names — the SQL text itself is reusable verbatim, and
                # the cache keys on the table object)
                from deepflow_tpu.query import datasource as qds
                sketch = qds.sketch_percentile(self.db, table, select,
                                               self.rollup.horizons())
                if sketch is None:
                    picked = qds.select_rollup(self.db, table, select,
                                               self.rollup.horizons())
                    if picked is not None:
                        table, info = picked
                        debug["datasource"] = info
                        debug["table"] = table.name
            pl.annotate(table=debug["table"], federated=fed is not None,
                        **({"datasource": str(debug["datasource"])}
                           if "datasource" in debug else {}))
        if fed is not None:
            with qtrace.span("execute", path="federation") as ex:
                result, info = fed.sql_query(table, select, sql_text,
                                             org_id=org)
                if isinstance(info, dict):
                    ex.annotate(shards=int(info.get("shards", 1)),
                                cache=str(info.get("cache", "")))
            return self._annotate_degraded(
                {"result": result.to_dict(), "debug": debug,
                 "federation": info}, table.name)
        if sketch is not None:
            result, info = sketch
            debug["datasource"] = info
            qtrace.annotate(datasource=str(info))
            return self._annotate_degraded(
                {"result": result.to_dict(), "debug": debug}, table.name)
        # org scoping rewrote the AST, not the text — fold it into the
        # cache key so scoped variants of one SQL string don't collide
        with qtrace.span("execute", path="local") as ex:
            result = self.query_cache.execute(
                table, sql_text, select=select,
                extra_key=None if org is None else ("org", org))
            ex.annotate(rows=len(result.values))
        return self._annotate_degraded(
            {"result": result.to_dict(), "debug": debug}, table.name)

    def _degraded_for(self, table_name: str) -> dict | None:
        """Quarantine marker for a table: rows the integrity scrubber
        pulled from service (corrupt segments) and has not repaired
        yet. None when the table serves its full history."""
        store = getattr(self.db, "tier_store", None)
        if store is None:
            return None
        info = store.quarantine_info(table_name)
        if not info:
            return None
        return {"reason": "segment_quarantine", **info}

    def _annotate_degraded(self, out: dict, table_name: str) -> dict:
        """Attach the degraded marker + a human warning to a query
        response — the same short-answer honesty contract federation's
        missing_shards uses: results during a quarantine gap are
        SERVED, but never silently presented as complete. Remote-shard
        markers gathered by the scatter ride in under federation."""
        deg = self._degraded_for(table_name)
        fed = out.get("federation")
        fed_deg = (fed.get("degraded_shards")
                   if isinstance(fed, dict) else None)
        if deg is not None:
            out["degraded"] = deg
            out.setdefault("warnings", []).append(
                f"results may be incomplete: {deg['rows']} rows in "
                f"{deg['segments']} quarantined segment(s) of "
                f"{table_name} await repair")
        if fed_deg:
            out.setdefault("warnings", []).append(
                f"results may be incomplete: quarantined segments on "
                f"shard(s) {sorted(fed_deg)} await repair")
        return out

    # -- EXPLAIN [ANALYZE] ---------------------------------------------------

    def _explain(self, body: dict, stmt: qsql.Explain,
                 db_name: str) -> dict:
        """EXPLAIN: plan only (table/tier/datasource/federation route).
        EXPLAIN ANALYZE: run the query under a CAPTURED trace and
        annotate the plan with observed wall/CPU per stage; the observed
        stage costs feed the stage cost model (query/costmodel.py)."""
        root = self.qtracer.start_trace(
            "query", kind="explain", sql=stmt.sql[:200], capture=True)
        out = None
        with root:
            if stmt.analyze:
                out = self._run_select(body, stmt.select, stmt.sql,
                                       db_name)
            else:
                with qtrace.span("plan") as pl:
                    table = self._resolve_table(stmt.select.table, db_name)
                    org = body.get("org_id")
                    if org is not None:
                        self._org_scope(stmt.select, table, org)
                    fed = self._fed()
                    pl.annotate(table=table.name,
                                federated=fed is not None)
                    if fed is None and self.rollup is not None:
                        from deepflow_tpu.query import datasource as qds
                        picked = qds.select_rollup(self.db, table,
                                                   stmt.select,
                                                   self.rollup.horizons())
                        if picked is not None:
                            pl.annotate(datasource=str(picked[1]),
                                        table=picked[0].name)
        # E2E = the root span's wall (query work only; excludes the
        # trace's own sink flush) — the number stage walls must sum to
        total_ns = root.duration_ns
        spans = root.trace_spans()
        root_id = root.span_id
        stages = []
        for d in spans:
            if d["parent_span_id"] != root_id:
                continue
            stages.append({"stage": d["name"],
                           "wall_ms": round(d["duration_ns"] / 1e6, 3),
                           "cpu_ms": round(d["cpu_ns"] / 1e6, 3),
                           "status": d["status"],
                           "detail": d["attrs"]})
        stages.sort(key=lambda s: -s["wall_ms"])
        plan: dict = {"analyze": stmt.analyze}
        rows_out = 0
        for d in spans:
            a = d["attrs"]
            nm = d["name"]
            if nm == "plan":
                plan.update({k: a[k] for k in
                             ("table", "federated", "datasource")
                             if k in a})
            elif nm == "execute":
                plan["path"] = a.get("path", "")
                rows_out = int(a.get("rows", 0) or 0)
                if "shards" in a:
                    plan["shards"] = a["shards"]
                if a.get("cache"):
                    plan["scatter_cache"] = a["cache"]
            elif nm.startswith("prune"):
                pr = plan.setdefault("prune", {"candidates": 0,
                                               "zone_pruned": 0,
                                               "bloom_checked": 0,
                                               "bloom_pruned": 0,
                                               "scanned": 0})
                for src, dst in (("candidates", "candidates"),
                                 ("zone_pruned", "zone_pruned"),
                                 ("bloom_checked", "bloom_checked"),
                                 ("bloom_pruned", "bloom_pruned"),
                                 ("scanned", "scanned")):
                    pr[dst] += int(a.get(src, 0) or 0)
            elif nm.startswith("scan"):
                if "mode" in a:
                    plan["scan_mode"] = a["mode"]
                if "degree" in a:
                    plan["morsel_degree"] = a["degree"]
                if "morsels" in a:
                    plan["morsels"] = a["morsels"]
            elif nm == "cache.lookup":
                plan["cache_layer"] = a.get("layer", "")
                if "outcome" in a:
                    plan["cache_outcome"] = a["outcome"]
        if stmt.analyze:
            # observed per-stage costs feed the same EWMA machinery the
            # kernel/degree choosers learn from
            for s in stages:
                self.stage_cost.observe(s["stage"], max(rows_out, 1),
                                        s["wall_ms"] * 1e6)
        result_rows = [[s["stage"], s["wall_ms"], s["cpu_ms"],
                        json.dumps(s["detail"], sort_keys=True,
                                   default=str)]
                       for s in stages]
        explain = {"analyze": stmt.analyze, "trace_id": root.trace_id,
                   "plan": plan, "stages": stages,
                   "total_ms": round(total_ns / 1e6, 3),
                   "spans": len(spans)}
        if stmt.analyze:
            explain["rows_returned"] = rows_out
            if out is not None and "federation" in out:
                explain["federation"] = out["federation"]
        return {"result": {"columns": ["stage", "wall_ms", "cpu_ms",
                                       "detail"],
                           "values": result_rows},
                "explain": explain,
                "debug": {"explain": True, "table": plan.get("table", "")}}

    def profile_tracing(self, body: dict) -> dict:
        table = self.db.table("profile.in_process_profile")
        params = {"time_start": body.get("time_start"),
                  "time_end": body.get("time_end"),
                  "event_type": body.get("event_type"),
                  "app_service": body.get("app_service"),
                  "profiler": body.get("profiler")}
        fed = self._fed()
        if fed is not None:
            from deepflow_tpu.query.flamegraph import build_flame_tree

            def flame_fn(p, db):
                part = self._flame_stacks(p, db)
                return part["stacks"], part["values"]

            (stacks, values), info = fed.flame_stacks(flame_fn, params)
            return {"result": build_flame_tree(stacks, values).to_dict(),
                    "federation": info}
        tree = profile_flame_tree(
            table,
            time_start_ns=params["time_start"],
            time_end_ns=params["time_end"],
            event_type=params["event_type"],
            app_service=params["app_service"],
            profiler=params["profiler"],
        )
        return {"result": tree.to_dict()}

    def _flame_stacks(self, params: dict, db=None) -> dict:
        """Shard-local half of a federated flame graph: aggregate by
        stack in this shard's encoded space, return DECODED stacks."""
        from deepflow_tpu.query.flamegraph import profile_stack_values
        table = (db if db is not None else self.db).table(
            "profile.in_process_profile")
        stacks, values = profile_stack_values(
            table,
            time_start_ns=params.get("time_start"),
            time_end_ns=params.get("time_end"),
            event_type=params.get("event_type"),
            app_service=params.get("app_service"),
            profiler=params.get("profiler"))
        return {"stacks": stacks, "values": values}

    def tpu_flame(self, body: dict) -> dict:
        """Flame view over HLO device spans: module -> op hierarchy.
        Device kinds only by default; pass include_host to include
        host-compile/runtime spans in the same tree."""
        table = self.db.table("profile.tpu_hlo_span")
        where = ["duration_ns > 0"]
        if not body.get("include_host"):
            from deepflow_tpu.store.schema import TPU_SPAN_KINDS
            device_kinds = ", ".join(
                f"'{k}'" for k in TPU_SPAN_KINDS if k.startswith("device-"))
            where.append(f"kind IN ({device_kinds})")
        if body.get("time_start"):
            where.append(f"time >= {int(body['time_start'])}")
        if body.get("time_end"):
            where.append(f"time < {int(body['time_end'])}")
        if body.get("device_id") is not None:
            where.append(f"device_id = {int(body['device_id'])}")
        sql_text = (
            "SELECT hlo_module, hlo_category, hlo_op, Sum(duration_ns) AS d "
            f"FROM t WHERE {' AND '.join(where)} "
            "GROUP BY hlo_module, hlo_category, hlo_op")
        fed = self._fed()
        info = None
        if fed is not None:
            # an exact push-down case: Sum partials merge shard-side ids
            # never travel (group keys are decoded strings)
            res, info = fed.sql_query(table, qsql.parse(sql_text),
                                      sql_text)
        else:
            res = qengine.execute(table, sql_text)
        from deepflow_tpu.query.flamegraph import build_flame_tree
        stacks, values = [], []
        for mod, cat, op, d in res.values:
            stacks.append(";".join(x for x in (mod, cat or "other", op) if x))
            values.append(int(d))
        out = {"result": build_flame_tree(stacks, values).to_dict()}
        if info is not None:
            out["federation"] = info
        return out

    def tpu_memory(self, body: dict) -> dict:
        """HBM observability (BASELINE config 3 '+ HBM'): per-device usage
        timeline, headroom summary, per-HLO memory attribution (top ops by
        bytes_accessed), and OOM forensics — what ran in the window around
        the highest-pressure sample. Reference analog: the EE memory
        profiler (memory_profile.rs) flame view, redesigned around XLA
        allocator statistics + xplane span memory traffic."""
        mem = self.db.table("profile.tpu_memory")
        where = ["bytes_limit > 0"]
        if body.get("time_start"):
            where.append(f"time >= {int(body['time_start'])}")
        if body.get("time_end"):
            where.append(f"time < {int(body['time_end'])}")
        if body.get("device_id") is not None:
            where.append(f"device_id = {int(body['device_id'])}")
        res = qengine.execute(
            mem, "SELECT time, device_id, bytes_in_use, peak_bytes_in_use, "
                 "bytes_limit, largest_free_block FROM t "
                 f"WHERE {' AND '.join(where)} ORDER BY time")
        timeline = [
            {"time": int(t), "device_id": int(d), "bytes_in_use": int(b),
             "peak_bytes_in_use": int(p), "bytes_limit": int(lim),
             "largest_free_block": int(fr)}
            for t, d, b, p, lim, fr in res.values]
        devices: dict[int, dict] = {}
        for s in timeline:  # time-ordered: last write wins = latest
            d = s["device_id"]
            cur = devices.setdefault(d, {"device_id": d, "peak_pct": 0.0})
            cur["bytes_in_use"] = s["bytes_in_use"]
            cur["peak_bytes_in_use"] = s["peak_bytes_in_use"]
            cur["bytes_limit"] = s["bytes_limit"]
            cur["largest_free_block"] = s["largest_free_block"]
            cur["peak_pct"] = round(
                100.0 * s["peak_bytes_in_use"] / s["bytes_limit"], 1)
            cur["headroom_bytes"] = s["bytes_limit"] - s["peak_bytes_in_use"]
        # per-HLO memory attribution: top ops by HBM traffic in the window
        spans = self.db.table("profile.tpu_hlo_span")
        swhere = ["bytes_accessed > 0"]
        if body.get("time_start"):
            swhere.append(f"time >= {int(body['time_start'])}")
        if body.get("time_end"):
            swhere.append(f"time < {int(body['time_end'])}")
        top_n = int(body.get("top", 15))
        sres = qengine.execute(
            spans, "SELECT hlo_op, hlo_module, Sum(bytes_accessed) AS b, "
                   "Sum(duration_ns) AS d, Count() AS n FROM t "
                   f"WHERE {' AND '.join(swhere)} "
                   "GROUP BY hlo_op, hlo_module ORDER BY b DESC "
                   f"LIMIT {top_n}")
        top_ops = [
            {"hlo_op": op, "hlo_module": mod, "bytes_accessed": int(b),
             "duration_ns": int(d), "count": int(n),
             "hbm_gbps": round(b / max(1, d), 2)}  # bytes/ns = GB/s
            for op, mod, b, d, n in sres.values]
        # OOM forensics: the highest-pressure sample and what ran near it
        forensics = None
        if timeline:
            worst = max(timeline,
                        key=lambda s: s["bytes_in_use"] / s["bytes_limit"])
            w = int(body.get("forensics_window_s", 10)) * 1_000_000_000
            t0, t1 = worst["time"] - w, worst["time"] + w
            fres = qengine.execute(
                spans, "SELECT hlo_op, Sum(bytes_accessed) AS b FROM t "
                       f"WHERE bytes_accessed > 0 AND time >= {t0} "
                       f"AND time < {t1} GROUP BY hlo_op "
                       "ORDER BY b DESC LIMIT 10")
            forensics = {
                "pressure_peak": worst,
                "pressure_pct": round(
                    100.0 * worst["bytes_in_use"] / worst["bytes_limit"], 1),
                "ops_near_peak": [
                    {"hlo_op": op, "bytes_accessed": int(b)}
                    for op, b in fres.values],
            }
        return {"result": {
            "devices": sorted(devices.values(),
                              key=lambda d: d["device_id"]),
            "timeline": timeline[-int(body.get("limit", 2000)):],
            "top_ops": top_ops,
            "forensics": forensics,
        }}

    def tpu_collectives(self, body: dict) -> dict:
        """Cross-device stitched collectives (reference: SURVEY §2.9.5 ICI
        observation). Each group = one collective instance across all its
        participant devices, with latency/skew/bandwidth."""
        rows = self._tpu_span_rows(body, collectives_only=True)
        from deepflow_tpu.tpuprobe.collectives import stitch
        return {"result": [g.to_dict() for g in stitch(rows)]}

    def tpu_step_trace(self, body: dict) -> dict:
        """One training step stitched across devices: per-device span
        bounds + collective groups + straggler skew."""
        rows = self._tpu_span_rows(body)
        from deepflow_tpu.tpuprobe.collectives import step_trace
        run_id = body.get("run_id")
        return {"result": step_trace(
            rows, run_id=None if run_id is None else int(run_id))}

    _STEP_COLS = ("time, end_ns, latency_ns, run_id, step, job, "
                  "device_count, device_skew_ns, compute_ns, "
                  "collective_ns, straggler_device, straggler_lag_ns, "
                  "top_hlos, host")

    def _step_rollups(self, body: dict) -> tuple[list[dict], dict | None]:
        """Merged (job, run_id, step) rollups from per-host
        tpu_step_metrics records, plus federation info when the query
        scattered. The shard partial is a plain row SELECT — each host's
        record lands on exactly one shard, so the union of shard rows is
        the exact single-node row set and merge_host_partials on top is
        federation-exact."""
        from deepflow_tpu.server import stephealth
        table = self.db.table("profile.tpu_step_metrics")
        where = []
        if body.get("job"):
            job = str(body["job"]).replace("'", "")
            where.append(f"job = '{job}'")
        if body.get("run_id") is not None:
            where.append(f"run_id = {int(body['run_id'])}")
        if body.get("time_start"):
            where.append(f"time >= {int(body['time_start'])}")
        if body.get("time_end"):
            where.append(f"time < {int(body['time_end'])}")
        sql_text = f"SELECT {self._STEP_COLS} FROM t"
        if where:
            sql_text += f" WHERE {' AND '.join(where)}"
        fed = self._fed()
        info = None
        if fed is not None:
            res, info = fed.sql_query(table, qsql.parse(sql_text),
                                      sql_text)
        else:
            res = qengine.execute(table, sql_text)
        cols = res.columns
        rows = [dict(zip(cols, row)) for row in res.values]
        return stephealth.merge_host_partials(rows), info

    def tpu_steps(self, body: dict) -> dict:
        """Per-step health timeline: merged pod-level rollups annotated by
        the same EWMA+MAD scorer the live StepRegressionDetector runs, so
        what a human reads here agrees with the alerts that fired."""
        from deepflow_tpu.server import stephealth
        rollups, info = self._step_rollups(body)
        scored = stephealth.score_timeline(rollups)
        limit = int(body.get("limit", 500))
        out = {"result": {"steps": scored[-limit:],
                          "total_steps": len(scored)}}
        if info is not None:
            out["federation"] = info
        return out

    def tpu_step_critical_path(self, body: dict) -> dict:
        """Critical-path attribution for ONE step: where its latency went
        (per-device compute vs collective wait vs device skew) relative to
        a rolling baseline of the healthy steps before it, naming the
        straggler device/host and the dominant HLOs by delta."""
        from deepflow_tpu.server import stephealth
        rollups, info = self._step_rollups(body)
        if not rollups:
            raise qengine.QueryError("no step records in window")
        want_run = body.get("run_id")
        want_step = body.get("step")
        idx = len(rollups) - 1
        if want_step is not None:
            idx = next(
                (i for i, r in enumerate(rollups)
                 if r["step"] == int(want_step)
                 and (want_run is None or r["run_id"] == int(want_run))),
                -1)
            if idx < 0:
                raise qengine.QueryError(
                    f"step {want_step} not found in window")
        target = rollups[idx]
        # baseline = the healthy steps BEFORE the target, per the same
        # streaming scorer — the target itself never pollutes it
        sc = stephealth.EwmaMad()
        for r in rollups[:idx]:
            if r["job"] == target["job"]:
                sc.feed(r)
        att = stephealth.attribute(target, sc.baseline())
        out = {"result": {"step": target, "attribution": att}}
        if info is not None:
            out["federation"] = info
        return out

    def _tpu_span_rows(self, body: dict,
                       collectives_only: bool = False) -> list[dict]:
        table = self.db.table("profile.tpu_hlo_span")
        where = ["duration_ns > 0"]
        if collectives_only:
            where.append("collective != ''")
        if body.get("time_start"):
            where.append(f"time >= {int(body['time_start'])}")
        if body.get("time_end"):
            where.append(f"time < {int(body['time_end'])}")
        sql_text = (
            "SELECT time, duration_ns, device_id, core_id, hlo_op, "
            "collective, run_id, bytes_transferred, replica_group_size, "
            "step, host, slice_id, tpu_pod FROM t "
            f"WHERE {' AND '.join(where)}")
        res = qengine.execute(table, sql_text)
        cols = res.columns
        return [dict(zip(cols, row)) for row in res.values]

    def orgs_api(self, body: dict) -> dict:
        """Org/team scoping admin (reference: controller/db org model):
        assign an agent group to an org; list assignments. Scoped reads
        pass org_id on /v1/query and the PromQL endpoints — cooperative
        view filtering only, not tenant isolation (docs/SECURITY.md)."""
        if self.controller is None:
            raise qengine.QueryError("no controller")
        action = body.get("action", "list")
        if action == "assign":
            group = body.get("group", "default")
            try:
                org = int(body.get("org_id", 1))
            except (TypeError, ValueError):
                raise qengine.QueryError("org_id must be an integer")
            if org < 1 or org > 0xFFFF:
                raise qengine.QueryError("org_id out of range (1..65535)")
            self.controller.assign_org(group, org)
        return {"orgs": self.controller.org_assignments(),
                "default_org": 1}

    def qos_api(self, body: dict) -> dict:
        """Multi-tenant QoS admin (deepflow_tpu/qos): list per-tenant
        weights/quotas/pressure, or set a tenant's policy (hot-applied
        to the live admission queues). Backs `dfctl qos`."""
        if self.qos is None or not self.qos.enabled:
            return {"enabled": False, "tenants": {}}
        action = body.get("action", "list")
        if action == "set":
            from deepflow_tpu.qos import TenantQos
            try:
                org = int(body.get("org_id", 0))
            except (TypeError, ValueError):
                raise qengine.QueryError("org_id must be an integer")
            if org < 1 or org > 0xFFFF:
                raise qengine.QueryError("org_id out of range (1..65535)")
            cfg = self.qos.config
            cur = cfg.tenant(org)
            t = TenantQos.from_dict({
                "org_id": org,
                "weight": body.get("weight", cur.weight),
                "rate_fps": body.get("rate_fps", cur.rate_fps),
                "burst": body.get("burst", cur.burst)})
            cfg.set_tenant(t)
            self.qos.reconfigure(cfg)
        out = self.qos.snapshot()
        if self.drop_attribution is not None:
            out["drops"] = self.drop_attribution()
        return out

    def _require_token(self, token: str | None, what: str) -> None:
        """Reject a gated control-plane action unless the caller presented
        the shared token (no-op when no token is configured — localhost
        trust, see docs/SECURITY.md)."""
        if self.api_token and (token or "") != self.api_token:
            raise AuthError(f"{what} requires a valid API token "
                            "(X-DF-Token header or token field)")

    def repo_api(self, body: dict, token: str | None = None) -> dict:
        """Agent package repo (reference: deepflow-ctl repo agent
        upload): upload versioned packages for OTA rollout; list them.
        Rollout = `dfctl exec <agent> upgrade version=vX`."""
        if self.controller is None:
            raise qengine.QueryError("no controller")
        action = body.get("action", "list")
        if action == "upload":
            # uploads feed the OTA path: an unauthenticated upload would
            # be remote code execution on every agent that upgrades
            self._require_token(token, "/v1/repo upload")
            import base64
            try:
                data = base64.b64decode(body.get("data_b64", ""),
                                        validate=True)
            except Exception:
                raise qengine.QueryError("data_b64 is not valid base64")
            try:
                info = self.controller.packages.upload(
                    body.get("name", "agent"),
                    body.get("version", ""), data)
            except ValueError as e:
                raise qengine.QueryError(str(e))
            return {"uploaded": info,
                    "packages": self.controller.packages.list()}
        return {"packages": self.controller.packages.list()}

    def _prom_db(self):
        """The db handed to promql.evaluate: the federated shim when
        peers are alive (raw selectors fan out, the AST still evaluates
        here — exact), else the plain local store."""
        fed = self._fed()
        return fed.prom_db() if fed is not None else self.db

    @staticmethod
    def _prom_annotate(out: dict, db) -> dict:
        missing = sorted(getattr(db, "missing_shards", ()))
        info = dict(getattr(db, "fed_info", None) or {})
        # annotate only when there is something to say — a fully healthy
        # federated answer stays byte-identical to a standalone one
        if missing or info.get("covered_shards"):
            info["missing_shards"] = missing
            out["federation"] = info
        if missing:
            out.setdefault("warnings", []).append(
                f"partial result: shards {missing} did not answer")
        return out

    def prom_query_range(self, params: dict) -> dict:
        """GET /prom/api/v1/query_range (reference: querier/app/prometheus,
        router.go:41)."""
        from deepflow_tpu.query import promql
        q = params.get("query", "")
        try:
            start = int(float(params.get("start", 0)))
            end = int(float(params.get("end", 0)))
            step = max(1, int(float(params.get("step", 15))))
        except ValueError as e:
            raise qengine.QueryError(f"bad time param: {e}")
        db = self._prom_db()
        with self.qtracer.start_trace("query", kind="promql",
                                      promql=q[:200]):
            try:
                ast = promql.parse(q)
                if params.get("org_id") is not None:
                    promql.scope_to_org(ast, int(params["org_id"]))
                with qtrace.span("execute", path="promql_range",
                                 step=step):
                    result = promql.evaluate(db, ast, start, end, step)
            except promql.PromqlError as e:
                return {"status": "error", "errorType": "bad_data",
                        "error": str(e)}
        return self._prom_annotate(
            {"status": "success",
             "data": {"resultType": "matrix", "result": result}}, db)

    def prom_query(self, params: dict) -> dict:
        """GET /prom/api/v1/query — instant queries (reference:
        querier/app/prometheus/router/router.go:40)."""
        import time as _time

        from deepflow_tpu.query import promql
        q = params.get("query", "")
        try:
            t = int(float(params.get("time", _time.time())))
        except ValueError as e:
            raise qengine.QueryError(f"bad time param: {e}")
        db = self._prom_db()
        with self.qtracer.start_trace("query", kind="promql",
                                      promql=q[:200]):
            try:
                ast = promql.parse(q)
                if params.get("org_id") is not None:
                    promql.scope_to_org(ast, int(params["org_id"]))
                with qtrace.span("execute", path="promql_instant"):
                    data = promql.evaluate_instant(db, ast, t)
            except promql.PromqlError as e:
                return {"status": "error", "errorType": "bad_data",
                        "error": str(e)}
        return self._prom_annotate({"status": "success", "data": data}, db)

    def _prom_meta_args(self, params: dict) -> tuple:
        """params is a parse_qs dict (every value a list — match[] can
        repeat). Defaults: the last hour."""
        import time as _time
        matches = params.get("match[]", [])
        now = int(_time.time())
        try:
            start = int(float(params.get("start", [now - 3600])[0]))
            end = int(float(params.get("end", [now])[0]))
        except (ValueError, IndexError) as e:
            raise qengine.QueryError(f"bad time param: {e}")
        return matches, start, end

    def prom_series(self, params: dict) -> dict:
        """GET /prom/api/v1/series (reference: querier/app/prometheus
        series API — Grafana variable queries)."""
        from deepflow_tpu.query import promql
        matches, start, end = self._prom_meta_args(params)
        if not matches:
            return {"status": "error", "errorType": "bad_data",
                    "error": "no match[] parameter"}
        db = self._prom_db()  # series() goes through fetch_raw: federates
        try:
            return self._prom_annotate(
                {"status": "success",
                 "data": promql.series(db, matches, start, end)}, db)
        except promql.PromqlError as e:
            return {"status": "error", "errorType": "bad_data",
                    "error": str(e)}

    def prom_labels(self, params: dict) -> dict:
        from deepflow_tpu.query import promql
        matches, start, end = self._prom_meta_args(params)
        # with match[]: goes through series() -> fetch_raw, so the shim
        # federates it; without matches, metadata stays LOCAL by design
        # (schema is identical cluster-wide — docs/CLUSTER.md)
        db = self._prom_db() if matches else self.db
        try:
            return self._prom_annotate(
                {"status": "success",
                 "data": promql.label_names(db, matches, start, end)}, db)
        except promql.PromqlError as e:
            return {"status": "error", "errorType": "bad_data",
                    "error": str(e)}

    def prom_label_values(self, label: str, params: dict) -> dict:
        from deepflow_tpu.query import promql
        matches, start, end = self._prom_meta_args(params)
        db = self._prom_db() if matches else self.db
        try:
            return self._prom_annotate(
                {"status": "success",
                 "data": promql.label_values(db, label, matches,
                                             start, end)}, db)
        except promql.PromqlError as e:
            return {"status": "error", "errorType": "bad_data",
                    "error": str(e)}

    _TEMPO_DUR = {"ns": 1, "us": 1e3, "µs": 1e3, "ms": 1e6, "s": 1e9,
                  "m": 60e9, "h": 3600e9}

    @classmethod
    def _tempo_duration_ns(cls, s: str) -> int:
        import re as _re
        m = _re.match(r"^([\d.]+)(ns|us|µs|ms|s|m|h)$", s.strip())
        if not m:
            raise qengine.QueryError(f"bad duration {s!r}")
        return int(float(m.group(1)) * cls._TEMPO_DUR[m.group(2)])

    _TEMPO_TAGS = ("service.name", "endpoint", "l7.protocol",
                   "http.status_code")

    def _tempo_scan(self, params: dict, db=None) -> list[dict]:
        """Shard-local Tempo scan: one partial dict per trace seen HERE.
        Tags select per-SPAN, but start/end/duration are per-TRACE and a
        trace's spans may live on several shards — so duration filters
        and the limit must NOT apply here; only at the merge/finalize.
        db: an optional claim-filtered view (replication) to scan
        instead of the raw local store."""
        import re as _re
        import time as _time
        tags = {}
        for k, v_quoted, v_plain in _re.findall(
                r'([\w.]+)=(?:"([^"]*)"|(\S+))', params.get("tags", "")):
            tags[k] = v_quoted or v_plain
        for k in tags:
            if k not in self._TEMPO_TAGS:
                raise qengine.QueryError(
                    f"unsupported search tag {k!r}; known: "
                    f"{sorted(self._TEMPO_TAGS)}")
        where = ["trace_id != ''"]
        # a search must ALWAYS have a lower bound (a bare or end-only
        # request must not scan all history): default start is one hour
        # before end (or before now)
        if params.get("start"):
            start_ts = int(float(params["start"]))
        else:
            ref = (int(float(params["end"])) if params.get("end")
                   else int(_time.time()))
            start_ts = ref - 3600
        where.append(f"time >= {start_ts * 1_000_000_000}")
        if params.get("end"):
            where.append(
                f"time < {int(float(params['end'])) * 1_000_000_000}")
        table = (db if db is not None else self.db).table(
            "flow_log.l7_flow_log")
        res = qengine.execute(
            table,
            "SELECT time, trace_id, app_service, request_type, endpoint, "
            "response_duration, l7_protocol, response_code FROM t "
            "WHERE " + " AND ".join(where))
        traces: dict[str, dict] = {}
        for t, tid, svc, rtype, ep, dur, proto, code in res.values:
            t, dur = int(t), int(dur)
            span_tags = {"service.name": svc or "", "endpoint": ep or "",
                         "l7.protocol": str(proto),
                         "http.status_code": str(int(code))}
            matched = all(span_tags.get(k) == v for k, v in tags.items())
            tr = traces.get(tid)
            if tr is None:
                tr = traces[tid] = {
                    "traceID": tid, "_start_ns": t, "_end_ns": t + dur,
                    "spanCount": 1,
                    "rootServiceName": svc or "",
                    "rootTraceName": f"{rtype} {ep}".strip() or tid,
                    "_root_t": t, "_matched": matched}
            else:
                tr["_start_ns"] = min(tr["_start_ns"], t)
                tr["_end_ns"] = max(tr["_end_ns"], t + dur)
                tr["spanCount"] += 1
                tr["_matched"] = tr["_matched"] or matched
                if t < tr["_root_t"]:
                    tr["_root_t"] = t
                    tr["rootServiceName"] = svc or ""
                    tr["rootTraceName"] = f"{rtype} {ep}".strip() or tid
        # dogfood: the querier's own query traces (self-monitoring
        # store) surface through the SAME search API as workload traces
        self.qtracer.flush()
        qt = self.db.table("deepflow_system.query_trace")
        if len(qt):
            qres = qengine.execute(
                qt, "SELECT time, trace_id, parent_span_id, name, "
                    "service, duration_ns, status FROM t "
                    "WHERE " + " AND ".join(where))
            for t, tid, psid, name, svc, dur, status in qres.values:
                t, dur = int(t), int(dur)
                span_tags = {"service.name": svc or "", "endpoint": "",
                             "l7.protocol": "query",
                             "http.status_code": str(status)}
                matched = all(span_tags.get(k) == v
                              for k, v in tags.items())
                tr = traces.get(tid)
                if tr is None:
                    tr = traces[tid] = {
                        "traceID": tid, "_start_ns": t,
                        "_end_ns": t + dur, "spanCount": 1,
                        "rootServiceName": svc or "",
                        "rootTraceName": name or tid,
                        "_root_t": t, "_matched": matched}
                else:
                    tr["_start_ns"] = min(tr["_start_ns"], t)
                    tr["_end_ns"] = max(tr["_end_ns"], t + dur)
                    tr["spanCount"] += 1
                    tr["_matched"] = tr["_matched"] or matched
                if psid == "":
                    # the coordinator root names the trace regardless
                    # of span arrival order
                    tr["_root_t"] = t
                    tr["rootServiceName"] = svc or ""
                    tr["rootTraceName"] = name or tid
        return list(traces.values())

    def tempo_search(self, params: dict) -> dict:
        """GET /api/search — Tempo search API (reference: querier/tempo):
        logfmt tags filter, min/maxDuration, time range, limit.

        Tempo semantics: tags select traces (any single span matching ALL
        tags qualifies the trace), but root/start/duration report the
        WHOLE trace — so the scan keeps every span of the window and
        filters at the trace level (cluster: after the cross-shard
        merge)."""
        limit = max(1, min(int(params.get("limit", 20)), 500))
        min_ns = (self._tempo_duration_ns(params["minDuration"])
                  if params.get("minDuration") else 0)
        max_ns = (self._tempo_duration_ns(params["maxDuration"])
                  if params.get("maxDuration") else 0)
        fed = self._fed()
        info = None
        with self.qtracer.start_trace("query", kind="tempo",
                                      tags=params.get("tags", "")):
            if fed is not None:
                with qtrace.span("execute", path="federation"):
                    traces, info = fed.tempo_search(
                        self._tempo_scan, params)
            else:
                with qtrace.span("execute", path="local"):
                    traces = self._tempo_scan(params)
        out = []
        for tr in traces:
            if not tr["_matched"]:
                continue
            dur_ns = tr["_end_ns"] - tr["_start_ns"]
            if min_ns and dur_ns < min_ns:
                continue
            if max_ns and dur_ns > max_ns:
                continue
            out.append({"traceID": tr["traceID"],
                        "rootServiceName": tr["rootServiceName"],
                        "rootTraceName": tr["rootTraceName"],
                        "startTimeUnixNano": str(tr["_start_ns"]),
                        "durationMs": dur_ns // 1_000_000})
        out.sort(key=lambda tr: -int(tr["startTimeUnixNano"]))
        resp = {"traces": out[:limit], "metrics": {
            "inspectedTraces": len(traces)}}
        if info is not None:
            resp["federation"] = info
        return resp

    def tempo_search_tags(self) -> dict:
        return {"tagNames": list(self._TEMPO_TAGS)}

    def tempo_search_tag_values(self, name: str) -> dict:
        """Values come from live rows (chunk scan), not dictionary
        snapshots: retention-trimmed services must not keep appearing."""
        from deepflow_tpu.query.promql import _codes_in_range
        table = self.db.table("flow_log.l7_flow_log")
        lo, hi = 0, 1 << 62
        if name in ("service.name", "endpoint"):
            col = "app_service" if name == "service.name" else "endpoint"
            d = table.dicts[col]
            vals = []
            for c in _codes_in_range(table, col, lo, hi):
                try:
                    s = d.decode(c)
                except IndexError:
                    continue
                if s:
                    vals.append(s)
        elif name == "l7.protocol":
            enum = table.columns["l7_protocol"].enum_values
            vals = [enum[c] for c in _codes_in_range(
                table, "l7_protocol", lo, hi)
                if 0 <= c < len(enum) and enum[c]]
        elif name == "http.status_code":
            vals = [str(c) for c in sorted(_codes_in_range(
                table, "response_code", lo, hi)) if c]
        else:
            vals = []
        return {"tagValues": sorted(vals)}

    def tempo_trace(self, trace_id: str) -> dict:
        """GET /api/traces/{id} — Grafana Tempo-compatible shape
        (reference: querier/tempo)."""
        tree = self._assemble_trace(trace_id)
        spans = []

        def walk(node, parent_id=""):
            spans.append({
                "traceID": trace_id,
                "spanID": node["span_id"],
                "parentSpanID": parent_id,
                "operationName": node["name"],
                "serviceName": node["service"],
                "startTimeUnixNano": str(node["start_ns"]),
                "durationNano": str(node["duration_ns"]),
                "tags": [{"key": "l7_protocol",
                          "value": node["l7_protocol"]},
                         {"key": "status", "value": node["status"]},
                         {"key": "kind", "value": node["kind"]}],
            })
            for c in node["children"]:
                walk(c, node["span_id"])

        for root in tree["spans"]:
            walk(root)
        return {"batches": [{"spans": spans}]}

    def trace(self, body: dict) -> dict:
        """Distributed trace tree by trace_id (reference: tracemap), or by
        syscall chain id for uprobe-sourced flows without W3C headers."""
        trace_id = body.get("trace_id", "")
        syscall_id = body.get("syscall_trace_id")
        if syscall_id is not None:
            try:
                syscall_id = int(syscall_id)
            except (TypeError, ValueError):
                raise qengine.QueryError(
                    f"bad syscall_trace_id {syscall_id!r}") from None
            from deepflow_tpu.query.tracing import build_syscall_trace
            return {"result": build_syscall_trace(
                self.db.table("flow_log.l7_flow_log"), syscall_id)}
        if not trace_id:
            raise qengine.QueryError("trace_id or syscall_trace_id required")
        tree = self._assemble_trace(trace_id)
        # tracing adapter: splice spans from configured EXTERNAL backends
        tree = self.trace_adapters.merge_into(tree, trace_id)
        return {"result": tree}

    def collect_trace_spans(self, trace_id: str, db=None) -> list[dict]:
        """This shard's span dicts for one trace. Prefers the ingest-time
        precompute (flow_log.trace_tree rows + TraceTreeBuilder pending
        spans): touches only this trace's data. Falls back to the l7 scan
        for data ingested before the builder existed (e.g. loaded from an
        old data_dir). db: optional claim-filtered view (replication) —
        either way replica span copies also dedup at assembly by
        (span_id, start_ns, flow_id)."""
        import json as _json

        import numpy as np

        from deepflow_tpu.query.tracing import scan_trace_spans
        if db is None:
            db = self.db
        spans: list[dict] = []
        tree_table = db.table("flow_log.trace_tree")
        code = tree_table.dicts["trace_id"].lookup(trace_id)
        if code is not None:
            for ch in tree_table.snapshot():
                if not ch:
                    continue
                for i in np.flatnonzero(ch["trace_id"] == code).tolist():
                    spans.extend(_json.loads(
                        tree_table.dicts["tree"].decode(int(ch["tree"][i]))))
        if self.trace_trees is not None:
            spans.extend(self.trace_trees.pending_spans(trace_id))
        if not spans:
            spans = scan_trace_spans(
                db.table("flow_log.l7_flow_log"), trace_id)
        # dogfooded query traces live in the self-monitoring store, NOT
        # the flow store — union them so /api/traces and /v1/trace
        # render the querier's own spans like any workload's
        spans.extend(self._query_trace_spans(trace_id))
        return spans

    def _query_trace_spans(self, trace_id: str) -> list[dict]:
        """This node's deepflow_system.query_trace span dicts for one
        trace (+ the tracer's unflushed pending rows: read-your-writes
        for a trace completed microseconds ago)."""
        import numpy as np
        qt = self.db.table("deepflow_system.query_trace")
        code = qt.dicts["trace_id"].lookup(trace_id)
        rows: list[dict] = []
        if code is not None:
            for ch in qt.snapshot():
                if not ch:
                    continue
                for i in np.flatnonzero(ch["trace_id"] == code).tolist():
                    row = {}
                    for name, arr in ch.items():
                        spec = qt.columns[name]
                        v = arr[i]
                        if spec.kind == "str":
                            row[name] = qt.dicts[name].decode(int(v))
                        elif spec.kind == "enum":
                            row[name] = spec.enum_values[int(v)]
                        else:
                            row[name] = int(v)
                    rows.append(row)
        spans = qtrace.spans_from_rows(rows)
        spans.extend(self.qtracer.pending_spans(trace_id))
        return spans

    def _assemble_trace(self, trace_id: str, max_spans: int = 1000) -> dict:
        """One trace's tree: this shard's spans, plus — when peers are
        alive — every other shard's (one trace's spans may be ingested
        anywhere; build_trace_from_spans dedups on the merged set)."""
        from deepflow_tpu.query.tracing import build_trace_from_spans
        fed = self._fed()
        info = None
        if fed is not None:
            spans, info = fed.trace_spans(self.collect_trace_spans,
                                          trace_id)
        else:
            spans = self.collect_trace_spans(trace_id)
        tree = build_trace_from_spans(
            trace_id, spans,
            tpu_table=self.db.table("profile.tpu_hlo_span"),
            max_spans=max_spans)
        if info is not None:
            tree["federation"] = info
        return tree

    def log_search(self, body: dict) -> dict:
        """Search over the dedicated application_log.log store (reference:
        server/ingester/app_log + querier log queries). Filters:
        app_service, trace_id, min_severity, body substring (pushed down
        onto the body dictionary, not the rows), time range, limit.
        Newest rows first."""
        import numpy as np
        body = body or {}
        t = self.db.table("application_log.log")
        limit = max(1, min(10_000, int(body.get("limit", 100) or 100)))
        svc = body.get("app_service")
        needle = body.get("query") or body.get("body_contains")
        trace_id = body.get("trace_id")
        min_sev = int(body.get("min_severity", 0) or 0)
        t_from = int(body.get("from_ns", 0) or 0)
        t_to = int(body.get("to_ns", 0) or 0)
        empty = {"result": {"logs": [], "count": 0}}
        body_ids = None
        if needle:
            needle_l = str(needle).lower()
            body_ids = t.dicts["body"].match_ids(
                lambda s: needle_l in s.lower())
            if not len(body_ids):
                return empty
        svc_id = t.dicts["app_service"].lookup(str(svc)) if svc else None
        if svc and svc_id is None:
            return empty
        tid_id = (t.dicts["trace_id"].lookup(str(trace_id))
                  if trace_id else None)
        if trace_id and tid_id is None:
            return empty
        names = ("time", "app_service", "app_instance", "severity_number",
                 "severity_text", "body", "trace_id", "span_id", "attrs")
        # chunks are NOT globally time-ordered: concurrent HTTP handler
        # threads write through per-thread stripes, so newest-first needs
        # an explicit sort over the matches, not reversed chunk order
        chunks = t.snapshot()
        cand: list[tuple[int, int, int]] = []
        for ci, ch in enumerate(chunks):
            if not ch:
                continue
            mask = np.ones(len(ch["time"]), dtype=bool)
            if t_from:
                mask &= ch["time"] >= t_from
            if t_to:
                mask &= ch["time"] < t_to
            if svc_id is not None:
                mask &= ch["app_service"] == svc_id
            if tid_id is not None:
                mask &= ch["trace_id"] == tid_id
            if min_sev:
                mask &= ch["severity_number"] >= min_sev
            if body_ids is not None:
                mask &= np.isin(ch["body"], body_ids)
            times = ch["time"]
            for i in np.flatnonzero(mask).tolist():
                cand.append((int(times[i]), ci, i))
        cand.sort(key=lambda c: (-c[0], -c[1], -c[2]))
        out: list[dict] = []
        for _tm, ci, i in cand[:limit]:
            ch = chunks[ci]
            row = {}
            for n in names:
                v = ch[n][i]
                row[n] = (t.dicts[n].decode(int(v)) if n in t.dicts
                          else int(v))
            out.append(row)
        return {"result": {"logs": out, "count": len(out)}}

    def trace_search(self, body: dict) -> dict:
        """Service-path search over precomputed trace trees (reference:
        trace_tree service-path queries). Body: {service_path: [..],
        root_service, from_ns, to_ns, min_duration_ns, limit}."""
        from deepflow_tpu.server import tracetree as tt
        body = body or {}
        path = body.get("service_path") or []
        if isinstance(path, str):
            path = [p for p in path.split(">") if p]
        pending = (self.trace_trees.pending_summaries()
                   if self.trace_trees is not None else None)
        hits = tt.search(
            self.db.table("flow_log.trace_tree"),
            service_path_query=[str(p) for p in path],
            root_service=body.get("root_service"),
            time_from_ns=int(body.get("from_ns", 0) or 0),
            time_to_ns=int(body.get("to_ns", 0) or 0),
            min_duration_ns=int(body.get("min_duration_ns", 0) or 0),
            limit=int(body.get("limit", 50) or 50),
            pending=pending)
        return {"result": {"traces": hits, "count": len(hits)}}

    def tracing_adapters_api(self, body: dict | None = None) -> dict:
        if body and body.get("remove"):
            return {"removed": self.trace_adapters.remove(
                str(body["remove"])),
                "adapters": self.trace_adapters.list()}
        if body and body.get("kind"):
            try:
                self.trace_adapters.add(str(body["kind"]),
                                        str(body.get("base_url", "")))
            except ValueError as e:
                raise qengine.QueryError(str(e)) from None
        return {"adapters": self.trace_adapters.list()}

    def pcaps(self, body: dict | None = None) -> dict:
        store = getattr(self.db, "pcap_store", None)
        entries = list(store["entries"]) if store else []
        if body and body.get("name"):
            import base64
            import os
            for e in entries:
                if e["name"] == body["name"]:
                    data = e.get("data")
                    if data is None and e.get("path") and \
                            os.path.exists(e["path"]):
                        with open(e["path"], "rb") as f:
                            data = f.read()
                    if data is None:
                        raise qengine.QueryError("capture data gone")
                    return {"name": e["name"],
                            "pcap_gz_b64":
                                base64.b64encode(data).decode()}
            raise qengine.QueryError(f"no capture {body['name']!r}")
        return {"pcaps": [{k: v for k, v in e.items()
                           if k not in ("data",)} for e in entries]}

    def analyzers_api(self, body: dict | None = None) -> dict:
        if self.controller is None:
            raise qengine.QueryError("no controller")
        if body and "addrs" in body:
            addrs = [str(a) for a in body["addrs"]]
            try:
                self.controller.set_analyzers(addrs)
            except ValueError as e:
                raise qengine.QueryError(f"bad analyzer address: {e}") \
                    from None
        return {"analyzers": self.controller.analyzers()}

    def agent_exec(self, body: dict, token: str | None = None) -> dict:
        """Queue a registry command for an agent; poll with result_id."""
        if self.controller is None:
            raise qengine.QueryError("no controller")
        if "result_id" in body:
            r = self.controller.commands.result(int(body["result_id"]))
            if r is None:
                raise qengine.QueryError("unknown result_id")
            return {"result": r}
        agent_id = int(body.get("agent_id", 0))
        cmd = str(body.get("cmd", ""))
        if not agent_id or not cmd:
            raise qengine.QueryError("agent_id and cmd required")
        if cmd == "upgrade":
            # `upgrade` makes the agent re-exec a repo package: it is the
            # other half of the OTA code-execution path — same gate
            self._require_token(token, "the `upgrade` exec command")
        cid = self.controller.commands.submit(
            agent_id, cmd, [str(a) for a in body.get("args", [])])
        return {"result_id": cid}

    def agents(self) -> dict:
        """Agent fleet listing with health (reference: deepflow-ctl agent
        list / cli/ctl/agent.go:49 — staleness, exception bitmap, degraded
        state are the primary ops signals)."""
        if self.controller is None:
            return {"agents": []}
        import time as _time
        now = _time.time_ns()
        out = []
        for a in self.controller.registry.list():
            staleness_s = (now - a.get("last_seen_ns", now)) / 1e9
            out.append({
                "agent_id": a["agent_id"],
                "hostname": a["hostname"],
                "ctrl_ip": a["ctrl_ip"],
                "last_seen_ns": a.get("last_seen_ns", 0),
                "staleness_s": round(staleness_s, 1),
                "stale": staleness_s > 60.0,
                "state": a.get("state", 0),
                "exception_bitmap": a.get("exception_bitmap", 0),
                "degraded": a.get("degraded", False),
                "version": a.get("version", ""),
                "cpu_usage": a.get("cpu_usage", 0.0),
                "mem_bytes": a.get("mem_bytes", 0),
                "agent_group": a.get("agent_group", "default"),
                "config_version": a.get("config_version", 0),
                "syncs": a.get("syncs", 0),
                "devices": self.controller.devices_of(a["agent_id"]),
            })
        return {"agents": out}

    def update_agent_config(self, body: dict) -> dict:
        if self.controller is None:
            raise qengine.QueryError("controller not running")
        group = body.get("group", "default")
        yaml_text = body.get("yaml", "")
        version = self.controller.configs.update(group, yaml_text.encode())
        return {"group": group, "version": version}

    # -- cluster (scatter-gather) endpoints ---------------------------------

    def shard_exec(self, body: dict, token: str | None = None) -> dict:
        """POST /v1/shard/exec — the shard-local half of every federated
        query. Execution here is STRICTLY local (never re-fans-out, even
        with peers alive): the coordinator is the only merge point, so a
        cycle of shards can't amplify one query."""
        self._require_token(token, "/v1/shard/exec")
        op = body.get("op", "")
        # a replication-aware coordinator ships a ring snapshot + alive
        # set in the body; answer from the claim-filtered view so each
        # replicated row is reported by exactly one alive owner. A
        # pre-replication coordinator sends no ring: raw local answer.
        from deepflow_tpu.cluster.hashring import claim_db_from_body
        db = claim_db_from_body(body, self.db, self.shard_id)
        # a traced coordinator ships its trace context in the body: this
        # shard's spans join the SAME trace, parented under the
        # coordinator's scatter span, and land in the shard-local
        # query_trace table — read-time trace assembly unions them
        from deepflow_tpu.cluster import wire as _wire
        with self.qtracer.adopt(_wire.extract_ctx(body), "shard.exec",
                                op=op, shard=self.shard_id):
            return self._shard_exec_op(body, db, op)

    def _shard_exec_op(self, body: dict, db, op: str) -> dict:
        if op == "sql_partial":
            table = (db.table(body["table"]) if body.get("table")
                     else self._resolve_table("", ""))
            select = qsql.parse_statement(body.get("sql", ""))
            if not isinstance(select, qsql.Select):
                raise qengine.QueryError("sql_partial needs a SELECT")
            org = body.get("org_id")
            if org is not None:
                # the coordinator's org filter lives in its AST, not the
                # SQL text — re-inject it here from the op body
                self._org_scope(select, table, org)
            if not body.get("enc"):
                # pre-encoding coordinator: decoded partial, old wire form
                out = qengine.execute_partial(table, select)
            else:
                out = self._sql_partial_enc(body, table, select, org)
            # shard-side degraded marker: computed fresh per call (an
            # unchanged-token short-circuit reply still reports a NEW
            # quarantine), merged by the coordinator into
            # federation.degraded_shards
            deg = self._degraded_for(str(body.get("table") or ""))
            if deg is not None:
                out["degraded"] = deg
            return out
        if op == "promql_raw":
            from deepflow_tpu.query import promql
            vs = promql.VectorSelector(
                metric=str(body.get("metric", "")),
                matchers=[tuple(m) for m in body.get("matchers", [])])
            try:
                series = promql.fetch_raw(db, vs,
                                          float(body.get("lo_s", 0)),
                                          float(body.get("hi_s", 0)))
            except promql.UnknownMetricError:
                return {"unknown": True}
            return {"series": [
                {"labels": s.labels, "t": s.t.tolist(), "v": s.v.tolist(),
                 "counter": bool(s.counter)} for s in series]}
        if op == "tempo_scan":
            return {"traces": self._tempo_scan(body.get("params") or {},
                                               db)}
        if op == "trace_spans":
            return {"spans": self.collect_trace_spans(
                str(body.get("trace_id", "")), db)}
        if op == "profile_flame":
            return self._flame_stacks(body.get("params") or {}, db)
        if op == "table_counts":
            return {name: len(self.db.table(name))
                    for name in self.db.tables()}
        raise qengine.QueryError(f"unknown shard op {op!r}")

    def _sql_partial_enc(self, body: dict, table, select: qsql.Select,
                         org) -> dict:
        """Encoded half of a v2 sql_partial: change-token short-circuit,
        bucket-cached encoded partial, and the dictionary delta the
        coordinator needs to remap our ids (cluster/dictsync.py)."""
        # claim filtering answers for different rows under a different
        # ring/alive set even when the table itself is unchanged — fold
        # the ring context into both the change token and the cache key
        ring = body.get("ring") or {}
        ring_ctx = None if not ring else [
            ring.get("epoch"), ring.get("token"),
            sorted(int(s) for s in body.get("alive") or [])]
        # publish-gen handshake: a read-tier coordinator names the
        # pointer generation it adopted from us. On a gen match, answer
        # WITHOUT the published segments — the coordinator serves those
        # from the object store — so each sealed row is counted exactly
        # once. On a mismatch (it adopted an older pointer, or none)
        # answer in full; the coordinator drops our adopted segments
        # from its own scan instead.
        rt_req = (body.get("readtier") or {}).get(str(self.shard_id))
        rt_ack = None
        if rt_req is not None and self.publisher is not None:
            gen, fn_sets = self.publisher.current
            if int(rt_req) == gen:
                fns = fn_sets.get(table.name)
                if fns:
                    from deepflow_tpu.store.segcache import \
                        PublishedExcludeView
                    view = PublishedExcludeView(table, fns)
                    # a compaction/eviction may have retired published
                    # fns before the next publish tick moves `current`;
                    # excluding then would leave the replacement run
                    # (same rows) in our answer while the coordinator
                    # also serves the published blobs. Ack only while
                    # every published fn is still live — otherwise
                    # answer in full and let the coordinator drop our
                    # adopted segments (same path as a gen mismatch).
                    if view.complete:
                        table = view
                        rt_ack = gen
                else:
                    rt_ack = gen
        from deepflow_tpu.query.cache import change_token
        # read BEFORE computing; the exclusion context joins the token —
        # the same table state answers for different rows at a
        # different publish gen
        tok = [change_token(table), ring_ctx] + \
            ([["pub", rt_ack]] if rt_ack is not None else [])
        rt_reply = (None if rt_req is None else
                    {"gen": (self.publisher.current[0]
                             if self.publisher is not None else 0),
                     "excluded": rt_ack is not None})
        if_state = (body.get("if_state") or {}).get(str(self.shard_id))
        if if_state is not None and if_state == tok:
            out = {"kind": "unchanged", "state": tok}
            if rt_reply is not None:
                out["rt"] = rt_reply
            return out
        extra = ("fed", org, repr(ring_ctx)) + \
            ((("pub", rt_ack),) if rt_ack is not None else ())
        part = dict(self.query_cache.partial(
            table, body.get("sql", ""), select=select, extra_key=extra))
        dicts = part.get("dicts")
        if dicts:
            from deepflow_tpu.cluster.dictsync import build_sync
            known = (body.get("dict_known") or {}).get(
                str(self.shard_id)) or {}
            sync = build_sync(table, dicts, known)
            if sync is None:
                # a dictionary gen flipped between the partial build and
                # now — ids in the partial are unremappable; re-run in
                # the decoded wire form instead of shipping garbage
                # (against the SAME exclusion view, and still carrying
                # the rt ack so the coordinator's accounting holds)
                part = dict(qengine.execute_partial(table, select))
                if rt_reply is not None:
                    part["rt"] = rt_reply
                return part
            part["dict_sync"] = sync
        part["state"] = tok
        if rt_reply is not None:
            part["rt"] = rt_reply
        return part

    def cache_partial(self, body: dict, token: str | None = None) -> dict:
        """POST /v1/cache/partial — the serve side of the cluster-wide
        partial-aggregate cache (cluster/partialcache.py): hand a peer
        replica whatever warm, currently-valid bucket slices we hold
        for its (table, sql, org, pub_token) claim."""
        self._require_token(token, "/v1/cache/partial")
        if self.partial_cache is None:
            return {"buckets": {}}
        return self.partial_cache.serve(body)

    def cluster_join(self, body: dict) -> dict:
        if self.membership is None:
            raise qengine.QueryError("clustering not enabled")
        return self.membership.handle_join(body)

    def cluster_peers(self) -> dict:
        if self.membership is None:
            return {"version": 0, "peers": []}
        self.membership.refresh_self()
        return self.membership.directory.snapshot()

    def cluster_status(self) -> dict:
        """The dfctl `cluster` view: peer table with per-shard row
        counts and probe latency."""
        if self.federation is None:
            return {"shard_id": self.shard_id, "version": 0, "peers": [],
                    "fanout": {}}
        return self.federation.cluster_status()

    def segments(self, table: str | None = None,
                 v1_only: bool = False) -> dict:
        """Per-segment inspector (the `dfctl segments` backend): format
        version, rows, per-column codecs, zone/skip-index presence and
        sorted-run membership for every on-disk segment of a table (or
        all tables). ``v1_only`` filters to segments still awaiting
        format migration."""
        store = getattr(self.db, "tier_store", None)
        if store is None:
            return {"tables": {}, "storage": False}
        self.db._ensure_loaded()
        names = [table] if table else sorted(store.tables())
        tables: dict[str, list] = {}
        for name in names:
            tt = store.tier(name)
            rows = []
            for seg in tt.segments():
                if v1_only and seg.fmt >= 2:
                    continue
                codecs = seg.codecs()
                rows.append({
                    "file": os.path.basename(seg.path),
                    "format": seg.fmt,
                    "rows": seg.rows,
                    "bytes": seg.nbytes,
                    "tmin": seg.tmin, "tmax": seg.tmax,
                    "run": seg.run,
                    "sorted_by": seg.sorted_by,
                    "codecs": codecs,
                    "zoned_cols": len(seg.zones),
                    "indexed_cols": sorted(
                        c for c in codecs if seg.has_index(c)),
                })
            if rows or not v1_only:
                tables[name] = rows
        return {"tables": tables, "storage": True,
                "compact_gen": store.compact_gen}

    def fsck(self, table: str | None = None,
             repair: bool = True) -> dict:
        """On-demand integrity check (the `dfctl fsck` backend): verify
        every block checksum of every sealed local segment NOW, without
        waiting for the background scrubber's paced walk. Corrupt
        segments go through the same quarantine + repair path the
        scrubber uses (repair=False reports only). Pre-checksum (v1/
        early-v2) segments count as unverifiable, never as corrupt."""
        store = getattr(self.db, "tier_store", None)
        if store is None:
            return {"storage": False, "tables": {}}
        self.db._ensure_loaded()
        scrub = self.scrubber
        if scrub is None and repair:
            from deepflow_tpu.store.scrub import Scrubber
            scrub = Scrubber(self.db, shard_id=self.shard_id,
                             telemetry=self.telemetry)
        names = [table] if table else sorted(store.tables())
        tables: dict[str, dict] = {}
        for name in names:
            tt = store.tier(name)
            res = {"segments": 0, "blocks_checked": 0, "bytes": 0,
                   "clean": 0, "unverifiable": 0, "corrupt": [],
                   "repaired": [], "repair_failed": []}
            for seg in tt.segments():
                v = seg.verify()
                res["segments"] += 1
                res["blocks_checked"] += v["checked"]
                res["bytes"] += v["bytes"]
                if v["corrupt"]:
                    fn = os.path.basename(seg.path)
                    res["corrupt"].append({"file": fn,
                                           "blocks": v["corrupt"]})
                    if scrub is not None:
                        ok = scrub.quarantine_and_repair(
                            name, seg, f"fsck:{','.join(v['corrupt'])}")
                        res["repaired" if ok
                            else "repair_failed"].append(fn)
                elif v["verifiable"]:
                    res["clean"] += 1
                else:
                    res["unverifiable"] += 1
            q = store.quarantined().get(name)
            if q:
                res["quarantined"] = q
            tables[name] = res
        return {"storage": True, "tables": tables,
                "ok": not any(t["corrupt"] or t.get("quarantined")
                              for t in tables.values())}

    def health(self) -> dict:
        """Liveness + the self-telemetry spine: per-stage heartbeat
        status, the per-hop frame ledger (with imbalance), and wedge
        verdicts — the server's from its live Telemetry, the agents'
        mined back out of deepflow_system.deepflow_system (they run in
        other processes; the DFSTATS path is their only voice here)."""
        out = {
            "status": "ok",
            "tables": {name: len(self.db.table(name))
                       for name in self.db.tables()},
            "stats": self.stats_provider(),
        }
        out["query_cache"] = self.query_cache.snapshot()
        from deepflow_tpu.query import engine as _qengine
        from deepflow_tpu.query import pool as _qpool
        pool_stats = _qpool.stats()
        out["query"] = {
            **_qengine.scan_stats(),  # scanned/pruned segment counters
            "pool_busy": pool_stats["busy"],
            "pool_threads": pool_stats["threads"],
            "pool_dispatched": pool_stats["dispatched"],
            "degree": _qengine._DEGREE.snapshot(),
        }
        if self.storage_provider is not None:
            storage = self.storage_provider()
            if storage is not None:
                out["storage"] = storage
        if self.qos is not None:
            # overload-control state: admission queues, per-tenant
            # pressure levels, adaptive-sampling rates + the receiver's
            # per-(org, agent) drop attribution
            qos = self.qos.snapshot()
            if self.drop_attribution is not None:
                qos["drops"] = self.drop_attribution()
            out["qos"] = qos
        if self.membership is not None:
            out["cluster"] = {
                "shard_id": self.shard_id,
                "version": self.membership.directory.version,
                "peers_alive": len(self.membership.peers()),
            }
        if self.federation is not None:
            out["dict_sync"] = self.federation.dict_sync.snapshot()
            out["federation_cache"] = dict(
                self.federation.sql_cache_counters)
        if self.readtier is not None:
            # adopted publish state + the segment cache's fetch/hit/
            # miss/evict ledger (the readtier-check conservation input)
            out["readtier"] = self.readtier.snapshot()
        if self.partial_cache is not None:
            out["partial_cache"] = self.partial_cache.snapshot()
        if self.standing is not None:
            # standing queries: per-query generations/fold counters +
            # the conserved query.standing push ledger
            out["standing"] = self.standing.snapshot()
        if self.exporters is not None:
            ex = self.exporters.stats()
            if ex:
                # per-exporter counters now carry the conserved
                # exporter.<kind> hop ledger (satellite: spool evictions
                # and ship failures are accounted, never silent)
                out["exporters"] = ex
        if self.alerts is not None:
            out["alerting"] = self.alerts.snapshot()
        # dogfooded query tracing: span counters + the query.trace hop
        # ledger (emitted == delivered + dropped + pending holds, same
        # conservation law as every frame hop)
        out["query_trace"] = self.qtracer.snapshot()
        if self.publisher is not None:
            out["publish"] = dict(self.publisher.stats)
            out["publish"]["publish_gen"] = self.publisher.publish_gen
        wedged_stages: list[str] = []
        if self.telemetry is not None:
            selfmon = self.telemetry.snapshot()
            out["selfmon"] = selfmon
            out["stages"] = selfmon["stages"]
            out["pipeline"] = selfmon["pipeline"]
            out["ledger_imbalance"] = selfmon["ledger_imbalance"]
            out["wedges"] = selfmon["wedges"]
            wedged_stages += [w["stage"] for w in selfmon["wedges"]]
        from deepflow_tpu.telemetry import collect_agent_selfmon
        agents = collect_agent_selfmon(self.db)
        if (agents["pipeline"] or agents["heartbeats"]
                or agents["wedges"]):
            out["agents_selfmon"] = agents
        # an agent wedge only degrades health while it is CURRENT
        # (latest heartbeat row still says wedged=1): recovered stages
        # stop counting even though their verdict rows persist
        live = {s["stage"] for s in agents["heartbeats"].values()
                if s.get("wedged")}
        for w in agents["wedges"]:
            if w["stage"] in live or not agents["heartbeats"]:
                wedged_stages.append("agent:" + w["stage"])
        if wedged_stages:
            out["status"] = "degraded"
            out["wedged_stages"] = sorted(set(wedged_stages))
        return out


class QuerierHTTP:
    def __init__(self, api: QuerierAPI, host: str = "127.0.0.1",
                 port: int = 20416) -> None:
        self.api = api
        self.host = host
        self.port = port
        self._httpd: ThreadingHTTPServer | None = None

    def start(self) -> "QuerierHTTP":
        api = self.api

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                log.debug(fmt, *args)

            def _send(self, code: int, obj: dict) -> None:
                data = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _raw(self) -> bytes:
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n) if n else b""
                # Telegraf's HTTP output gzips by default; dropping such a
                # body as "0 accepted" with a 200 would silence all metrics
                if raw and self.headers.get("Content-Encoding",
                                            "").lower() == "gzip":
                    import gzip
                    import zlib
                    try:
                        raw = gzip.decompress(raw)
                    except (OSError, EOFError, zlib.error) as e:
                        # client-side input error -> 400, not a 500
                        raise ValueError(f"bad gzip body: {e}") from None
                return raw

            def _body(self) -> dict:
                return json.loads(self._raw() or b"{}")

            def _token(self, body: dict | None = None) -> str | None:
                """Shared API token: X-DF-Token header, Bearer auth, or a
                `token` body field (dfctl sends the header)."""
                tok = self.headers.get("X-DF-Token")
                if tok:
                    return tok
                auth = self.headers.get("Authorization", "")
                if auth.startswith("Bearer "):
                    return auth[len("Bearer "):]
                if body is not None:
                    return body.get("token")
                return None

            def _sse(self, params: dict) -> None:
                """GET /v1/subscribe?subscriber=ID — SSE stream of
                standing-query updates (long-poll POST action=poll is
                the fallback). One `data:` line per update; comment
                keepalives every idle poll round."""
                sid = params.get("subscriber", "")
                if api.standing is None or not sid:
                    self._send(400, {"error": "subscriber required "
                                     "(POST action=subscribe first)"})
                    return
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()
                try:
                    while True:
                        out = api.standing.poll(sid, timeout_s=10.0,
                                                max_items=64)
                        for u in out["updates"]:
                            self.wfile.write(
                                b"data: " + json.dumps(u).encode()
                                + b"\n\n")
                        if not out["updates"]:
                            self.wfile.write(b": keepalive\n\n")
                        self.wfile.flush()
                        if out["closed"]:
                            return
                except (BrokenPipeError, ConnectionResetError, OSError):
                    return  # client went away: the idle reaper cleans up

            def do_GET(self) -> None:
                from urllib.parse import parse_qsl, urlparse
                parsed = urlparse(self.path)
                path = parsed.path.rstrip("/")
                params = dict(parse_qsl(parsed.query))
                try:
                    if path == "/v1/subscribe":
                        self._sse(params)
                    elif path in ("/v1/health", "/health"):
                        self._send(200, api.health())
                    elif path == "/v1/cluster/peers":
                        self._send(200, api.cluster_peers())
                    elif path == "/v1/cluster/status":
                        self._send(200, api.cluster_status())
                    elif path == "/v1/agents":
                        self._send(200, api.agents())
                    elif path == "/v1/segments":
                        self._send(200, api.segments(
                            table=params.get("table") or None,
                            v1_only=params.get("v1") in ("1", "true")))
                    elif path == "/v1/fsck":
                        self._send(200, api.fsck(
                            table=params.get("table") or None,
                            repair=params.get("repair")
                            not in ("0", "false")))
                    elif path == "/v1/alerts":
                        self._send(200, api.alerts_api("list", {}))
                    elif path == "/v1/exporters":
                        self._send(200, {"exporters":
                                         api.exporters.stats()
                                         if api.exporters else {}})
                    elif path in ("/prom/api/v1/query_range",
                                  "/api/v1/query_range"):
                        self._send(200, api.prom_query_range(params))
                    elif path in ("/prom/api/v1/query", "/api/v1/query"):
                        self._send(200, api.prom_query(params))
                    elif path in ("/prom/api/v1/series", "/api/v1/series"):
                        from urllib.parse import parse_qs
                        self._send(200, api.prom_series(
                            parse_qs(parsed.query)))
                    elif path in ("/prom/api/v1/labels", "/api/v1/labels"):
                        from urllib.parse import parse_qs
                        self._send(200, api.prom_labels(
                            parse_qs(parsed.query)))
                    elif (path.startswith(("/prom/api/v1/label/",
                                           "/api/v1/label/"))
                          and path.endswith("/values")):
                        from urllib.parse import parse_qs
                        label = path.rsplit("/label/", 1)[1][:-len("/values")]
                        self._send(200, api.prom_label_values(
                            label, parse_qs(parsed.query)))
                    elif path.startswith("/api/traces/"):
                        self._send(200, api.tempo_trace(
                            path.rsplit("/", 1)[-1]))
                    elif path == "/api/echo":  # Tempo datasource health
                        self._send(200, {"status": "echo"})
                    elif path == "/api/search":
                        self._send(200, api.tempo_search(params))
                    elif path == "/api/search/tags":
                        self._send(200, api.tempo_search_tags())
                    elif (path.startswith("/api/search/tag/")
                          and path.endswith("/values")):
                        name = path[len("/api/search/tag/"):-len("/values")]
                        self._send(200, api.tempo_search_tag_values(name))
                    else:
                        self._send(404, {"error": f"no route {self.path}"})
                except (qengine.QueryError, ValueError) as e:
                    self._send(400, {"error": str(e)})

            def do_POST(self) -> None:
                from urllib.parse import parse_qsl, urlparse
                try:
                    parsed = urlparse(self.path)
                    if parsed.path.rstrip("/") == "/api/v1/profile/ingest":
                        self._send(200, api.integration.ingest_profile(
                            dict(parse_qsl(parsed.query)), self._raw()))
                        return
                    if parsed.path.rstrip("/") == "/api/v1/write":
                        self._send(200, api.integration.ingest_prometheus(
                            self._raw()))
                        return
                    if parsed.path.rstrip("/") == "/api/v1/telegraf":
                        self._send(200, api.integration.ingest_telegraf(
                            self._raw()))
                        return
                    if parsed.path.rstrip("/") in _DD_TRACE_PATHS:
                        self._send(200, api.integration.ingest_datadog(
                            self._raw(),
                            self.headers.get("Content-Type", "")))
                        return
                    body = self._body()
                    path = parsed.path.rstrip("/")
                    if path == "/v1/shard/exec":
                        # binary columnar response (codec SHARD_RESULT
                        # frame), not JSON: numeric result columns ride
                        # as raw little-endian arrays
                        from deepflow_tpu.cluster import wire
                        obj = api.shard_exec(body, token=self._token(body))
                        payload = wire.encode_result(
                            obj, shard_id=api.shard_id)
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/octet-stream")
                        self.send_header("Content-Length",
                                         str(len(payload)))
                        self.end_headers()
                        self.wfile.write(payload)
                        return
                    if path == "/v1/cache/partial":
                        # binary CACHE_PARTIAL frame: bucket slices
                        # carry ndarray id columns, jsonb keeps them raw
                        from deepflow_tpu.cluster import wire
                        obj = api.cache_partial(body,
                                                token=self._token(body))
                        payload = wire.encode_cache_partial(
                            obj, shard_id=api.shard_id)
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/octet-stream")
                        self.send_header("Content-Length",
                                         str(len(payload)))
                        self.end_headers()
                        self.wfile.write(payload)
                        return
                    if path == "/v1/cluster/join":
                        self._send(200, api.cluster_join(body))
                    elif path == "/v1/query":
                        self._send(200, api.query(body))
                    elif path == "/v1/profile/ProfileTracing":
                        self._send(200, api.profile_tracing(body))
                    elif path == "/v1/profile/TpuFlame":
                        self._send(200, api.tpu_flame(body))
                    elif path == "/v1/profile/TpuCollectives":
                        self._send(200, api.tpu_collectives(body))
                    elif path == "/v1/profile/TpuStepTrace":
                        self._send(200, api.tpu_step_trace(body))
                    elif path == "/v1/tpu/steps":
                        self._send(200, api.tpu_steps(body))
                    elif path == "/v1/tpu/steps/critical_path":
                        self._send(200, api.tpu_step_critical_path(body))
                    elif path == "/v1/profile/TpuMemory":
                        self._send(200, api.tpu_memory(body))
                    elif path == "/v1/tracing-adapters":
                        self._send(200, api.tracing_adapters_api(body))
                    elif path == "/v1/pcaps":
                        self._send(200, api.pcaps(body))
                    elif path == "/v1/analyzers":
                        self._send(200, api.analyzers_api(body))
                    elif path == "/v1/orgs":
                        self._send(200, api.orgs_api(body))
                    elif path == "/v1/qos":
                        self._send(200, api.qos_api(body))
                    elif path == "/v1/repo":
                        self._send(200, api.repo_api(
                            body, token=self._token(body)))
                    elif path == "/v1/agents/exec":
                        self._send(200, api.agent_exec(
                            body, token=self._token(body)))
                    elif path == "/v1/agent-group-config":
                        self._send(200, api.update_agent_config(body))
                    elif path == "/v1/trace/Tracing":
                        self._send(200, api.trace(body))
                    elif path == "/v1/trace/Search":
                        self._send(200, api.trace_search(body))
                    elif path == "/api/v1/otlp/traces":
                        self._send(200,
                                   api.integration.ingest_otlp_traces(body))
                    elif path == "/api/v1/log":
                        self._send(200, api.integration.ingest_app_log(body))
                    elif path == "/api/v1/otlp/logs":
                        self._send(200,
                                   api.integration.ingest_otlp_logs(body))
                    elif path == "/v1/log/search":
                        self._send(200, api.log_search(body))
                    elif path == "/v3/segments":
                        self._send(200,
                                   api.integration.ingest_skywalking(body))
                    elif path == "/v1/alerts":
                        self._send(200, api.alerts_api("upsert", body))
                    elif path == "/v1/alerts/delete":
                        self._send(200, api.alerts_api("delete", body))
                    elif path == "/v1/subscribe":
                        self._send(200, api.subscribe_api(body))
                    elif path == "/v1/exporters":
                        self._send(200, api.exporters_api(body))
                    elif path == "/v1/exporters/delete":
                        self._send(200, api.exporters_delete(body))
                    elif path == "/mcp":
                        resp = api.mcp.handle(body)
                        self._send(200 if resp else 202,
                                   resp or {"accepted": True})
                    else:
                        self._send(404, {"error": f"no route {self.path}"})
                except AuthError as e:
                    self._send(403, {"error": str(e)})
                except (qengine.QueryError, qsql.SqlError, KeyError,
                        json.JSONDecodeError, ValueError,
                        yaml.YAMLError) as e:
                    self._send(400, {"error": str(e)})
                except Exception as e:  # pragma: no cover
                    log.exception("querier 500")
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})

            def do_PUT(self) -> None:
                # only dd-trace PUTs are method-aliased; the rest of the
                # POST router must not gain mutation-via-PUT
                from urllib.parse import urlparse
                if urlparse(self.path).path.rstrip("/") in _DD_TRACE_PATHS:
                    return self.do_POST()
                self._send(405, {"error": "method not allowed"})

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        t = threading.Thread(target=self._httpd.serve_forever,
                             name="df-querier-http", daemon=True)
        t.start()
        return self

    def stop(self) -> None:
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
