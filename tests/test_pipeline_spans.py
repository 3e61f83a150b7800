"""Probe-cycle, frame and request spans (query/qtrace.py): one span
model from the agent's capture cycle through the sender, the server's
ingest and the querier's HTTP handler, all on the time.time_ns() clock
the stored device spans use."""

import json
import time
import urllib.request

import pytest

from deepflow_tpu.agent.agent import Agent
from deepflow_tpu.agent.config import AgentConfig
from deepflow_tpu.proto import pb
from deepflow_tpu.server import Server

AGENT_ID = 7


def _post(port: int, path: str, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())


@pytest.fixture
def server():
    s = Server(host="127.0.0.1", ingest_port=0, query_port=0,
               sync_port=0).start()
    yield s
    s.stop()


def _agent(server) -> Agent:
    cfg = AgentConfig()
    cfg.agent_id = AGENT_ID
    cfg.sender.servers = [("127.0.0.1", server.ingest_port)]
    cfg.profiler.enabled = False
    cfg.tpuprobe.enabled = False
    return Agent(cfg).start()


def _batch(n: int = 3) -> pb.TpuSpanBatch:
    batch = pb.TpuSpanBatch()
    t0 = time.time_ns()
    for i in range(n):
        s = batch.spans.add()
        s.start_ns, s.duration_ns = t0 + i * 1000, 500
        s.hlo_module, s.hlo_op = "jit_train_step", f"fusion.{i}"
        s.kind = pb.DEVICE_COMPUTE
    return batch


def _server_spans(server, name: str) -> list[dict]:
    """deepflow_system.query_trace rows, read with DF-SQL as an operator
    would once the tracer has flushed."""
    server.api.qtracer.flush()
    res = _post(server.query_port, "/v1/query", {
        "db": "deepflow_system",
        "sql": "SELECT trace_id, span_id, parent_span_id, name, time, "
               f"duration_ns, attr_json FROM query_trace "
               f"WHERE name = '{name}'"})["result"]
    cols = res["columns"]
    out = []
    for v in res["values"]:
        row = dict(zip(cols, v))
        row["attrs"] = json.loads(row.pop("attr_json"))
        out.append(row)
    return out


def test_send_frame_and_ingest_frame_join_on_agent_and_seq(server,
                                                           monkeypatch):
    monkeypatch.setenv("DF_QUERY_TRACE", "1")
    monkeypatch.setenv("DF_QUERY_TRACE_SAMPLE", "1")
    agent = _agent(server)
    try:
        # the probe's sink runs under its cycle's trace on the capture
        # thread: a frame handed over there is the cycle's
        with agent.qtracer.start_trace("probe.cycle") as cycle:
            agent.send_tpu_spans(_batch())
        assert server.wait_for_rows("profile.tpu_hlo_span", 3)
        deadline = time.monotonic() + 5
        while True:
            sent = [s for s in agent.recent_spans()
                    if s["name"] == "send.frame"]
            if sent or time.monotonic() > deadline:
                break
            time.sleep(0.02)
    finally:
        agent.stop()
    send, = sent
    assert send["trace_id"] == cycle.trace_id
    assert send["parent_span_id"] == cycle.span_id
    assert send["attrs"]["msg_type"] == "TPU_SPAN"
    assert send["attrs"]["bytes"] > 0
    key = (send["attrs"]["agent_id"], send["attrs"]["seq"])
    assert key[0] == AGENT_ID and key[1]
    deadline = time.monotonic() + 5    # the span closes after the append
    while True:
        ingest = [r for r in _server_spans(server, "ingest.frame")
                  if (r["attrs"]["agent_id"], r["attrs"]["seq"]) == key]
        if ingest or time.monotonic() > deadline:
            break
        time.sleep(0.02)
    assert len(ingest) == 1
    frame = ingest[0]
    assert frame["attrs"]["msg_type"] == "TPU_SPAN"
    assert frame["parent_span_id"] == ""
    assert frame["time"] + frame["duration_ns"] >= send["start_ns"]
    assert frame["time"] >= send["start_ns"]
    # received -> dequeued -> rows appended, as children of the frame
    parts = {r["name"]: r for name in ("ingest.queue", "ingest.write")
             for r in _server_spans(server, name)
             if r["trace_id"] == frame["trace_id"]}
    assert set(parts) == {"ingest.queue", "ingest.write"}
    assert parts["ingest.queue"]["time"] == frame["time"]
    assert parts["ingest.write"]["attrs"]["rows"] == 3
    # a frame sent under no trace records nothing
    assert [s for s in agent.recent_spans()
            if s["name"] == "send.frame"] == [send]


@pytest.mark.parametrize("route,body", [
    ("/v1/profile/TpuFlame", {}),
    ("/v1/profile/TpuMemory", {}),
    ("/v1/query", {"db": "profile",
                   "sql": "SELECT Count(*) AS n FROM tpu_hlo_span"}),
])
def test_request_opens_http_root_with_children(server, monkeypatch, route,
                                               body):
    monkeypatch.setenv("DF_QUERY_TRACE", "1")
    monkeypatch.setenv("DF_QUERY_TRACE_SAMPLE", "1")
    server.db.table("profile.tpu_hlo_span").append_rows([
        {"time": time.time_ns(), "duration_ns": 10, "kind": 1,
         "hlo_module": "jit_train_step", "hlo_op": "fusion.1"}])
    t0 = time.time_ns()
    _post(server.query_port, route, body)
    t1 = time.time_ns()
    # the root closes just after the response is written; the reads
    # below are requests of their own, all sent after t1
    deadline = time.monotonic() + 5
    while True:
        roots = [r for r in _server_spans(server, "http.request")
                 if r["attrs"]["route"] == route and t0 <= r["time"] <= t1]
        if roots or time.monotonic() > deadline:
            break
        time.sleep(0.02)
    assert len(roots) == 1
    root = roots[0]
    assert root["parent_span_id"] == ""
    assert root["attrs"]["code"] == 200
    kids = [r for name in ("execute", "query")
            for r in _server_spans(server, name)
            if r["parent_span_id"] == root["span_id"]]
    assert kids, "the route's work hangs under its request"


def test_kill_switch_records_no_span_anywhere(server, monkeypatch):
    from deepflow_tpu.tpuprobe.sources import XPlaneSource
    monkeypatch.setenv("DF_QUERY_TRACE", "0")
    agent = _agent(server)
    try:
        src = XPlaneSource(agent.send_tpu_spans, duration_ms=200,
                           telemetry=agent.telemetry, tracer=agent.qtracer)
        src.capture_once()
        src.capture_once()
        with agent.qtracer.start_trace("probe.cycle"):
            agent.send_tpu_spans(_batch())
        assert server.wait_for_rows("profile.tpu_hlo_span", 3)
        _post(server.query_port, "/v1/profile/TpuFlame", {})
        _post(server.query_port, "/v1/query", {
            "db": "profile", "sql": "SELECT Count(*) AS n FROM tpu_hlo_span"})
        agent.sender.flush_and_stop()
        assert agent.recent_spans() == []
        assert agent.qtracer.snapshot()["traces"] == 0
    finally:
        agent.stop()
    server.api.qtracer.flush()
    assert server.api.qtracer.snapshot()["traces"] == 0
    assert len(server.db.table("deepflow_system.query_trace")) == 0
