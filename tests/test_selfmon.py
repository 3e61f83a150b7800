"""Self-telemetry spine: hop ledger, heartbeats, deadman, health wiring.

The e2e tests are the acceptance criteria for the telemetry PR: the
frame ledger must balance across a real agent->server run, and a
stalled stage must be detected, named, and stack-snapshotted in
/v1/health AND in deepflow_system within the configured window.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from deepflow_tpu.agent.agent import Agent
from deepflow_tpu.agent.config import AgentConfig
from deepflow_tpu.server import Server
from deepflow_tpu.telemetry import (
    DeadmanDetector, HopLedger, LatencyHistogram, Telemetry)


def _get(port: int, path: str) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
        return json.loads(resp.read())


def _post(port: int, path: str, body: dict,
          token: str | None = None) -> dict:
    headers = {"Content-Type": "application/json"}
    if token:
        headers["X-DF-Token"] = token
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(), headers=headers)
    with urllib.request.urlopen(req, timeout=5) as resp:
        return json.loads(resp.read())


# -- unit: histogram / ledger / registry -------------------------------------

def test_latency_histogram_quantiles():
    h = LatencyHistogram()
    for _ in range(90):
        h.observe(500_000)          # 0.5ms -> 1ms bucket
    for _ in range(10):
        h.observe(5_000_000_000)    # 5s -> 10s bucket
    s = h.snapshot()
    assert s["count"] == 100
    assert s["p50_ms"] <= 1.0
    assert s["p99_ms"] >= 1000.0


def test_latency_histogram_resolves_within_a_1_2_5_step():
    """Quantiles land on the next 1-2-5 bound from 10 us to 100 s, not
    on the next decade."""
    bounds = LatencyHistogram.BOUNDS_NS
    assert bounds[0] == 10_000 and bounds[-1] == 100_000_000_000
    assert all(b / a <= 2.5 for a, b in zip(bounds, bounds[1:]))
    for wait_ns, want_ms in ((3_000_000, 5.0), (150_000, 0.2),
                             (1_200_000_000, 2000.0), (7_000, 0.01)):
        h = LatencyHistogram()
        h.observe(wait_ns, 4)
        assert h.snapshot()["p50_ms"] == want_ms, wait_ns
    h = LatencyHistogram()
    h.observe(300_000_000_000)          # past the last bound: the mean
    assert h.snapshot()["p99_ms"] == 300_000.0


def test_hop_ledger_conservation():
    hop = HopLedger("sender")
    hop.account(emitted=10)
    hop.account(delivered=7, wait_ns=2_000_000)
    hop.account(dropped=2, reason="queue_full")
    hop.account(dropped=1, reason="send_error")
    s = hop.snapshot()
    assert s["emitted"] == 10
    assert s["delivered"] == 7
    assert s["dropped"] == {"queue_full": 2, "send_error": 1}
    assert s["in_flight"] == 0
    assert s["emitted"] == s["delivered"] + s["dropped_total"] \
        + s["in_flight"]


def test_disabled_telemetry_is_noop():
    t = Telemetry("agent", enabled=False)
    hop = t.hop("sender")
    hop.account(emitted=5, delivered=5)
    hb = t.heartbeat("stats")
    hb.beat(progress=3)
    snap = t.snapshot()
    assert snap["enabled"] is False
    assert snap["pipeline"] == []
    assert snap["stages"] == []
    assert list(t.stats_metrics()) == []
    # a detector over a disabled registry never starts its thread
    d = DeadmanDetector(t, window_s=0.1).start()
    assert d._thread is None


def test_pipeline_order_is_registration_order():
    t = Telemetry("server")
    for name in ("receiver", "decoder.METRICS", "table_write"):
        t.hop(name)
    assert [h["hop"] for h in t.pipeline_snapshot()] == \
        ["receiver", "decoder.METRICS", "table_write"]


# -- unit: deadman ----------------------------------------------------------

def test_deadman_wedge_and_recovery():
    t = Telemetry("agent")
    d = DeadmanDetector(t, window_s=0.2)
    done = threading.Event()
    release = threading.Event()

    def stalls():
        hb = t.heartbeat("tpuprobe.xplane")
        hb.beat(progress=1)
        done.set()
        release.wait(5.0)   # wedged: no further beats
        hb.beat(progress=2)

    th = threading.Thread(target=stalls, daemon=True)
    th.start()
    assert done.wait(2.0)
    assert d.check_once() == []          # still inside the window
    time.sleep(0.3)
    new = d.check_once()
    assert [w["stage"] for w in new] == ["tpuprobe.xplane"]
    w = new[0]
    assert w["stalled_s"] >= 0.2 and w["progress"] == 1
    # the stack snapshot points INTO the stalled thread
    assert "stalls" in w["stack"] and "release.wait" in w["stack"]
    assert t.snapshot()["wedges_total"] == 1
    # same wedge is not re-reported while it persists...
    assert d.check_once() == []
    assert len(t.snapshot()["wedges"]) == 1
    # ...and clears as soon as the stage beats again
    release.set()
    th.join(timeout=2.0)
    d.check_once()
    assert t.snapshot()["wedges"] == []


def test_deadman_respects_interval_hint():
    t = Telemetry("server")
    hb = t.heartbeat("janitor", interval_hint_s=10.0)
    hb.beat()
    d = DeadmanDetector(t, window_s=0.1)
    time.sleep(0.15)
    # a 10s-cadence stage is not wedged after 0.15s even with a tiny
    # window: the effective window is max(window, 2.5*hint)
    assert d.check_once() == []


def test_stats_metrics_shape():
    t = Telemetry("agent")
    t.hop("sender").account(emitted=3, delivered=2, dropped=1,
                            reason="queue_full", wait_ns=1_000_000)
    t.heartbeat("stats").beat(progress=4)
    by_name = {}
    for name, tags, values in t.stats_metrics():
        by_name.setdefault(name, []).append((tags, values))
    assert by_name["agent.pipeline"][0][0] == {"hop": "sender"}
    vals = by_name["agent.pipeline"][0][1]
    assert vals["emitted"] == 3.0 and vals["dropped"] == 1.0
    drop_tags = by_name["agent.pipeline.drop"][0][0]
    assert drop_tags == {"hop": "sender", "reason": "queue_full"}
    hb_tags, hb_vals = by_name["agent.heartbeat"][0]
    assert hb_tags == {"stage": "stats"} and hb_vals["progress"] == 4.0


# -- e2e: ledger conservation through a live pipeline ------------------------

@pytest.fixture
def server():
    s = Server(host="127.0.0.1", ingest_port=0, query_port=0,
               selfstats_interval_s=0.5).start()
    yield s
    s.stop()


def test_e2e_ledger_conservation(server):
    cfg = AgentConfig()
    cfg.app_service = "selfmon-e2e"
    cfg.sender.servers = [("127.0.0.1", server.ingest_port)]
    cfg.profiler.sample_hz = 200.0
    cfg.profiler.emit_interval_s = 0.2
    cfg.tpuprobe.enabled = False
    cfg.stats_interval_s = 0.3
    agent = Agent(cfg).start()
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(i * i for i in range(2000))

    th = threading.Thread(target=busy, name="busy")
    th.start()
    time.sleep(1.2)
    stop.set()
    th.join()
    # agent-side live ledger balances BEFORE stop (in_flight may be
    # nonzero mid-run; conservation must hold at every snapshot)
    for hop in agent.telemetry.pipeline_snapshot():
        assert hop["emitted"] == hop["delivered"] \
            + hop["dropped_total"] + hop["in_flight"], hop
    agent.stop()

    assert server.wait_for_rows("profile.in_process_profile", 1)
    assert server.wait_for_rows("deepflow_system.deepflow_system", 1)

    # after quiescence every server hop must fully drain: in_flight 0
    deadline = time.time() + 10.0
    while time.time() < deadline:
        h = _get(server.query_port, "/v1/health")
        hops = {p["hop"]: p for p in h.get("pipeline", [])}
        if hops and all(p["in_flight"] == 0 for p in hops.values()):
            break
        time.sleep(0.2)
    assert hops, "no server pipeline telemetry in /v1/health"
    assert "receiver" in hops
    assert any(k.startswith("decoder.") for k in hops)
    assert "table_write" in hops
    for name, p in hops.items():
        assert p["in_flight"] == 0, f"{name} did not drain: {p}"
        assert p["emitted"] == p["delivered"] + p["dropped_total"], p
    assert hops["receiver"]["emitted"] > 0
    assert hops["table_write"]["delivered"] > 0
    # queue-wait histograms saw real traffic (the enqueue->dequeue wait
    # is observed by the decoder at dequeue time)
    assert any(p["wait"]["count"] > 0 for k, p in hops.items()
               if k.startswith("decoder."))
    assert h["ledger_imbalance"] == 0

    # server stages are beating and none is wedged
    stages = {s["stage"]: s for s in h["stages"]}
    for required in ("receiver", "janitor", "deadman", "selfstats"):
        assert required in stages, sorted(stages)
        assert stages[required]["beats"] >= 1
        assert not stages[required]["wedged"]
    assert any(s.startswith("decoder.") for s in stages)
    assert h["status"] == "ok"

    # the agent's ledger + heartbeats came back out of deepflow_system
    ag = h.get("agents_selfmon")
    assert ag, "agent selfmon rows missing from /v1/health"
    assert "sender" in ag["pipeline"]
    assert ag["pipeline"]["sender"]["emitted"] >= 1
    assert "stats" in ag["heartbeats"]
    assert ag["wedges"] == []

    # and the same rows resolve through plain DF-SQL (PromQL shares
    # this path via the deepflow_system_* narrow-table mapping)
    out = _post(server.query_port, "/v1/query/", {
        "db": "deepflow_system",
        "sql": "SELECT metric_name, Count(1) AS n FROM deepflow_system "
               "WHERE metric_name = 'agent.pipeline' GROUP BY metric_name"})
    assert out["result"]["values"], out


# -- e2e: wedge detection (the regression test from ADVICE r5) ---------------

def test_e2e_wedge_detected_named_and_stack_snapshotted(server):
    cfg = AgentConfig()
    cfg.app_service = "selfmon-wedge"
    cfg.sender.servers = [("127.0.0.1", server.ingest_port)]
    cfg.profiler.enabled = False
    cfg.tpuprobe.enabled = False
    cfg.stats_interval_s = 0.3
    cfg.selfmon.deadman_window_s = 0.6
    cfg.selfmon.check_interval_s = 0.15
    agent = Agent(cfg).start()
    release = threading.Event()

    def fake_capture():
        # stands in for the tpuprobe xplane capture thread that wedges inside
        # capture_once: beats once on entry, then not again until released
        hb = agent.telemetry.heartbeat("tpuprobe.xplane")
        hb.beat(progress=1)
        release.wait(30.0)
        hb.beat(progress=2)  # recovery beat

    th = threading.Thread(target=fake_capture, name="fake-capture",
                          daemon=True)
    th.start()
    try:
        # within the window (+ shipping latency) the wedge must surface in
        # /v1/health, sourced from deepflow_system rows
        deadline = time.time() + 10.0
        h = {}
        while time.time() < deadline:
            h = _get(server.query_port, "/v1/health")
            if h.get("status") == "degraded":
                break
            time.sleep(0.2)
        assert h.get("status") == "degraded", h.get("status")
        assert "agent:tpuprobe.xplane" in h["wedged_stages"]
        wedges = {w["stage"]: w
                  for w in h["agents_selfmon"]["wedges"]}
        assert "tpuprobe.xplane" in wedges
        w = wedges["tpuprobe.xplane"]
        assert w.get("wedged") == 1.0
        assert w.get("stalled_s", 0) >= 0.6
        # the stack names the wedged frame, not just the stage
        assert "fake_capture" in w["stack"]
        assert "release.wait" in w["stack"]
        hb = h["agents_selfmon"]["heartbeats"]["tpuprobe.xplane"]
        assert hb["wedged"] == 1.0

        # raw rows landed in deepflow_system.deepflow_system too (the
        # PromQL/alerting surface)
        t = server.db.table("deepflow_system.deepflow_system")
        sid = t.dicts["metric_name"].lookup("agent.deadman")
        assert sid is not None, "no agent.deadman rows shipped"
    finally:
        release.set()
        th.join(timeout=2.0)

    # recovery: the stage beat again, so the next deadman scan clears
    # the verdict from the live registry...
    agent.deadman.check_once()
    assert agent.telemetry.snapshot()["wedges"] == []
    # ...and the final stats flush in stop() ships wedged=0 heartbeat
    # rows, so /v1/health returns to ok
    agent.stop()
    deadline = time.time() + 10.0
    h = {}
    while time.time() < deadline:
        h = _get(server.query_port, "/v1/health")
        if h["status"] == "ok":
            break
        time.sleep(0.2)
    assert h.get("status") == "ok", h.get("wedged_stages")


# -- satellite: control-plane token gating -----------------------------------

def test_token_gates_repo_upload_and_upgrade_exec():
    import base64
    s = Server(host="127.0.0.1", ingest_port=0, query_port=0, sync_port=0,
               enable_controller=True, api_token="s3cret").start()
    try:
        data_b64 = base64.b64encode(b"pkg-bytes").decode()
        up = {"action": "upload", "name": "agent", "version": "v9",
              "data_b64": data_b64}
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(s.query_port, "/v1/repo", up)
        assert ei.value.code == 403
        # wrong token is still 403
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(s.query_port, "/v1/repo", up, token="wrong")
        assert ei.value.code == 403
        # body-field token works too (CLI sends the header)
        out = _post(s.query_port, "/v1/repo", up, token="s3cret")
        assert out["uploaded"]["version"] == "v9"
        # list stays open: read-only, not part of the OTA exec path
        out = _post(s.query_port, "/v1/repo", {"action": "list"})
        assert "agent" in out["packages"]

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(s.query_port, "/v1/agents/exec",
                  {"agent_id": 1, "cmd": "upgrade", "args": ["version=v9"]})
        assert ei.value.code == 403
        out = _post(s.query_port, "/v1/agents/exec",
                    {"agent_id": 1, "cmd": "upgrade",
                     "args": ["version=v9"], "token": "s3cret"})
        assert "result_id" in out
        # non-upgrade exec commands stay open (read-only diagnostics)
        out = _post(s.query_port, "/v1/agents/exec",
                    {"agent_id": 1, "cmd": "status"})
        assert "result_id" in out
    finally:
        s.stop()
