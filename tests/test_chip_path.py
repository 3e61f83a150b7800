"""The profiled-job path's bring-up fixes, checked without a chip: the
probe follows a job that imports JAX after the agent attached, topology
waits for the job's own backends, and nothing swaps in a fake source."""

import time
from types import SimpleNamespace

import jax

from deepflow_tpu.agent.config import AgentConfig
from deepflow_tpu.tpuprobe import sources, topology
from deepflow_tpu.tpuprobe.sources import HooksSource, XPlaneSource


def _fake_agent(**tpuprobe):
    cfg = AgentConfig()
    cfg.tpuprobe.memory_poll_s = 0
    for k, v in tpuprobe.items():
        setattr(cfg.tpuprobe, k, v)
    return SimpleNamespace(config=cfg, telemetry=None, process_name="t",
                           send_tpu_spans=lambda b: None,
                           send_step_metrics=lambda p: True)


def test_auto_means_xplane():
    from deepflow_tpu.tpuprobe.probe import TpuProbe
    probe = TpuProbe(_fake_agent(source="auto")).start()
    try:
        xsrc, hooks = probe.sources
        assert isinstance(xsrc, XPlaneSource)
        assert isinstance(hooks, HooksSource)
        # the capture loop retries the hooks every cycle
        assert xsrc._on_cycle == hooks.start
    finally:
        probe.stop()


def test_hooks_wait_until_the_jobs_import_of_jax_completes(monkeypatch):
    """An agent thread that imports from jax while the job is still
    importing it hands the job half-initialized modules."""
    monkeypatch.setattr(jax.__spec__, "_initializing", True)
    assert not sources.jax_imported()
    assert not sources.jax_backends_initialized()
    hooks = HooksSource(sink=lambda evs: None).start()
    assert not hooks._registered
    monkeypatch.setattr(jax.__spec__, "_initializing", False)
    try:
        assert hooks.start()._registered
    finally:
        hooks.stop()


def test_xplane_loop_calls_on_cycle_and_polls_fast_before_jax(monkeypatch):
    monkeypatch.setattr(XPlaneSource, "available", lambda self: False)
    calls = []
    src = XPlaneSource(lambda evs: None, interval_s=60.0,
                       on_cycle=lambda: calls.append(time.monotonic()))
    src.start()
    try:
        deadline = time.monotonic() + 10
        while len(calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        src.stop()
    # a 60 s fallback gap would allow one cycle; waiting for JAX polls ~1 s
    assert len(calls) >= 2 and src.stats["skipped"] >= 2


def test_topology_lists_devices_only_once_the_job_brought_jax_up(
        monkeypatch):
    monkeypatch.setattr(topology, "jax_backends_initialized", lambda: False)
    assert len(topology.collect_platform_data().devices) == 0
    monkeypatch.setattr(topology, "jax_backends_initialized", lambda: True)
    p = topology.collect_platform_data()
    assert len(p.devices) == len(jax.devices())
    assert p.devices[0].device_kind == jax.devices()[0].device_kind


def test_synchronizer_recollects_topology_until_devices_seen(monkeypatch):
    """A first sync before the job's `import jax` must not leave the
    controller without the job's TPU topology for the rest of the run."""
    from deepflow_tpu.agent.agent import Agent
    from deepflow_tpu.server import Server

    server = Server(host="127.0.0.1", ingest_port=0, query_port=0,
                    sync_port=0, enable_controller=True).start()
    cfg = AgentConfig()
    cfg.sender.servers = [("127.0.0.1", server.ingest_port)]
    cfg.controller = f"127.0.0.1:{server.controller.port}"
    cfg.profiler.enabled = False
    cfg.tpuprobe.enabled = False
    cfg.sync_interval_s = 3600  # syncs below are driven by hand
    up = {"v": False}
    monkeypatch.setattr(topology, "jax_backends_initialized",
                        lambda: up["v"])
    agent = Agent(cfg).start()
    try:
        agent.synchronizer.sync_once()
        aid = agent.config.agent_id
        assert server.controller.devices_of(aid) == []
        up["v"] = True                   # the job brought its devices up
        agent.synchronizer.sync_once()
        devs = server.controller.devices_of(aid)
        assert len(devs) == len(jax.devices())
        (listed,) = [a for a in server.api.agents()["agents"]
                     if a["agent_id"] == aid]
        assert listed["devices"] == devs
        assert devs[0]["device_kind"] == jax.devices()[0].device_kind
    finally:
        agent.stop()
        server.stop()


def test_compile_cache_location(monkeypatch):
    from deepflow_tpu.utils import jaxcache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv(jaxcache.ENV, "/somewhere/else")
    assert jaxcache.enable_compile_cache() == "/somewhere/else"
    assert updates == []                 # JAX reads the variable itself
    monkeypatch.delenv(jaxcache.ENV)
    assert jaxcache.enable_compile_cache() == jaxcache.DEFAULT_DIR
    assert updates == [("jax_compilation_cache_dir", jaxcache.DEFAULT_DIR)]
    assert jaxcache.DEFAULT_DIR.endswith("/.jax_cache")


def test_attach_polices_only_the_agents_own_threads():
    from deepflow_tpu.agent import agent as agent_mod
    agent = agent_mod.attach(app_service="t", servers=[("127.0.0.1", 1)])
    try:
        assert agent_mod.current() is agent
        assert agent.in_process and agent.guard.in_process
    finally:
        agent_mod.detach()
    assert agent_mod.current() is None
