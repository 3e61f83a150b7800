import time

from deepflow_tpu.agent.agent import Agent
from deepflow_tpu.agent.config import AgentConfig
from deepflow_tpu.agent.guard import Guard, read_self_usage


def make_agent():
    cfg = AgentConfig()
    cfg.guard.enabled = False       # manual guard in tests
    cfg.profiler.enabled = True
    cfg.tpuprobe.enabled = False
    cfg.sender.servers = [("127.0.0.1", 1)]
    return Agent(cfg).start()


def test_read_self_usage():
    cpu_s, rss = read_self_usage()
    assert cpu_s > 0
    assert rss > 10 * 1024 * 1024  # a python process is >10MB


def test_guard_degrade_and_recover():
    agent = make_agent()
    try:
        g = Guard(agent, max_cpu_pct=50.0, max_mem_mb=4096)
        g._last = (0.0, 0.0)
        assert agent.sampler is not None

        # force a breach: fake 100% cpu
        g.cpu_pct = 100.0
        g.rss_mb = 100.0
        g._evaluate()
        assert g.degraded
        assert g.exception_bitmap & 1
        assert agent.sampler is None  # profilers paused

        # recovery below hysteresis threshold resumes them
        g.cpu_pct = 10.0
        g._evaluate()
        assert not g.degraded
        assert agent.sampler is not None
        assert g.stats["degrades"] == 1 and g.stats["recoveries"] == 1
    finally:
        agent.stop()


def test_guard_cpu_accounting():
    agent = make_agent()
    try:
        g = Guard(agent, max_cpu_pct=10_000, max_mem_mb=1 << 20)
        g.check(now=100.0)
        t0 = time.process_time()
        while time.process_time() - t0 < 0.3:
            sum(i * i for i in range(1000))
        # pretend 1s wall elapsed -> cpu_pct ≈ 30+
        g.check(now=101.0)
        assert g.cpu_pct > 10.0
        assert not g.degraded
    finally:
        agent.stop()


def test_config_push_cannot_override_degraded_guard():
    """start_sampler is a no-op while the guard has profiling paused."""
    agent = make_agent()
    try:
        g = Guard(agent, max_cpu_pct=50.0, max_mem_mb=4096)
        agent.guard = g
        g.cpu_pct = 100.0
        g._evaluate()
        assert g.degraded and agent.sampler is None
        # a config push (or anyone) trying to restart is refused
        agent.start_sampler()
        assert agent.sampler is None
        # recovery resumes per config
        g.cpu_pct = 1.0
        g.rss_mb = 10.0
        g._evaluate()
        assert agent.sampler is not None
    finally:
        agent.stop()


def test_guard_limits_retune_via_config_push():
    import yaml as _yaml
    from deepflow_tpu.agent.synchronizer import Synchronizer
    agent = make_agent()
    try:
        agent.guard = Guard(agent, max_cpu_pct=50.0, max_mem_mb=4096)
        sync = Synchronizer.__new__(Synchronizer)
        sync.agent = agent
        sync._apply_config(b"guard:\n  max_cpu_pct: 20.0\n", version=2)
        assert agent.guard.max_cpu_pct == 20.0
        assert agent.config.guard.max_cpu_pct == 20.0
    finally:
        agent.stop()


def _burn(seconds: float) -> None:
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        sum(i * i for i in range(1000))


def test_in_process_guard_ignores_the_jobs_threads():
    """Attached inside a job, the guard holds only the agent's df-*
    threads to its CPU limit: the job's own compute must not pause the
    profilers, and process RSS (the job's) is not the agent's: memory is
    what the agent's sender buffers."""
    import threading
    agent = make_agent()
    try:
        g = Guard(agent, max_cpu_pct=10.0, max_mem_mb=16,
                  in_process=True)
        g.check(now=100.0)
        _burn(0.3)                       # the job's main thread
        g.check(now=101.0)
        assert g.cpu_pct < 10.0 and g.rss_mb < 16
        assert not g.degraded

        burned, hold = threading.Event(), threading.Event()

        def busy():
            _burn(0.3)
            burned.set()
            hold.wait(5)             # stay alive until measured

        g.check(now=102.0)
        th = threading.Thread(target=busy, name="df-test-busy")
        th.start()
        try:
            assert burned.wait(10)
            g.check(now=103.0)
            assert g.cpu_pct > 10.0      # an agent thread's CPU counts
            assert g.degraded
        finally:
            hold.set()
            th.join()
    finally:
        agent.stop()


def test_in_process_guard_holds_buffered_bytes_to_max_mem():
    """Frames the sender cannot ship pile up in the agent's memory: past
    max_mem_mb the in-process guard pauses the profilers that make them."""
    from deepflow_tpu.codec import MessageType
    agent = make_agent()                 # its server port never answers
    try:
        g = Guard(agent, max_cpu_pct=1e6, max_mem_mb=1, in_process=True)
        g.check(now=100.0)
        assert not g.degraded
        for _ in range(3):
            agent.sender.send(MessageType.PROFILE, b"x" * (512 << 10))
        assert agent.sender.buffered_bytes() >= 3 * (512 << 10)
        g.check(now=101.0)
        assert g.rss_mb >= 1.5 and g.degraded
        assert g.exception_bitmap & 2    # EXC_MEM_LIMIT
    finally:
        agent.stop()
