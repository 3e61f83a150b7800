"""Agent runtime: wires profilers + probes + stats into the uniform sender.

Reference analog: agent/src/trident.rs (Components wiring) — scaled to the
round-1 component set: OnCPU sampler, TPU probe, self-stats. Runs standalone
(no controller, reference `--standalone` mode) or controller-managed once the
sync plane lands.
"""

from __future__ import annotations

import functools
import logging
import os
import sys
import threading
import time

from deepflow_tpu.agent.config import AgentConfig
from deepflow_tpu.agent.profiler import OnCpuSampler, ProfileSample
from deepflow_tpu.agent.sender import UniformSender
from deepflow_tpu.codec import MessageType
from deepflow_tpu.proto import pb

log = logging.getLogger("df.agent")


class Agent:
    def __init__(self, config: AgentConfig | None = None,
                 in_process: bool = False) -> None:
        self.config = config or AgentConfig()
        # attached inside the job it profiles (deepflow-run / attach):
        # the process's CPU and RSS are the job's, not the agent's
        self.in_process = in_process
        self.process_name = os.path.basename(sys.argv[0]) or "python"
        self.app_service = self.config.app_service or self.process_name
        # self-telemetry spine: hop ledger + heartbeats + deadman
        # (deepflow_tpu/telemetry.py); one registry per Agent instance
        from deepflow_tpu.telemetry import DeadmanDetector, Telemetry
        sm = self.config.selfmon
        # config False forces off; config True still honors DF_NO_SELFMON
        self.telemetry = Telemetry(
            "agent", enabled=None if sm.enabled else False)
        self.deadman = DeadmanDetector(
            self.telemetry, window_s=sm.deadman_window_s,
            check_interval_s=sm.check_interval_s or None,
            on_wedge=self._on_wedge)
        # the agent's own spans (probe cycles, the frames they hand the
        # sender) on the querier's tracer; no table here, so they are
        # kept in a ring (`recent_spans`, the "spans" ops command)
        from deepflow_tpu.query import qtrace
        self.span_ring = qtrace.SpanRing()
        self.qtracer = qtrace.QueryTracer(
            self.telemetry, service="deepflow-agent", sink=self.span_ring)
        if self.config.sender.replication > 1:
            from deepflow_tpu.agent.sender import ReplicatedSender
            self.sender = ReplicatedSender(
                self.config.sender.servers,
                replication=self.config.sender.replication,
                agent_id=self.config.agent_id,
                queue_size=self.config.sender.queue_size,
                telemetry=self.telemetry,
                durable=self.config.sender.durable,
                ack_window=self.config.sender.ack_window,
                spool_factory=self._build_spool_factory(),
                chaos=self._build_chaos(), tracer=self.qtracer)
        else:
            self.sender = UniformSender(
                self.config.sender.servers, agent_id=self.config.agent_id,
                queue_size=self.config.sender.queue_size,
                telemetry=self.telemetry,
                durable=self.config.sender.durable,
                ack_window=self.config.sender.ack_window,
                spool=self._build_spool(),
                chaos=self._build_chaos(), tracer=self.qtracer)
        self.sampler: OnCpuSampler | None = None
        self.memprofiler = None
        self.extprofilers: list = []
        self.tpuprobe = None
        self.tpuprobe_calibration = None  # kept across guard pauses
        self.synchronizer = None
        self.socket_scanner = None
        self.guard = None
        self.integration_proxy = None
        self.dispatcher = None
        self.live_capture = None
        self.sslprobe = None
        self.memhook = None
        from deepflow_tpu.agent.labeler import AclRule, Labeler
        self.labeler = Labeler()
        self.labeler.load_acls([
            AclRule(cidr=a.get("cidr", ""), port=int(a.get("port", 0)),
                    protocol=int(a.get("protocol", 0)),
                    action=a.get("action", "trace"))
            for a in getattr(self.config, "acls", [])])
        self._stats_thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._components: list[str] = []
        # serializes sampler/tpuprobe lifecycle across guard, synchronizer
        # and stats threads
        self._profiler_lock = threading.RLock()
        # server-directed backpressure (qos/): last pressure level
        # applied from a SyncResponse.qos directive (0 = nominal)
        self.pressure_level = 0

    def _build_spool(self):
        sc = self.config.sender.spool
        if not sc.enabled:
            return None
        import tempfile
        from deepflow_tpu.agent.spool import Spool
        directory = sc.dir or os.path.join(
            tempfile.gettempdir(),
            f"deepflow-spool-{self.config.agent_id}")
        return Spool(directory, max_bytes=sc.max_mb << 20,
                     segment_bytes=sc.segment_mb << 20,
                     max_age_s=sc.max_age_s)

    def _build_spool_factory(self):
        """Replicated transport: one spool SUBDIRECTORY per destination
        (each destination has its own seq space; sharing a spool would
        interleave them and break trim/replay watermarks)."""
        sc = self.config.sender.spool
        if not sc.enabled:
            return None
        import tempfile
        from deepflow_tpu.agent.spool import Spool
        base = sc.dir or os.path.join(
            tempfile.gettempdir(),
            f"deepflow-spool-{self.config.agent_id}")

        def factory(dest_key: str):
            return Spool(os.path.join(base, dest_key),
                         max_bytes=sc.max_mb << 20,
                         segment_bytes=sc.segment_mb << 20,
                         max_age_s=sc.max_age_s)

        return factory

    def _build_chaos(self):
        # DF_CHAOS (env) wins over the config block; the sender also
        # falls back to the env knob itself when this returns None, so
        # returning None here means "no config-driven injector"
        from deepflow_tpu.chaos import ChaosConfig, ChaosInjector, \
            chaos_from_env
        env = chaos_from_env()
        if env is not None:
            return env
        cc = self.config.chaos
        if not cc.enabled:
            return None
        return ChaosInjector(ChaosConfig(
            enabled=True, seed=cc.seed, conn_refuse=cc.conn_refuse,
            conn_reset=cc.conn_reset, partial_write=cc.partial_write,
            latency_ms=cc.latency_ms, disk_full=cc.disk_full))

    # -- lifecycle -----------------------------------------------------------

    def start_sampler(self) -> None:
        with self._profiler_lock:
            if self.sampler is not None:
                return
            if self.guard is not None and self.guard.degraded:
                return  # guard has profiling paused; resume handles restart
            self.sampler = OnCpuSampler(
                self._profile_sink,
                hz=self.config.profiler.sample_hz,
                emit_interval_s=self.config.profiler.emit_interval_s,
                process_name=self.process_name,
                app_service=self.app_service).start()

    def start_tpuprobe(self) -> None:
        with self._profiler_lock:
            if self.tpuprobe is not None:
                return
            if self.guard is not None and self.guard.degraded:
                return
            try:
                from deepflow_tpu.tpuprobe.probe import TpuProbe
            except ImportError as e:
                log.warning("tpuprobe unavailable: %s", e)
                return
            self.tpuprobe = TpuProbe(self).start()

    def start_memprofiler(self) -> None:
        with self._profiler_lock:
            if self.memprofiler is not None:
                return
            if self.guard is not None and self.guard.degraded:
                return
            from deepflow_tpu.agent.memprofiler import MemProfiler
            self.memprofiler = MemProfiler(
                self._profile_sink,
                interval_s=self.config.profiler.memory_interval_s).start()

    def start_extprofilers(self) -> None:
        with self._profiler_lock:
            if self.extprofilers:
                return
            if self.guard is not None and self.guard.degraded:
                return
            for pid in self.config.profiler.external_pids:
                try:
                    from deepflow_tpu.agent.extprofiler import \
                        ExternalProfiler
                    ep = ExternalProfiler(
                        None, pid=int(pid),
                        hz=self.config.profiler.sample_hz,
                        window_s=self.config.profiler.emit_interval_s)
                    # samples carry the TARGET's identity, captured at
                    # attach time (the target may exit before the last emit)
                    ep.sink = functools.partial(
                        self._profile_sink, process_name=ep.process_name,
                        app_service=ep.app_service)
                    ep.start()
                    self.extprofilers.append(ep)
                    if f"extprof-{pid}" not in self._components:
                        self._components.append(f"extprof-{pid}")
                    if self.config.profiler.external_offcpu:
                        from deepflow_tpu.agent.extprofiler import \
                            OffCpuProfiler
                        op = OffCpuProfiler(
                            None, pid=int(pid),
                            window_s=self.config.profiler.emit_interval_s)
                        op.sink = functools.partial(
                            self._profile_sink,
                            process_name=op.process_name,
                            app_service=op.app_service)
                        op.start()
                        self.extprofilers.append(op)
                        if f"offcpu-{pid}" not in self._components:
                            self._components.append(f"offcpu-{pid}")
                except (OSError, RuntimeError, ImportError,
                        AttributeError) as e:
                    # AttributeError: stale libdfnative.so without the
                    # df_prof_* symbols — degrade, don't abort startup
                    log.warning("external profiler for pid %s unavailable:"
                                " %s", pid, e)

    def pause_profilers(self) -> None:
        with self._profiler_lock:
            if self.sampler is not None:
                self.sampler.stop()
                self.sampler = None
            if self.memprofiler is not None:  # tracemalloc costs real CPU
                self.memprofiler.stop()
                self.memprofiler = None
            for ep in self.extprofilers:  # drain+symbolize burns agent CPU
                ep.stop()
            self.extprofilers = []
            if self.tpuprobe is not None:
                self.tpuprobe.stop()
                self.tpuprobe = None

    def resume_profilers(self) -> None:
        with self._profiler_lock:
            if self.config.profiler.enabled:
                self.start_sampler()
            if self.config.profiler.memory:
                self.start_memprofiler()
            if self.config.tpuprobe.enabled:
                self.start_tpuprobe()
        self.start_extprofilers()

    def apply_backpressure(self, level: int) -> None:
        """Degrade gracefully under server-reported ingest pressure
        (SyncResponse.qos): sampler hz shrinks, profile emit windows
        widen (fewer, larger frames), HLO top-K narrows, trace captures
        thin out — per-level factors from config.qos. Idempotent per
        level; scales apply to the CONFIGURED values (never compounded),
        so level 0 restores the baselines exactly."""
        cfg = self.config
        if not cfg.qos.enabled:
            return
        level = max(0, min(3, int(level)))
        if level == self.pressure_level:
            return
        prev, self.pressure_level = self.pressure_level, level
        trace_scale = cfg.qos.trace_scale[level]
        with self._profiler_lock:
            sampler = self.sampler
            if sampler is not None:
                hz = max(1.0, cfg.profiler.sample_hz
                         * cfg.qos.hz_scale[level])
                sampler.period_s = 1.0 / hz
                sampler.period_us = int(1_000_000 / hz)
                sampler.emit_interval_s = (cfg.profiler.emit_interval_s
                                           * cfg.qos.emit_scale[level])
            probe = self.tpuprobe
            if probe is not None:
                if probe.stepagg is not None:
                    base = getattr(cfg.tpuprobe, "step_topk", 5)
                    probe.stepagg.topk = max(
                        1, int(base * cfg.qos.topk_scale[level]))
                for src in probe.sources:
                    if hasattr(src, "interval_s"):
                        src.interval_s = (cfg.tpuprobe.trace_interval_s
                                          * trace_scale)
                    if hasattr(src, "steps_per_capture"):
                        src.steps_per_capture = max(1, int(
                            cfg.tpuprobe.steps_per_capture * trace_scale))
        log.info("backpressure level %d -> %d", prev, level)

    def start(self) -> "Agent":
        plugins = getattr(self.config, "plugins", [])
        if plugins:
            from deepflow_tpu.agent.ops import load_plugins
            load_plugins(plugins)
        self.deadman.start()
        self.sender.start()
        self._components.append("sender")
        if self.config.profiler.enabled:
            self.start_sampler()
            self._components.append("oncpu-sampler")
        if self.config.profiler.memory:
            self.start_memprofiler()
            self._components.append("mem-profiler")
        self.start_extprofilers()
        if self.config.tpuprobe.enabled:
            self.start_tpuprobe()
            if self.tpuprobe is not None:
                self._components.append("tpuprobe")
        has_pkt_acls = any(a.get("action") in ("pcap", "npb")
                           for a in getattr(self.config, "acls", []))
        if self.config.flow.enabled or self.config.sslprobe_sock or \
                has_pkt_acls:
            from deepflow_tpu.agent.dispatcher import Dispatcher
            self.dispatcher = Dispatcher(
                sender=self.sender,
                agent_id=self.config.agent_id,
                labeler=self.labeler,
                telemetry=self.telemetry).start()
            from deepflow_tpu.agent.packet_actions import PacketActions
            self.dispatcher.packet_actions = PacketActions(
                self.labeler, sender=self.sender,
                agent_id=self.config.agent_id,
                npb_target=self.config.npb_target,
                npb_vni=self.config.npb_vni)
        if self.config.sslprobe_sock:
            from deepflow_tpu.agent.sslprobe import SslProbeListener
            self.sslprobe = SslProbeListener(
                self.dispatcher, self.config.sslprobe_sock).start()
            self._components.append("ssl-probe")
        if self.config.memhook_sock:
            from deepflow_tpu.agent.memhook import MemHookListener

            def _mem_sink(batch):
                pid = batch[0].pid if batch else 0
                try:
                    with open(f"/proc/{pid}/comm") as f:
                        name = f.read().strip()
                except OSError:
                    name = str(pid)
                self._profile_sink(batch, process_name=name,
                                   app_service=name)
            self.memhook = MemHookListener(
                _mem_sink, self.config.memhook_sock).start()
            self._components.append("memhook")
        if self.config.flow.enabled:
            from deepflow_tpu.agent.live_capture import LiveCapture
            # the agent's own telemetry must never be captured (feedback
            # amplification): union the REAL sender ports into the exclusions
            exclude = set(self.config.flow.exclude_ports)
            exclude.update(p for _, p in self.sender.servers)
            try:
                self.live_capture = LiveCapture(
                    self.dispatcher,
                    interface=self.config.flow.interface,
                    exclude_ports=tuple(exclude),
                    capture_mode=self.config.flow.capture_mode,
                ).start()
                self._components.append("live-capture")
            except (OSError, AttributeError) as e:
                # PermissionError (no CAP_NET_RAW), ENODEV (bad iface),
                # AttributeError (no AF_PACKET on this OS): degrade
                log.warning("live capture unavailable (%s); replay and "
                            "synthetic sources still work", e)
        if self.config.integration.enabled:
            from deepflow_tpu.agent.integration_proxy import IntegrationProxy
            ic = self.config.integration
            self.integration_proxy = IntegrationProxy(
                ic.server_http, host=ic.host, port=ic.port).start()
            self._components.append("integration-proxy")
        if self.config.guard.enabled:
            from deepflow_tpu.agent.guard import Guard
            g = self.config.guard
            self.guard = Guard(
                self, max_cpu_pct=g.max_cpu_pct, max_mem_mb=g.max_mem_mb,
                check_interval_s=g.check_interval_s,
                in_process=self.in_process).start()
            self._components.append("guard")
        if self.config.controller:
            from deepflow_tpu.agent.synchronizer import Synchronizer
            self.synchronizer = Synchronizer(
                self, self.config.controller,
                interval_s=self.config.sync_interval_s).start()
            self._components.append("synchronizer")
            if getattr(self.config, "socket_scan_interval_s", 0) > 0:
                from deepflow_tpu.agent.socket_scan import SocketScanner
                self.socket_scanner = SocketScanner(
                    self.synchronizer, agent_id=self.config.agent_id,
                    interval_s=self.config.socket_scan_interval_s).start()
                self._components.append("socket-scan")
        self._stats_thread = threading.Thread(
            target=self._stats_loop, name="df-agent-stats", daemon=True)
        self._stats_thread.start()
        self._components.append("stats")
        log.info("agent started: %s", ", ".join(self._components))
        return self

    def stop(self) -> None:
        self._stop.set()
        self.deadman.stop()
        if self.guard:
            self.guard.stop()
        if getattr(self, "socket_scanner", None):
            self.socket_scanner.stop()
        if self.synchronizer:
            self.synchronizer.stop()
        if self.sampler:
            self.sampler.stop()
        if self.memprofiler:
            self.memprofiler.stop()
        for ep in self.extprofilers:
            ep.stop()
        self.extprofilers = []
        if self.tpuprobe:
            self.tpuprobe.stop()
        if self.integration_proxy:
            self.integration_proxy.stop()
        if self.sslprobe:
            self.sslprobe.stop()
        if self.memhook:
            self.memhook.stop()
        if self.live_capture:
            self.live_capture.stop()
        if self.dispatcher:
            if self.dispatcher.packet_actions is not None:
                self.dispatcher.packet_actions.stop()
            self.dispatcher.stop()
        self._emit_stats()  # final stats flush
        self.sender.flush_and_stop()

    def ensure_packet_actions(self, cfg=None) -> None:
        """Controller-pushed pcap/npb ACLs need a dispatcher + executor
        even when the agent booted without one (hot-apply path)."""
        cfg = cfg or self.config
        if self.dispatcher is None:
            from deepflow_tpu.agent.dispatcher import Dispatcher
            self.dispatcher = Dispatcher(
                sender=self.sender, agent_id=self.config.agent_id,
                labeler=self.labeler, telemetry=self.telemetry).start()
            self._components.append("dispatcher")
        if self.dispatcher.packet_actions is None:
            from deepflow_tpu.agent.packet_actions import PacketActions
            self.dispatcher.packet_actions = PacketActions(
                self.labeler, sender=self.sender,
                agent_id=self.config.agent_id,
                npb_target=getattr(cfg, "npb_target", ""),
                npb_vni=getattr(cfg, "npb_vni", 1))

    # -- sinks ---------------------------------------------------------------

    def _profile_sink(self, batch: list[ProfileSample],
                      process_name: str | None = None,
                      app_service: str | None = None) -> None:
        out = pb.ProfileBatch()
        for s in batch:
            p = out.profiles.add()
            p.process_name = process_name or self.process_name
            p.app_service = app_service or self.app_service
            p.pid = s.pid
            p.tid = s.tid & 0xFFFFFFFF
            p.thread_name = s.thread_name
            p.event_type = _EVENT_TYPES.get(s.event_type, pb.ON_CPU)
            p.timestamp_ns = s.timestamp_ns
            p.stack = s.stack.encode()
            p.value = s.value_us
            p.count = s.count
            p.profiler = s.profiler
        self.sender.send(MessageType.PROFILE, out.SerializeToString())

    def send_tpu_spans(self, spans_pb: "pb.TpuSpanBatch") -> None:
        self.sender.send(MessageType.TPU_SPAN, spans_pb.SerializeToString())

    def send_step_metrics(self, payload: bytes) -> bool:
        """Per-step rollup records (pre-encoded STEP_METRICS payload —
        JSON, not protobuf; see tpuprobe/stepmetrics.py)."""
        return self.sender.send(MessageType.STEP_METRICS, payload)

    def recent_spans(self) -> list[dict]:
        """The agent's last finished spans, oldest first."""
        self.qtracer.flush()
        return self.span_ring.snapshot()

    # -- self-telemetry (reference: agent/src/utils/stats.rs -> dfstats) -----

    def _on_wedge(self, verdict: dict) -> None:
        """Deadman verdict: ship it IMMEDIATELY (the stats loop may be
        minutes away — a wedge report must not wait on a schedule)."""
        try:
            self._emit_stats()
        except Exception:
            log.exception("wedge stats emit failed")

    def _stats_loop(self) -> None:
        hb = self.telemetry.heartbeat(
            "stats", interval_hint_s=self.config.stats_interval_s)
        hb.beat()
        while not self._stop.wait(self.config.stats_interval_s):
            hb.beat()
            try:
                self._emit_stats()
            except Exception:
                log.exception("stats emit failed")  # never kill the loop

    def _emit_stats(self) -> None:
        batch = pb.StatsBatch()
        ts = time.time_ns()

        def metric(name: str, values: dict,
                   extra_tags: dict | None = None) -> None:
            m = batch.metrics.add()
            m.name = name
            m.timestamp_ns = ts
            m.tags["process"] = self.process_name
            if extra_tags:
                for k, v in extra_tags.items():
                    m.tags[k] = str(v)
            for k, v in values.items():
                m.values[k] = float(v)

        metric("agent.sender", self.sender.stats)
        sampler, tpuprobe = self.sampler, self.tpuprobe  # racy nulling-safe
        if sampler is not None:
            st = sampler.stats
            metric("agent.oncpu_sampler", {
                "samples": st.samples, "emits": st.emits,
                "overruns": st.overruns})
        if tpuprobe is not None:
            metric("agent.tpuprobe", tpuprobe.stats)
        if self.integration_proxy is not None:
            metric("agent.integration_proxy", self.integration_proxy.stats)
        if self.live_capture is not None:
            metric("agent.live_capture", self.live_capture.stats)
        if self.dispatcher is not None:
            metric("agent.flow_map", self.dispatcher.flow_map.stats)
        if self.guard is not None:
            metric("agent.guard", {
                "cpu_pct": self.guard.cpu_pct,
                "rss_mb": self.guard.rss_mb,
                "degraded": int(self.guard.degraded),
                **self.guard.stats})
        if self.pressure_level:
            metric("agent.qos", {"pressure_level": self.pressure_level})
        sync = getattr(self, "synchronizer", None)
        if sync is not None and sync.stats.get("ntp_syncs"):
            metric("agent.clock", {
                "offset_ms": sync.clock_offset_ns / 1e6,
                "ntp_rtt_ms": sync.ntp_rtt_ns / 1e6})
        # the self-telemetry spine: hop ledger, stage heartbeats, phase
        # durations, wedge verdicts — all ride the same DFSTATS batch
        # into deepflow_system (the span ring's ledger settles first)
        self.qtracer.flush()
        for name, tags, values in self.telemetry.stats_metrics():
            metric(name, values, extra_tags=tags)
        self.sender.send(MessageType.DFSTATS, batch.SerializeToString())


_EVENT_TYPES = {
    "on-cpu": pb.ON_CPU,
    "off-cpu": pb.OFF_CPU,
    "mem-alloc": pb.MEM_ALLOC,
    "tpu-device": pb.TPU_DEVICE,
    "tpu-host": pb.TPU_HOST,
}

_GLOBAL_AGENT: Agent | None = None
_ATEXIT_REGISTERED = False


def attach(app_service: str = "", servers: list | None = None,
           **overrides) -> Agent:
    """In-process zero-code attach: start an agent inside the current
    process (used by `deepflow-run` and direct instrumentation)."""
    global _GLOBAL_AGENT
    if _GLOBAL_AGENT is not None:
        return _GLOBAL_AGENT
    cfg = AgentConfig()
    if app_service:
        cfg.app_service = app_service
    if servers:
        cfg.sender.servers = servers
    for k, v in overrides.items():
        setattr(cfg, k, v)
    _GLOBAL_AGENT = Agent(cfg, in_process=True).start()
    # interpreter teardown with a live xplane capture aborts the process
    # (daemon thread inside jax.profiler during shutdown): detach cleanly.
    # Registered once per process; detach() is idempotent.
    global _ATEXIT_REGISTERED
    if not _ATEXIT_REGISTERED:
        import atexit
        atexit.register(detach)
        _ATEXIT_REGISTERED = True
    return _GLOBAL_AGENT


def current() -> Agent | None:
    """The agent attach() started in this process, if any."""
    return _GLOBAL_AGENT


def detach() -> None:
    global _GLOBAL_AGENT
    if _GLOBAL_AGENT is not None:
        _GLOBAL_AGENT.stop()
        _GLOBAL_AGENT = None


def main() -> None:
    import argparse
    parser = argparse.ArgumentParser(description="deepflow-tpu agent")
    parser.add_argument("-f", "--config", default=None)
    parser.add_argument("--standalone", action="store_true")
    parser.add_argument("--server", default=None,
                        help="host:port (overrides config when given)")
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args()
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    cfg = AgentConfig.load(args.config)
    if args.standalone:
        cfg.standalone = True
        cfg.controller = ""
    if args.server is not None:
        from deepflow_tpu.agent.config import _parse_addr
        cfg.sender.servers = [_parse_addr(args.server)]
    agent = Agent(cfg).start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        agent.stop()


if __name__ == "__main__":
    main()
