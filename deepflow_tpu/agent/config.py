"""Agent configuration.

Reference analog: agent/src/config (static UserConfig + controller-pushed
RuntimeConfig, hot-applied by ConfigHandler callbacks). Round-1 surface: a
typed dataclass loadable from YAML, controller push lands with the control
plane.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field


@dataclass
class ProfilerConfig:
    enabled: bool = True
    sample_hz: float = 99.0
    emit_interval_s: float = 1.0
    memory: bool = False            # tracemalloc allocation flame graphs
    memory_interval_s: float = 10.0
    # out-of-process perf_event_open targets (ANY pid, not just Python);
    # needs CAP_PERFMON or perf_event_paranoid <= 2 with same-user targets
    external_pids: list = field(default_factory=list)
    # additionally profile external_pids OFF-CPU (blocked + runqueue wait
    # flame graphs from context-switch events; needs kernel-context perf,
    # perf_event_paranoid <= 1 or CAP_PERFMON)
    external_offcpu: bool = False


@dataclass
class TpuProbeConfig:
    enabled: bool = True
    source: str = "auto"          # auto (= xplane) | xplane | hooks | sim
    trace_interval_s: float = 10.0  # fallback cadence before steps observed
    trace_duration_ms: int = 1000
    # step-adaptive duty cycle: windows sized to whole steps, gaps sized so
    # this fraction of ALL steps is captured
    target_coverage: float = 0.5
    steps_per_capture: int = 20
    # per-device HBM usage sampling cadence (allocator statistics; ~free).
    # 0 disables.
    memory_poll_s: float = 5.0
    # continuous per-step rollups (STEP_METRICS records: latency, skew,
    # collective wait, top-K HLO self-times per (run_id, step))
    step_metrics: bool = True
    step_topk: int = 5


@dataclass
class FlowConfig:
    enabled: bool = False           # needs CAP_NET_RAW
    interface: str = ""             # "" = all interfaces
    # local: this host's own traffic (self-ports excluded to break the
    # telemetry feedback loop); mirror: a SPAN/mirror port carrying OTHER
    # hosts' traffic (promiscuous, no self-port exclusion; tunnels are
    # decapsulated either way)
    capture_mode: str = "local"     # local | mirror | analyzer
    exclude_ports: list = field(
        default_factory=lambda: [20033, 20035, 20416])


@dataclass
class IntegrationConfig:
    enabled: bool = False
    host: str = "0.0.0.0"           # pods reach it via the node IP
    port: int = 38086
    server_http: str = "127.0.0.1:20416"


@dataclass
class GuardConfig:
    enabled: bool = True
    max_cpu_pct: float = 50.0
    max_mem_mb: float = 2048.0
    check_interval_s: float = 10.0


@dataclass
class SpoolConfig:
    """On-disk overflow/replay spool for the durable sender
    (deepflow_tpu/agent/spool.py): frames that would be dropped land in
    CRC-framed segment files and replay on reconnect."""
    enabled: bool = False
    dir: str = ""                 # "" = <tmpdir>/deepflow-spool-<agent_id>
    max_mb: int = 64              # oldest-segment eviction past this
    segment_mb: int = 4
    # age retention: closed segments older than this are evicted (0 =
    # size-only). Bounds how stale a replayed backlog can be after a
    # long server outage; evictions ledger as dropped(spool_age_evict).
    max_age_s: float = 0.0


@dataclass
class SenderConfig:
    servers: list = field(default_factory=lambda: [("127.0.0.1", 20033)])
    queue_size: int = 8192
    # durable transport: per-frame seq + server ACKs + retransmit window
    # (at-least-once; the server dedups). False = legacy fire-and-forget
    # v1 wire for pre-ACK servers.
    durable: bool = True
    # sent-but-unacked frames kept for retransmit after a reconnect
    ack_window: int = 1024
    # replication factor R: ship every HIGH/MID frame to the first R
    # servers (independent seq/ack/spool per destination) so a dead
    # primary's frames land durably on a replica. 1 = single-copy
    # (plain UniformSender, pre-replication behavior). Normally pushed
    # down from the controller's ring via analyzer_addrs.
    replication: int = 1
    spool: SpoolConfig = field(default_factory=SpoolConfig)


@dataclass
class ChaosConfig:
    """Deterministic transport fault injection (deepflow_tpu/chaos.py).
    The DF_CHAOS env knob overrides this block; both use per-call
    probabilities in [0,1]. Never enable in production — this exists so
    the chaos harness can prove the loss bounds hold."""
    enabled: bool = False
    seed: int = 0
    conn_refuse: float = 0.0
    conn_reset: float = 0.0
    partial_write: float = 0.0
    latency_ms: float = 0.0
    disk_full: float = 0.0


@dataclass
class BackpressureConfig:
    """Server-directed degradation (deepflow_tpu/qos): each Sync/Push
    response carries the ingest tier's pressure level for this agent's
    org (0 nominal .. 3 critical); the agent scales its own emission
    down by the level-indexed factors below. Level 0 restores the
    configured baselines exactly."""
    enabled: bool = True
    # one factor per pressure level 0..3, applied to the CONFIGURED
    # value (never compounded)
    hz_scale: list = field(
        default_factory=lambda: [1.0, 0.5, 0.25, 0.1])
    emit_scale: list = field(
        default_factory=lambda: [1.0, 1.0, 2.0, 4.0])
    topk_scale: list = field(
        default_factory=lambda: [1.0, 1.0, 0.5, 0.2])
    trace_scale: list = field(
        default_factory=lambda: [1.0, 1.0, 2.0, 4.0])


@dataclass
class SelfmonConfig:
    """Self-telemetry spine: frame ledger + heartbeats + deadman
    (deepflow_tpu/telemetry.py). Also disabled globally by
    DF_NO_SELFMON=1."""
    enabled: bool = True
    # a stage with no heartbeat for this long is flagged wedged (its
    # stack is snapshotted and shipped via dfstats)
    deadman_window_s: float = 15.0
    check_interval_s: float = 0.0   # 0 = deadman_window_s / 4


@dataclass
class AgentConfig:
    agent_id: int = 0
    app_service: str = ""
    # AF_UNIX path for the LD_PRELOAD ssl/syscall probe (pre-encryption L7
    # visibility); "" = disabled
    sslprobe_sock: str = ""
    # AF_UNIX path for the LD_PRELOAD malloc interposer (out-of-process
    # allocation flame graphs, libdfmemhook.so); "" = disabled
    memhook_sock: str = ""
    # agent-side ACLs (reference: policy first_path rules): list of dicts
    # {cidr, port, protocol, action: trace|ignore|pcap|npb} — pcap and
    # npb imply trace and additionally capture/forward matched PACKETS
    # (frame-visible paths: replay + socket capture mode)
    acls: list = field(default_factory=list)
    # NPB packet broker target for action=npb ACLs (reference:
    # plugins/npb_sender): matched frames are VXLAN-encapsulated to
    # host:port; "" disables forwarding
    npb_target: str = ""
    npb_vni: int = 1
    # parser plugin modules (reference: wasm plugin hooks): each exports
    # PARSERS = [L7Parser subclasses], registered ahead of builtins
    plugins: list = field(default_factory=list)
    group: str = "default"        # agent-group for config routing
    controller: str = ""          # host:port; empty = standalone mode
    standalone: bool = True
    # /proc socket-inode scan feeding GpidSync: flow logs get process
    # identity (gpid + comm) for ANY local process, no preload required.
    # 0 disables. Needs a controller (entries ride the sync plane).
    socket_scan_interval_s: float = 30.0
    profiler: ProfilerConfig = field(default_factory=ProfilerConfig)
    tpuprobe: TpuProbeConfig = field(default_factory=TpuProbeConfig)
    guard: GuardConfig = field(default_factory=GuardConfig)
    flow: FlowConfig = field(default_factory=FlowConfig)
    integration: IntegrationConfig = field(
        default_factory=IntegrationConfig)
    sender: SenderConfig = field(default_factory=SenderConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    selfmon: SelfmonConfig = field(default_factory=SelfmonConfig)
    qos: BackpressureConfig = field(default_factory=BackpressureConfig)
    stats_interval_s: float = 10.0
    sync_interval_s: float = 10.0

    @classmethod
    def from_dict(cls, d: dict) -> "AgentConfig":
        cfg = cls()
        if isinstance(d.get("profiler"), dict):
            cfg.profiler = ProfilerConfig(**d["profiler"])
        if isinstance(d.get("tpuprobe"), dict):
            cfg.tpuprobe = TpuProbeConfig(**d["tpuprobe"])
        if isinstance(d.get("guard"), dict):
            cfg.guard = GuardConfig(**d["guard"])
        if isinstance(d.get("integration"), dict):
            cfg.integration = IntegrationConfig(**d["integration"])
        if isinstance(d.get("flow"), dict):
            cfg.flow = FlowConfig(**d["flow"])
        if isinstance(d.get("sender"), dict):
            sd = dict(d["sender"])
            if "servers" in sd:
                sd["servers"] = [
                    tuple(x) if isinstance(x, (list, tuple))
                    else _parse_addr(x) for x in sd["servers"]]
            if isinstance(sd.get("spool"), dict):
                sd["spool"] = SpoolConfig(**sd["spool"])
            cfg.sender = SenderConfig(**sd)
        if isinstance(d.get("chaos"), dict):
            cfg.chaos = ChaosConfig(**d["chaos"])
        if isinstance(d.get("selfmon"), dict):
            cfg.selfmon = SelfmonConfig(**d["selfmon"])
        if isinstance(d.get("qos"), dict):
            cfg.qos = BackpressureConfig(**d["qos"])
        for f in dataclasses.fields(cls):
            if f.name in ("profiler", "tpuprobe", "guard", "integration",
                          "flow", "sender", "chaos", "selfmon", "qos"):
                continue
            if f.name in d:
                setattr(cfg, f.name, d[f.name])
        return cfg

    def validate(self) -> "AgentConfig":
        """Type/range checks (reference: template.yaml-driven validation)."""
        def num(v, name, lo=None, hi=None):
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(f"{name} must be a number, got {v!r}")
            if lo is not None and v < lo:
                raise ValueError(f"{name} must be >= {lo}, got {v}")
            if hi is not None and v > hi:
                raise ValueError(f"{name} must be <= {hi}, got {v}")

        num(self.profiler.sample_hz, "profiler.sample_hz", 0.1, 10_000)
        num(self.profiler.emit_interval_s, "profiler.emit_interval_s", 0.01)
        num(self.profiler.memory_interval_s, "profiler.memory_interval_s", 1)
        num(self.tpuprobe.trace_interval_s, "tpuprobe.trace_interval_s", 0.1)
        num(self.tpuprobe.trace_duration_ms, "tpuprobe.trace_duration_ms", 1)
        num(self.tpuprobe.target_coverage, "tpuprobe.target_coverage",
            0.01, 0.95)
        num(self.tpuprobe.steps_per_capture, "tpuprobe.steps_per_capture",
            1, 10_000)
        num(self.tpuprobe.step_topk, "tpuprobe.step_topk", 1, 100)
        num(self.stats_interval_s, "stats_interval_s", 0.1)
        num(self.sync_interval_s, "sync_interval_s", 0.1)
        num(self.selfmon.deadman_window_s, "selfmon.deadman_window_s", 0.1)
        num(self.selfmon.check_interval_s, "selfmon.check_interval_s", 0)
        num(self.sender.queue_size, "sender.queue_size", 1)
        num(self.sender.ack_window, "sender.ack_window", 1)
        num(self.sender.replication, "sender.replication", 1, 8)
        num(self.sender.spool.max_mb, "sender.spool.max_mb", 1)
        num(self.sender.spool.segment_mb, "sender.spool.segment_mb", 1)
        if self.sender.spool.segment_mb > self.sender.spool.max_mb:
            raise ValueError(
                "sender.spool.segment_mb must be <= sender.spool.max_mb "
                "(the cap must hold at least one segment)")
        if not isinstance(self.qos.enabled, bool):
            raise ValueError(
                f"qos.enabled must be a bool, got {self.qos.enabled!r}")
        for sname in ("hz_scale", "emit_scale", "topk_scale",
                      "trace_scale"):
            scales = getattr(self.qos, sname)
            if not isinstance(scales, (list, tuple)) or len(scales) != 4:
                raise ValueError(
                    f"qos.{sname} must be 4 factors (levels 0..3), "
                    f"got {scales!r}")
            for i, v in enumerate(scales):
                num(v, f"qos.{sname}[{i}]", 0.001, 1000)
        for p in ("conn_refuse", "conn_reset", "partial_write", "disk_full"):
            num(getattr(self.chaos, p), f"chaos.{p}", 0.0, 1.0)
        num(self.chaos.latency_ms, "chaos.latency_ms", 0)
        num(self.guard.max_cpu_pct, "guard.max_cpu_pct", 1)
        num(self.guard.max_mem_mb, "guard.max_mem_mb", 16)
        num(self.guard.check_interval_s, "guard.check_interval_s", 0.1)
        import ipaddress as _ipaddr
        for i, a in enumerate(self.acls):
            if not isinstance(a, dict):
                raise ValueError(f"acls[{i}] must be a mapping, got {a!r}")
            if a.get("action", "trace") not in ("trace", "ignore",
                                                "pcap", "npb"):
                raise ValueError(
                    f"acls[{i}].action must be trace|ignore|pcap|npb")
            if a.get("cidr"):
                try:
                    _ipaddr.ip_network(a["cidr"], strict=False)
                except ValueError as e:
                    raise ValueError(f"acls[{i}].cidr invalid: {e}") from None
            num(a.get("port", 0), f"acls[{i}].port", 0, 65535)
            num(a.get("protocol", 0), f"acls[{i}].protocol", 0, 3)
        if self.tpuprobe.source not in ("auto", "xplane", "hooks", "sim"):
            raise ValueError(
                f"tpuprobe.source must be auto|xplane|hooks|sim, "
                f"got {self.tpuprobe.source!r}")
        if self.flow.capture_mode not in ("local", "mirror", "analyzer"):
            raise ValueError(
                f"flow.capture_mode must be local|mirror|analyzer, "
                f"got {self.flow.capture_mode!r}")
        if self.flow.capture_mode in ("mirror", "analyzer") and \
                not self.flow.interface:
            raise ValueError(
                f"flow.capture_mode={self.flow.capture_mode} needs "
                "flow.interface: promiscuous mode is per-NIC, and an "
                "analyzer NIC must be named (capturing 'all' would "
                "include this host's own telemetry with exclusions off)")
        for b, name in ((self.profiler.enabled, "profiler.enabled"),
                        (self.tpuprobe.enabled, "tpuprobe.enabled"),
                        (self.tpuprobe.step_metrics,
                         "tpuprobe.step_metrics"),
                        (self.sender.durable, "sender.durable"),
                        (self.sender.spool.enabled, "sender.spool.enabled"),
                        (self.chaos.enabled, "chaos.enabled"),
                        (self.selfmon.enabled, "selfmon.enabled"),
                        (self.standalone, "standalone")):
            if not isinstance(b, bool):
                raise ValueError(f"{name} must be a bool, got {b!r}")
        return self

    @classmethod
    def load(cls, path: str | None = None) -> "AgentConfig":
        if path is None or not os.path.exists(path):
            return cls()
        import yaml
        with open(path) as f:
            data = yaml.safe_load(f) or {}
        return cls.from_dict(data)


DEFAULT_INGEST_PORT = 20033


_TEMPLATE_DOCS = {
    "agent_id": "0 = controller-assigned",
    "app_service": "logical service name (defaults to process name)",
    "group": "agent-group for config routing",
    "controller": "host:port; empty = standalone mode",
    "sslprobe_sock": "AF_UNIX path for the LD_PRELOAD ssl probe; empty=off",
    "acls": "policy rules: [{cidr, port, protocol, action: "
            "trace|ignore|pcap|npb}]; pcap/npb also capture/forward "
            "matched packets",
    "plugins": "parser plugin modules exporting PARSERS",
    "profiler.sample_hz": "OnCPU sampling rate",
    "profiler.external_pids": "out-of-process perf targets (any pid)",
    "tpuprobe.source": "auto (= xplane) | xplane | hooks | sim",
    "guard.max_cpu_pct": "in a job (deepflow-run / attach): the agent's "
                         "df-* threads; standalone: the process",
    "guard.max_mem_mb": "in a job: bytes the agent's sender buffers; "
                        "standalone: process RSS",
    "tpuprobe.target_coverage": "fraction of steps captured (0.01-0.95)",
    "tpuprobe.steps_per_capture": "whole steps per capture window",
    "tpuprobe.step_metrics": "emit per-(run_id, step) STEP_METRICS rollups",
    "tpuprobe.step_topk": "HLO self-times kept per step record",
    "flow.interface": "capture interface; empty = all",
    "flow.exclude_ports": "never capture these ports (feedback guard)",
    "sender.servers": "ingest endpoints, failover order",
    "sender.durable": "per-frame seq + server ACK + retransmit "
                      "(at-least-once); false = legacy v1 fire-and-forget",
    "sender.ack_window": "sent-but-unacked frames kept for retransmit",
    "sender.replication": "ship HIGH/MID frames to the first R servers "
                          "(per-destination seq/ack/spool); 1 = "
                          "single-copy",
    "sender.spool.enabled": "spill overflow/unsent frames to disk and "
                            "replay them on reconnect",
    "sender.spool.dir": "segment directory; empty = tmpdir",
    "sender.spool.max_mb": "spool cap; oldest segment evicted (and "
                           "ledgered as dropped) past this",
    "sender.spool.segment_mb": "rotate segment files at this size",
    "sender.spool.max_age_s": "evict closed segments older than this "
                              "(dropped(spool_age_evict)); 0 = "
                              "size-only retention",
    "chaos.enabled": "transport fault injection (tests only); the "
                     "DF_CHAOS env spec overrides this block",
    "chaos.seed": "PRNG seed — same seed, same fault schedule",
    "selfmon.deadman_window_s": "flag a stage wedged after this many "
                                "seconds without a heartbeat",
    "selfmon.check_interval_s": "deadman scan cadence; 0 = window/4",
    "qos.enabled": "honor server backpressure directives "
                   "(SyncResponse.qos pressure level 0..3)",
    "qos.hz_scale": "profiler sample_hz factor per pressure level 0..3",
    "qos.emit_scale": "profile emit-interval factor per level (bigger "
                      "window = fewer, larger frames)",
    "qos.topk_scale": "step-metrics HLO top-K factor per level",
    "qos.trace_scale": "tpuprobe trace interval / steps-per-capture "
                       "factor per level",
}


def render_template() -> str:
    """Documented YAML template generated FROM the dataclasses (reference:
    the 6535-line template.yaml that validates agent-group configs —
    here the dataclass is the single source of truth, so template and
    validation can't drift)."""
    import dataclasses
    lines = ["# deepflow-tpu agent configuration template",
             "# generated from AgentConfig (single source of truth);",
             "# every value shows its default — uncomment to override.",
             ""]

    def emit(obj, prefix: str, indent: str) -> None:
        for f in dataclasses.fields(obj):
            val = getattr(obj, f.name)
            key = f"{prefix}{f.name}" if prefix else f.name
            doc = _TEMPLATE_DOCS.get(key)
            if dataclasses.is_dataclass(val):
                lines.append(f"{indent}{f.name}:")
                emit(val, f"{key}.", indent + "  ")
                continue
            if doc:
                lines.append(f"{indent}# {doc}")
            if isinstance(val, (list, tuple)):
                import json as _j
                shown = _j.dumps([list(v) if isinstance(v, tuple) else v
                                  for v in val])
            elif isinstance(val, bool):
                shown = "true" if val else "false"
            else:
                shown = repr(val) if isinstance(val, str) else str(val)
            lines.append(f"{indent}{f.name}: {shown}")
        lines.append("")

    emit(AgentConfig(), "", "")
    return "\n".join(lines)


def _parse_addr(s: str) -> tuple[str, int]:
    host, sep, port = s.rpartition(":")
    if not sep:
        return (s or "127.0.0.1", DEFAULT_INGEST_PORT)
    try:
        return (host or "127.0.0.1", int(port))
    except ValueError:
        raise ValueError(f"bad server address {s!r}: expected host[:port]"
                         ) from None
