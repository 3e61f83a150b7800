"""Agent-side controller sync loop.

Reference analog: agent/src/rpc/synchronizer.rs (run :1921 — periodic Sync,
on_response :1135 config diff + hot apply). Hand-built gRPC method calls
(no generated stubs on this image).
"""

from __future__ import annotations

import logging
import os
import socket
import statistics
import threading
import time
from collections import deque

import grpc

from deepflow_tpu.proto import pb

log = logging.getLogger("df.sync")

_SYNC = "/deepflow_tpu.Synchronizer/Sync"
_NTP = "/deepflow_tpu.Synchronizer/Ntp"
_GPID = "/deepflow_tpu.Synchronizer/GpidSync"
_PUSH = "/deepflow_tpu.Synchronizer/Push"
_PODMAP = "/deepflow_tpu.Synchronizer/PodMap"
_PKG = "/deepflow_tpu.Synchronizer/FetchPackage"


class Synchronizer:
    def __init__(self, agent, controller_addr: str,
                 interval_s: float = 10.0) -> None:
        self.agent = agent
        self.addr = controller_addr
        self.interval_s = interval_s
        self._channel: grpc.Channel | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._push_thread: threading.Thread | None = None
        self._push_call = None
        self.config_version = 0
        self.config_epoch = 0
        self.platform_version = 0
        self._platform_cache: pb.PlatformData | None = None
        self._configured_servers = list(agent.sender.servers)  # for revert
        self._pending_results: list = []
        self._results_lock = threading.Lock()  # sync loop + upgrade timer
        from deepflow_tpu.agent.ops import CommandRegistry
        self._ops = CommandRegistry(agent)
        self._apply_lock = threading.Lock()  # poll + push threads both apply
        self.stats = {"syncs": 0, "errors": 0, "config_updates": 0}
        # NTP clock sync vs the controller (reference: rpc/ntp.rs): median
        # over recent min-rtt exchanges damps outliers from GC/net jitter
        self.clock_offset_ns = 0
        self.ntp_rtt_ns = 0
        self._ntp_samples: deque[int] = deque(maxlen=5)

    def start(self) -> "Synchronizer":
        # message caps sized for OTA packages (PackageRepo.MAX_PACKAGE
        # 64MiB + headroom); grpc's 4MiB default would RESOURCE_EXHAUST
        # any real agent-tree fetch
        self._channel = grpc.insecure_channel(self.addr, options=[
            ("grpc.max_receive_message_length", 80 << 20),
            ("grpc.max_send_message_length", 80 << 20)])
        self._thread = threading.Thread(
            target=self._run, name="df-synchronizer", daemon=True)
        self._thread.start()
        self._push_thread = threading.Thread(
            target=self._push_loop, name="df-sync-push", daemon=True)
        self._push_thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        call = self._push_call
        if call is not None:
            call.cancel()
        if self._thread:
            self._thread.join(timeout=2.0)
        if self._push_thread:
            self._push_thread.join(timeout=2.0)
        if self._channel:
            self._channel.close()

    def _push_loop(self) -> None:
        """Config changes arrive the moment they are saved (reference:
        trisolaris Push stream), instead of waiting for the next poll."""
        stream = self._channel.unary_stream(
            _PUSH,
            request_serializer=pb.SyncRequest.SerializeToString,
            response_deserializer=pb.SyncResponse.FromString)
        while not self._stop.is_set():
            req = pb.SyncRequest()
            req.agent_group = getattr(self.agent.config, "group",
                                      "") or "default"
            req.agent_id = self.agent.config.agent_id
            req.config_version = self.config_version  # enables catch-up
            req.config_epoch = self.config_epoch  # else every (re)connect
            # looks epoch-stale and gets a spurious full-config replay
            try:
                call = stream(req)
                self._push_call = call
                for resp in call:
                    if self._stop.is_set():
                        return
                    self.stats["pushes"] = self.stats.get("pushes", 0) + 1
                    self._on_response(resp)
            except grpc.RpcError as e:
                code = getattr(e, "code", lambda: None)()
                if code == grpc.StatusCode.RESOURCE_EXHAUSTED:
                    if self._stop.wait(30.0):  # capacity: back off hard
                        return
            finally:
                self._push_call = None
            if self._stop.wait(2.0):
                return

    def _run(self) -> None:
        # first sync immediately, then on the interval
        while True:
            try:
                self.sync_once()
            except Exception as e:
                self.stats["errors"] += 1
                log.debug("sync failed: %s", e)
            if self._stop.wait(self.interval_s):
                return

    def ntp_sync(self, exchanges: int = 3) -> int:
        """One NTP round: several 4-timestamp exchanges, keep the offset
        from the minimum-RTT one (least queueing noise), fold into the
        recent-sample median. Returns the current smoothed offset (ns)."""
        best_rtt = None
        best_off = 0
        call = self._channel.unary_unary(
            _NTP,
            request_serializer=pb.NtpRequest.SerializeToString,
            response_deserializer=pb.NtpResponse.FromString)
        for _ in range(exchanges):
            t1 = time.time_ns()
            resp = call(pb.NtpRequest(t1_ns=t1), timeout=5.0)
            t4 = time.time_ns()
            if resp.t1_ns != t1:
                continue  # not our exchange
            rtt = (t4 - t1) - (resp.t3_ns - resp.t2_ns)
            off = ((resp.t2_ns - t1) + (resp.t3_ns - t4)) // 2
            if rtt >= 0 and (best_rtt is None or rtt < best_rtt):
                best_rtt, best_off = rtt, off
        if best_rtt is not None:
            self._ntp_samples.append(best_off)
            self.ntp_rtt_ns = best_rtt
            self.clock_offset_ns = int(
                statistics.median(self._ntp_samples))
            self.stats["ntp_syncs"] = self.stats.get("ntp_syncs", 0) + 1
        return self.clock_offset_ns

    def sync_once(self) -> pb.SyncResponse:
        try:
            self.ntp_sync()
        except Exception as e:
            # clock sync is best-effort; a failed exchange must not block
            # config/platform sync
            log.debug("ntp sync failed: %s", e)
        req = pb.SyncRequest()
        req.ctrl_ip = _local_ip()
        req.hostname = socket.gethostname()
        req.agent_id = self.agent.config.agent_id
        req.config_version = self.config_version
        req.config_epoch = self.config_epoch
        req.platform_version = self.platform_version
        guard = self.agent.guard
        if guard is not None and guard.degraded:
            req.state = pb.DEGRADED
            req.exception_bitmap = guard.exception_bitmap
        else:
            req.state = pb.RUNNING
        if guard is not None:
            req.cpu_usage = guard.cpu_pct
            req.mem_bytes = int(guard.rss_mb * 1024 * 1024)
        req.version = "0.1.0"
        req.agent_group = getattr(self.agent.config, "group", "") or "default"
        # clock_offset_ns = controller_clock - agent_clock: the amount the
        # server ADDS to this agent's absolute timestamps at ingest.
        # Presence contract (messages.proto:392): only set once measured —
        # a restarted agent must not clear the controller's stored skew
        # with an unmeasured 0 before its first NTP exchange completes.
        if self._ntp_samples:
            req.clock_offset_ns = self.clock_offset_ns
        with self._results_lock:
            sent_results = list(self._pending_results)
        for r in sent_results:
            req.command_results.append(r)
        # collect topology until it lists devices (the job may bring JAX
        # up after the first sync), but RE-SEND every sync: a restarted
        # controller must be able to rebuild its platform/gpid state from
        # long-lived agents (the request is tiny)
        if self._platform_cache is None or not self._platform_cache.devices:
            from deepflow_tpu.tpuprobe.topology import collect_platform_data
            self._platform_cache = collect_platform_data()
        req.platform.CopyFrom(self._platform_cache)
        p = req.processes.add()
        p.pid = os.getpid()
        p.name = self.agent.process_name
        call = self._channel.unary_unary(
            _SYNC,
            request_serializer=pb.SyncRequest.SerializeToString,
            response_deserializer=pb.SyncResponse.FromString)
        resp = call(req, timeout=5.0)
        # results are only dropped once the controller HAS them: a failed
        # RPC keeps them queued for the next sync (identity-based removal:
        # a concurrent sync from the upgrade timer must not over-trim)
        if sent_results:
            with self._results_lock:
                self._pending_results = [
                    r for r in self._pending_results
                    if not any(r is s for s in sent_results)]
        self.stats["syncs"] += 1
        self._on_response(resp)
        try:
            self._sync_pod_map()
        except Exception as e:
            # optional feature (older controller / no genesis): a PodMap
            # failure must not poison an otherwise-successful sync
            log.debug("pod map fetch failed: %s", e)
        return resp

    def _sync_pod_map(self) -> None:
        """Labeler feed: fetch the cluster resource model when stale
        (reference: platform data push building first_path)."""
        labeler = getattr(self.agent, "labeler", None)
        if labeler is None:
            return
        req = pb.PodMapRequest()
        req.version = labeler.version
        req.epoch = labeler.epoch
        call = self._channel.unary_unary(
            _PODMAP,
            request_serializer=pb.PodMapRequest.SerializeToString,
            response_deserializer=pb.PodMapResponse.FromString)
        resp = call(req, timeout=5.0)
        if resp.version == labeler.version and resp.epoch == labeler.epoch:
            return  # an empty-but-NEWER map still applies (pods gone)
        from deepflow_tpu.agent.labeler import ResourceLabel
        labeler.load_resources(
            ((e.cidr, ResourceLabel(pod=e.pod, namespace=e.namespace,
                                    workload=e.workload, node=e.node))
             for e in resp.entries),
            version=resp.version)
        labeler.epoch = resp.epoch
        self.stats["podmap_updates"] = \
            self.stats.get("podmap_updates", 0) + 1

    def _on_response(self, resp: pb.SyncResponse) -> None:
        with self._apply_lock:  # poll + push threads: serialize, and only
            # ever move FORWARD (a stale in-flight poll response must not
            # downgrade a newer pushed config)
            if resp.agent_id and \
                    resp.agent_id != self.agent.config.agent_id:
                self.agent.config.agent_id = resp.agent_id
                self.agent.sender.agent_id = resp.agent_id
            epoch_changed = (resp.config_epoch
                             and resp.config_epoch != self.config_epoch)
            if resp.user_config_yaml and (
                    epoch_changed
                    or resp.config_version > self.config_version):
                self._apply_config(resp.user_config_yaml,
                                   resp.config_version)
                self.config_version = resp.config_version
                if resp.config_epoch:
                    self.config_epoch = resp.config_epoch
                self.stats["config_updates"] += 1
            if resp.platform_version:  # push responses leave it unset
                self.platform_version = resp.platform_version
            if resp.analyzer_assignment:
                self._apply_analyzers(list(resp.analyzer_addrs))
            if resp.HasField("qos"):
                # closed-loop backpressure: the server's per-tenant
                # pressure level rides every Sync/Push response
                try:
                    self.agent.apply_backpressure(
                        int(resp.qos.pressure_level))
                except Exception:
                    log.exception("backpressure apply failed")
                self.stats["pressure_level"] = \
                    int(resp.qos.pressure_level)
        for rc in resp.commands:
            code, out = self._ops.run(rc.cmd, list(rc.args))
            with self._results_lock:
                self._pending_results.append(pb.CommandResult(
                    id=rc.id, exit_code=code, output=out))
            self.stats["commands"] = self.stats.get("commands", 0) + 1

    def _apply_analyzers(self, addrs: list[str]) -> None:
        """Rebalance: adopt the controller's ingest-node preference order
        (the sender fails over down this list)."""
        from deepflow_tpu.agent.config import _parse_addr
        try:
            parsed = [_parse_addr(a) for a in addrs]
        except ValueError as e:
            log.warning("bad analyzer list %r: %s", addrs, e)
            return
        if not parsed:
            # assignment cleared: fall back to the configured servers
            parsed = list(self._configured_servers)
        sender = self.agent.sender
        if parsed and parsed != sender.servers:
            sender.servers = parsed
            sender.stats["rebalances"] = \
                sender.stats.get("rebalances", 0) + 1
            log.info("analyzer assignment: %s", parsed)

    def _apply_config(self, yaml_bytes: bytes, version: int) -> None:
        """Hot-apply the pushed config (reference: ConfigHandler per-module
        callbacks): sampler rate, probe cadence, AND enable/disable take
        effect live."""
        import yaml
        from deepflow_tpu.agent.config import AgentConfig
        try:
            new = AgentConfig.from_dict(
                yaml.safe_load(yaml_bytes) or {}).validate()
        except Exception as e:
            log.warning("rejecting bad pushed config: %s", e)
            return
        cfg = self.agent.config
        cfg.profiler = new.profiler
        cfg.tpuprobe = new.tpuprobe
        cfg.stats_interval_s = new.stats_interval_s
        cfg.guard = new.guard
        cfg.acls = new.acls
        cfg.qos = new.qos
        labeler = getattr(self.agent, "labeler", None)
        if labeler is not None:  # pushed ACLs take effect live
            from deepflow_tpu.agent.labeler import AclRule
            labeler.load_acls([
                AclRule(cidr=a.get("cidr", ""),
                        port=int(a.get("port", 0)),
                        protocol=int(a.get("protocol", 0)),
                        action=a.get("action", "trace"))
                for a in new.acls])
            if any(a.get("action") in ("pcap", "npb") for a in new.acls):
                # pushed packet-action ACLs must not be silently inert
                # on agents that started without a dispatcher
                self.agent.ensure_packet_actions(new)

        # guard limits retune live (the controller's knob for hot agents)
        guard = self.agent.guard
        if guard is not None:
            guard.max_cpu_pct = new.guard.max_cpu_pct
            guard.max_mem_mb = new.guard.max_mem_mb
            guard.check_interval_s = new.guard.check_interval_s

        with self.agent._profiler_lock:
            mem = self.agent.memprofiler
            if new.profiler.memory and mem is None:
                self.agent.start_memprofiler()
            elif not new.profiler.memory and mem is not None:
                mem.stop()
                self.agent.memprofiler = None
            elif mem is not None:
                mem.interval_s = new.profiler.memory_interval_s
            sampler = self.agent.sampler
            if new.profiler.enabled and sampler is None:
                # no-op while guard-degraded (start_sampler checks)
                self.agent.start_sampler()
            elif not new.profiler.enabled and sampler is not None:
                sampler.stop()
                self.agent.sampler = None
            elif sampler is not None:
                sampler.period_s = 1.0 / new.profiler.sample_hz
                sampler.period_us = int(1_000_000 / new.profiler.sample_hz)
                sampler.emit_interval_s = new.profiler.emit_interval_s

            probe = self.agent.tpuprobe
            if new.tpuprobe.enabled and probe is None:
                self.agent.start_tpuprobe()
            elif not new.tpuprobe.enabled and probe is not None:
                probe.stop()
                self.agent.tpuprobe = None
            elif probe is not None:
                for src in probe.sources:
                    if hasattr(src, "interval_s"):
                        src.interval_s = new.tpuprobe.trace_interval_s
                        src.duration_ms = new.tpuprobe.trace_duration_ms
                    if hasattr(src, "target_coverage"):
                        # the adaptive cadence's operator throttle
                        src.target_coverage = min(max(
                            new.tpuprobe.target_coverage, 0.05), 0.95)
                        src.steps_per_capture = \
                            new.tpuprobe.steps_per_capture
        log.info("applied pushed config v%d", version)

    def fetch_package(self, name: str = "agent",
                      version: str = "") -> pb.PackageResponse:
        """OTA download over the sync plane (reference: the Upgrade
        stream, message/agent.proto:9)."""
        call = self._channel.unary_unary(
            _PKG,
            request_serializer=pb.PackageRequest.SerializeToString,
            response_deserializer=pb.PackageResponse.FromString)
        return call(pb.PackageRequest(name=name, version=version),
                    timeout=60.0)

    def gpid_sync(self, entries: list[pb.GpidEntry]) -> pb.GpidSyncResponse:
        req = pb.GpidSyncRequest()
        req.agent_id = self.agent.config.agent_id
        req.entries.extend(entries)
        call = self._channel.unary_unary(
            _GPID,
            request_serializer=pb.GpidSyncRequest.SerializeToString,
            response_deserializer=pb.GpidSyncResponse.FromString)
        return call(req, timeout=5.0)


def _local_ip() -> str:
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("10.255.255.255", 1))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return "127.0.0.1"
