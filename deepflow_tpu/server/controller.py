"""Controller: agent management over gRPC (trisolaris-lite).

Reference analog: server/controller/trisolaris (sync_push.go:166 AgentEvent.
Sync — per-agent SyncResponse with versioned config + platform data) and
trisolaris/services/grpc/agentsynchronize/process_info.go (GPID allocation).
gRPC service methods are hand-registered (generic handlers) because the
image has protoc but not grpcio-tools.

Fleet scale: the server runs on grpc.aio — every Push stream is a coroutine
awaiting a per-group condition, not a pinned thread, so thousands of agents
hold push streams concurrently (the reference's pushmanager serves its
fleet the same way; round 1's thread-pool design capped at 48).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time

import grpc

from deepflow_tpu.proto import pb
from deepflow_tpu.server.platform_info import AgentInfo, PlatformInfoTable

log = logging.getLogger("df.controller")

DEFAULT_AGENT_CONFIG_YAML = b"""\
# deepflow-tpu rendered agent config (controller-pushed)
profiler:
  enabled: true
  sample_hz: 99.0
  emit_interval_s: 1.0
tpuprobe:
  enabled: true
  source: auto
  trace_interval_s: 10.0
  trace_duration_ms: 1000
stats_interval_s: 10.0
"""


class AgentRegistry:
    """Agent identity + state; the vtap cache analog."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._by_key: dict[tuple, dict] = {}
        self._next_id = 1

    def register(self, ctrl_ip: str, hostname: str, agent_id: int,
                 request: "pb.SyncRequest | None" = None) -> dict:
        key = (ctrl_ip, hostname)
        with self._lock:
            entry = self._by_key.get(key)
            if entry is None:
                entry = {
                    "agent_id": agent_id or self._next_id,
                    "ctrl_ip": ctrl_ip,
                    "hostname": hostname,
                    "first_seen_ns": time.time_ns(),
                    "syncs": 0,
                }
                if not agent_id:
                    self._next_id += 1
                else:
                    self._next_id = max(self._next_id, agent_id + 1)
                self._by_key[key] = entry
            entry["last_seen_ns"] = time.time_ns()
            entry["syncs"] = entry.get("syncs", 0) + 1
            if request is not None:
                # health view for /v1/agents (reference: vtap list,
                # cli/ctl/agent.go:49 — the primary fleet ops surface)
                entry["state"] = int(request.state)
                entry["exception_bitmap"] = int(request.exception_bitmap)
                entry["degraded"] = bool(request.exception_bitmap)
                entry["version"] = request.version
                entry["cpu_usage"] = round(float(request.cpu_usage), 2)
                entry["mem_bytes"] = int(request.mem_bytes)
                entry["agent_group"] = request.agent_group or "default"
                entry["config_version"] = int(request.config_version)
                if request.HasField("clock_offset_ns"):
                    entry["clock_offset_ms"] = round(
                        request.clock_offset_ns / 1e6, 3)
                else:
                    # unmeasured, not "0 ms skew" — operators must be able
                    # to tell the two apart in /v1/agents
                    entry["clock_offset_ms"] = None
            return entry

    def list(self) -> list[dict]:
        with self._lock:
            return [dict(v) for v in self._by_key.values()]

    def group_of(self, agent_id: int) -> str:
        with self._lock:
            for e in self._by_key.values():
                if e["agent_id"] == agent_id:
                    return e.get("agent_group", "default")
        return "default"


class GpidAllocator:
    """Global process IDs: (agent_id, pid) -> gpid, plus the 5-tuple table
    that lets the ingester join client/server sides of one connection
    (reference §2.8 GPID glue).

    Lifecycle: each agent's sync is a full snapshot — entries that agent
    reported before and no longer does are dropped (a dead process's
    ephemeral port must not attribute a later process's flows), and a TTL
    sweep retires entries from agents that stopped syncing entirely.

    Entries are bucketed PER AGENT: a sync diffs only that agent's bucket
    against the flat lookup index instead of rebuilding the whole
    fleet-wide table (which made every sync O(fleet) — at 1k agents x 30s
    sync the controller spent most of its lock hold time re-dict-ing
    other agents' unchanged entries). The TTL sweep likewise moved off
    the per-sync path onto an interval: it only has work to do when an
    agent has been silent for minutes, so running it per sync was pure
    overhead."""

    ENTRY_TTL_S = 600.0
    SWEEP_INTERVAL_S = 60.0

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._gpids: dict[tuple, int] = {}
        # agent_id -> {(ip, port, proto, role): entry} (that agent's
        # last full snapshot) and its last-sync monotonic timestamp
        self._by_agent: dict[int, dict[tuple, pb.GpidEntry]] = {}
        self._agent_ts: dict[int, float] = {}
        # flat (ip, port, proto, role) -> entry index for ingest-side
        # point reads; maintained incrementally from the buckets
        self._flat: dict[tuple, pb.GpidEntry] = {}
        self._last_sweep = 0.0
        self._next = 1

    def gpid_for(self, agent_id: int, pid: int) -> int:
        key = (agent_id, pid)
        with self._lock:
            g = self._gpids.get(key)
            if g is None:
                g = self._next
                self._next += 1
                self._gpids[key] = g
            return g

    def sync(self, req: pb.GpidSyncRequest) -> pb.GpidSyncResponse:
        now = time.monotonic()
        with self._lock:
            bucket: dict[tuple, pb.GpidEntry] = {}
            for e in req.entries:
                e.agent_id = req.agent_id  # never trust the entry field
                e.gpid = self._gpids.get((req.agent_id, e.pid), 0) or \
                    self._alloc_locked(req.agent_id, e.pid)
                bucket[(bytes(e.ip), e.port, int(e.proto), e.role)] = e
            # snapshot semantics: this agent's stale entries go away now —
            # only keys this agent owned and stopped reporting are touched
            old = self._by_agent.get(req.agent_id)
            if old:
                for k in old:
                    if k not in bucket:
                        cur = self._flat.get(k)
                        if cur is not None and \
                                cur.agent_id == req.agent_id:
                            del self._flat[k]
            self._by_agent[req.agent_id] = bucket
            self._agent_ts[req.agent_id] = now
            self._flat.update(bucket)
            # TTL sweep (agents that stopped syncing: crash, drain) runs
            # on an interval, not per sync
            if now - self._last_sweep >= self.SWEEP_INTERVAL_S:
                self._sweep_locked(now)
            # echo only the REQUESTER's entries (gpids now filled) — the
            # ingest-side join lives here, and echoing the whole fleet's
            # socket table back on every scan would be O(fleet) waste
            resp = pb.GpidSyncResponse()
            resp.entries.extend(req.entries)
            return resp

    def _sweep_locked(self, now: float) -> None:
        self._last_sweep = now
        cutoff = now - self.ENTRY_TTL_S
        for aid in [a for a, ts in self._agent_ts.items() if ts < cutoff]:
            for k in self._by_agent.pop(aid, {}):
                cur = self._flat.get(k)
                if cur is not None and cur.agent_id == aid:
                    del self._flat[k]
            del self._agent_ts[aid]

    def _alloc_locked(self, agent_id: int, pid: int) -> int:
        g = self._next
        self._next += 1
        self._gpids[(agent_id, pid)] = g
        return g

    def lookup(self, ip: bytes, port: int, proto: int) -> int:
        """Ingest-side join (reference grpc_platformdata.go:2047): map a
        flow endpoint to its global process id; tries server role (exact
        listen tuple) then client role."""
        e = self._entry_for(ip, port, proto)
        return e.gpid if e is not None else 0

    def name_lookup(self, ip: bytes, port: int, proto: int
                    ) -> tuple[int, str]:
        """(gpid, process_name) for a flow endpoint — lets flow logs show
        identity for processes that never loaded the preload interposer
        (socket-inode scan supplies the entries)."""
        e = self._entry_for(ip, port, proto)
        return (e.gpid, e.process_name) if e is not None else (0, "")

    def _entry_for(self, ip: bytes, port: int, proto: int):
        # exact-match ONLY: wildcard binds are expanded into concrete
        # local addresses agent-side (socket_scan.scan_entries) — a
        # server-side any-ip fallback would attribute flows toward
        # REMOTE endpoints on the same port to a local listener
        flat = self._flat  # GIL-atomic point reads; entry objects are
        # never mutated after insertion (a sync inserts fresh ones)
        for role in (1, 0):
            e = flat.get((ip, port, proto, role))
            if e is not None:
                return e
        return None


class PackageRepo:
    """Versioned agent packages for OTA rollout (reference: the repo
    that `deepflow-ctl repo agent upload` feeds, served to agents over
    the Upgrade stream — here a unary fetch; packages are MB-scale
    tarballs of the python package tree)."""

    MAX_PACKAGE = 64 << 20
    MAX_VERSIONS = 8   # keep the repo bounded; oldest evicted

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # name -> ordered {version: (data, sha256)}
        self._pkgs: dict[str, dict[str, tuple[bytes, str]]] = {}

    def upload(self, name: str, version: str, data: bytes) -> dict:
        import hashlib
        if not version:
            raise ValueError("version required")
        if len(data) > self.MAX_PACKAGE:
            raise ValueError(f"package over {self.MAX_PACKAGE} bytes")
        sha = hashlib.sha256(data).hexdigest()
        with self._lock:
            versions = self._pkgs.setdefault(name, {})
            versions[version] = (data, sha)
            while len(versions) > self.MAX_VERSIONS:
                versions.pop(next(iter(versions)))
        return {"name": name, "version": version, "sha256": sha,
                "size": len(data)}

    def get(self, name: str, version: str = ""
            ) -> tuple[str, bytes, str] | None:
        with self._lock:
            versions = self._pkgs.get(name)
            if not versions:
                return None
            if not version:
                version = next(reversed(versions))  # latest upload
            entry = versions.get(version)
            if entry is None:
                return None
            return version, entry[0], entry[1]

    def list(self) -> dict:
        with self._lock:
            return {name: [{"version": v, "sha256": d[1],
                            "size": len(d[0])}
                           for v, d in versions.items()]
                    for name, versions in self._pkgs.items()}


class ConfigStore:
    """Versioned agent-group configs (reference: agent-group config YAML
    validated against the template; push on version bump)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._configs: dict[str, tuple[bytes, int]] = {
            "default": (DEFAULT_AGENT_CONFIG_YAML, 1)}
        self._listeners: list = []  # callables(group, yaml, version)
        # boot nonce: version counters reset with the process; agents use
        # the epoch to tell "restarted controller" from "stale response"
        self.epoch = time.time_ns() & 0xFFFFFFFFFFFF

    def subscribe(self, fn) -> None:
        with self._lock:
            self._listeners.append(fn)

    def get(self, group: str = "default") -> tuple[bytes, int]:
        with self._lock:
            return self._configs.get(group, self._configs["default"])

    def update(self, group: str, yaml_bytes: bytes) -> int:
        self.validate(yaml_bytes)
        with self._lock:
            _, version = self._configs.get(group, (b"", 0))
            version += 1
            self._configs[group] = (yaml_bytes, version)
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(group, yaml_bytes, version)
            except Exception:
                log.exception("config listener failed")
        return version

    @staticmethod
    def validate(yaml_bytes: bytes) -> None:
        import yaml
        from deepflow_tpu.agent.config import AgentConfig
        data = yaml.safe_load(yaml_bytes) or {}
        if not isinstance(data, dict):
            raise ValueError("agent config must be a YAML mapping")
        AgentConfig.from_dict(data).validate()


class CommandQueue:
    """Per-agent remote-exec queue + result store (agent.proto:18 analog:
    controller queues registry commands, agents pick them up on sync)."""

    MAX_RESULTS = 1024       # oldest evicted; dfctl polls promptly
    MAX_PENDING_PER_AGENT = 64
    INFLIGHT_TTL_S = 30.0    # redeliver if no result (at-least-once)

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pending: dict[int, list] = {}    # agent_id -> [RemoteCommand]
        self._inflight: dict[int, tuple] = {}  # cmd_id -> (agent, rc, ts)
        self._results: dict[int, dict] = {}    # cmd_id -> result dict
        self._next_id = 1

    def submit(self, agent_id: int, cmd: str, args: list[str]) -> int:
        with self._lock:
            q = self._pending.setdefault(agent_id, [])
            if len(q) >= self.MAX_PENDING_PER_AGENT:
                raise ValueError(
                    f"agent {agent_id} has {len(q)} undelivered commands "
                    "(is it syncing?)")
            cid = self._next_id
            self._next_id += 1
            rc = pb.RemoteCommand(id=cid, cmd=cmd)
            rc.args.extend(args)
            q.append(rc)
            self._results[cid] = {"id": cid, "agent_id": agent_id,
                                  "cmd": cmd, "state": "pending"}
            while len(self._results) > self.MAX_RESULTS:
                self._results.pop(next(iter(self._results)))
            return cid

    def take_pending(self, agent_id: int) -> list:
        """Delivery is AT-LEAST-ONCE: commands stay in-flight until a
        result arrives; a lost Sync response redelivers after a TTL."""
        now = time.monotonic()
        with self._lock:
            out = self._pending.pop(agent_id, [])
            for cid, (aid, rc, ts) in list(self._inflight.items()):
                if aid == agent_id and now - ts > self.INFLIGHT_TTL_S:
                    out.append(rc)
                    del self._inflight[cid]
            for rc in out:
                self._inflight[rc.id] = (agent_id, rc, now)
            return out

    def deliver_results(self, results) -> None:
        with self._lock:
            for r in results:
                self._inflight.pop(r.id, None)
                entry = self._results.get(r.id)
                if entry is not None:
                    entry.update(state="done", exit_code=r.exit_code,
                                 output=r.output)

    def result(self, cmd_id: int) -> dict | None:
        with self._lock:
            r = self._results.get(cmd_id)
            return dict(r) if r else None


class Controller:
    """The gRPC Synchronizer service + shared state."""

    def __init__(self, platform_table: PlatformInfoTable,
                 host: str = "127.0.0.1", port: int = 20035,
                 pod_index=None, ring_provider=None, qos=None) -> None:
        self.platform_table = platform_table
        # closed-loop backpressure (deepflow_tpu/qos): each Sync response
        # carries this agent's org pressure directive so the fleet
        # degrades gracefully instead of overrunning the ingest tier
        self.qos = qos
        self.pod_index = pod_index  # K8s genesis resource model (server's)
        # zero-arg callable -> HashRing | None: when a replication ring
        # is active its per-agent owner order (primary first) wins over
        # the flat analyzer list below
        self.ring_provider = ring_provider
        self.registry = AgentRegistry()
        self.gpids = GpidAllocator()
        # agent-group -> org assignment (reference: controller/db org/team
        # model; redesigned as group-level scoping — the group is already
        # the config-routing identity, so it is the tenancy boundary too).
        # Unassigned groups belong to the default org 1.
        self._orgs: dict[str, int] = {}
        self._orgs_lock = threading.Lock()
        from deepflow_tpu.server.prom_encoder import PromEncoder
        self.prom_encoder = PromEncoder()
        self.commands = CommandQueue()
        # analyzer (ingest node) list for agent rebalance; never-set =
        # agents keep their configured servers; set-then-cleared = agents
        # REVERT to them
        self._analyzers: list[str] = []
        self._analyzers_managed = False
        self._analyzer_lock = threading.Lock()
        self.configs = ConfigStore()
        self.packages = PackageRepo()
        self.host = host
        self.port = port
        self._aio_server = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._loop_thread: threading.Thread | None = None
        self._loop_ready = threading.Event()
        self._stop_evt: asyncio.Event | None = None
        # cluster-wide platform snapshot (genesis -> recorder analog)
        self._platform_lock = threading.Lock()
        self._platforms: dict[int, pb.PlatformData] = {}
        self._platform_version = 1
        # push wake: per-group asyncio.Condition, owned by the loop thread;
        # streams track their own last-sent version (newest-wins, no queues)
        self._push_conds: dict[str, asyncio.Condition] = {}
        self.push_streams = 0
        self.configs.subscribe(self._on_config_update)

    # -- rpc handlers ---------------------------------------------------------

    def Sync(self, request: pb.SyncRequest, context) -> pb.SyncResponse:
        entry = self.registry.register(
            request.ctrl_ip, request.hostname, request.agent_id,
            request=request)
        agent_id = entry["agent_id"]
        resp = pb.SyncResponse()
        resp.status = pb.SUCCESS
        resp.agent_id = agent_id

        cfg, version = self.configs.get(request.agent_group or "default")
        # resend on version mismatch OR epoch mismatch: after a restart the
        # new store's version can coincide with the agent's stale one while
        # the content differs
        if request.config_version != version or \
                request.config_epoch != self.configs.epoch:
            resp.user_config_yaml = cfg
        resp.config_version = version
        resp.config_epoch = self.configs.epoch

        if request.HasField("platform"):
            self._ingest_platform(agent_id, request.platform)
        if request.HasField("clock_offset_ns"):
            # ingest-time normalization: decoders shift this agent's
            # absolute timestamps onto the controller clock
            self.platform_table.set_clock_offset(agent_id,
                                                 request.clock_offset_ns)
        for proc in request.processes:
            self.gpids.gpid_for(agent_id, proc.pid)

        with self._platform_lock:
            # version only: agents pull the snapshot when they grow a
            # policy/labeler consumer for it (reference pushes full
            # platform data because its agents label packets with it)
            resp.platform_version = self._platform_version
        if request.command_results:
            self.commands.deliver_results(request.command_results)
        for rc in self.commands.take_pending(agent_id):
            resp.commands.append(rc)
        addrs = self.assign_analyzers(agent_id)
        with self._analyzer_lock:
            resp.analyzer_assignment = (self._analyzers_managed
                                        or bool(addrs))
        for addr in addrs:
            resp.analyzer_addrs.append(addr)
        qos = self.qos
        if qos is not None and qos.enabled:
            org = self.org_of_group(request.agent_group or "default")
            d = qos.directive(org)
            if d is not None:
                resp.qos.pressure_level = int(d["pressure_level"])
                resp.qos.sample_rate = float(d["sample_rate"])
                resp.qos.weight = int(d["weight"])
                resp.qos.rate_fps = float(d["rate_fps"])
                resp.qos.updated_ns = int(d["updated_ns"])
        return resp

    def Ntp(self, request: pb.NtpRequest, context) -> pb.NtpResponse:
        """4-timestamp NTP exchange (reference: agent/src/rpc/ntp.rs).
        t2 is stamped on entry, t3 right before serialization."""
        resp = pb.NtpResponse()
        resp.t1_ns = request.t1_ns
        resp.t2_ns = time.time_ns()
        resp.t3_ns = time.time_ns()
        return resp

    def set_analyzers(self, addrs: list[str]) -> None:
        from deepflow_tpu.agent.config import _parse_addr
        for a in addrs:  # reject bad addresses HERE, not per-agent later
            _parse_addr(a)  # raises ValueError
        with self._analyzer_lock:
            self._analyzers = list(dict.fromkeys(addrs))
            self._analyzers_managed = True

    def analyzers(self) -> list[str]:
        with self._analyzer_lock:
            return list(self._analyzers)

    def assign_analyzers(self, agent_id: int) -> list[str]:
        """Per-agent ingest destinations. With a replication ring
        active, the ring's owner order (primary first, then replicas)
        IS the assignment — the synchronizer pushes it down
        analyzer_addrs and the agent's ReplicatedSender adopts it on
        the next sync, completing a leader-driven rebalance. Otherwise:
        rendezvous hashing over the flat analyzer list — even spread,
        minimal churn when the node set changes (reference:
        controller/monitor analyzer rebalance)."""
        import hashlib
        ring = self.ring_provider() if self.ring_provider else None
        if ring is not None:
            addrs = ring.ingest_addrs(agent_id)
            if addrs:
                return addrs
        with self._analyzer_lock:
            addrs = list(self._analyzers)
        if not addrs:
            return []
        def weight(addr: str) -> int:
            h = hashlib.blake2s(f"{agent_id}|{addr}".encode(),
                                digest_size=8)
            return int.from_bytes(h.digest(), "big")
        return sorted(addrs, key=weight, reverse=True)

    def GpidSync(self, request: pb.GpidSyncRequest,
                 context) -> pb.GpidSyncResponse:
        return self.gpids.sync(request)

    def PodMap(self, request: pb.PodMapRequest,
               context) -> pb.PodMapResponse:
        """Cluster resource model -> agents (labeler feed). Entries only
        when the agent's version is stale (steady-state syncs are tiny)."""
        resp = pb.PodMapResponse()
        if self.pod_index is None:
            return resp
        resp.version = self.pod_index.version
        resp.epoch = self.configs.epoch  # restart-coincidence guard
        if request.version == resp.version and \
                request.epoch == resp.epoch:
            return resp
        for ip, pod in self.pod_index.items_copy():
            e = resp.entries.add()
            e.cidr = f"{ip}/32" if ":" not in ip else f"{ip}/128"
            e.pod = pod.name
            e.namespace = pod.namespace
            e.workload = pod.workload
            e.node = pod.node
        return resp

    def _push_cond(self, group: str) -> asyncio.Condition:
        """Loop-thread only."""
        cond = self._push_conds.get(group)
        if cond is None:
            cond = self._push_conds[group] = asyncio.Condition()
        return cond

    async def Push(self, request: pb.SyncRequest, context):
        """Server-streaming: config-change notifications (reference:
        trisolaris push on version bump, sync_push.go pushmanager).

        Coroutine per stream, not thread per stream: each stream compares
        its last-sent version against the store and awaits a shared
        per-group condition — no stream cap, no per-stream queues to
        overflow, newest-wins by construction."""
        group = request.agent_group or "default"
        sent_version = int(request.config_version)
        sent_epoch = int(request.config_epoch)
        cond = self._push_cond(group)
        self.push_streams += 1
        try:
            while True:
                cfg, version = self.configs.get(group)
                if version != sent_version or \
                        sent_epoch != self.configs.epoch:
                    resp = pb.SyncResponse()
                    resp.status = pb.SUCCESS
                    resp.user_config_yaml = cfg
                    resp.config_version = version
                    resp.config_epoch = self.configs.epoch
                    yield resp
                    sent_version = version
                    sent_epoch = self.configs.epoch
                async with cond:
                    try:
                        await asyncio.wait_for(cond.wait(), timeout=5.0)
                    except asyncio.TimeoutError:
                        pass  # periodic re-check also covers missed wakes
        finally:
            self.push_streams -= 1

    def _on_config_update(self, group: str, yaml_bytes: bytes,
                          version: int) -> None:
        """Called from arbitrary threads (HTTP API); wake the loop."""
        loop = self._loop
        if loop is None or not loop.is_running():
            return

        def _notify() -> None:
            cond = self._push_cond(group)

            async def _do() -> None:
                async with cond:
                    cond.notify_all()

            asyncio.ensure_future(_do())

        loop.call_soon_threadsafe(_notify)

    def assign_org(self, group: str, org_id: int) -> None:
        """Assign an agent group to an org (takes effect on the agents'
        next platform sync). org 1 assignments just clear the entry."""
        with self._orgs_lock:
            if int(org_id) == 1:
                self._orgs.pop(group, None)
            else:
                self._orgs[group] = int(org_id)

    def org_of_group(self, group: str) -> int:
        with self._orgs_lock:
            return self._orgs.get(group, 1)

    def org_assignments(self) -> dict:
        with self._orgs_lock:
            return dict(self._orgs)

    def _ingest_platform(self, agent_id: int, p: pb.PlatformData) -> None:
        """Genesis upload -> platform snapshot + ingester tag table."""
        with self._platform_lock:
            prev = self._platforms.get(agent_id)
            if prev is None or prev.SerializeToString() != \
                    p.SerializeToString():
                self._platforms[agent_id] = pb.PlatformData()
                self._platforms[agent_id].CopyFrom(p)
                self._platform_version += 1
        self.platform_table.update(AgentInfo(
            agent_id=agent_id,
            host=p.hostname,
            pod_name=p.pod_name,
            pod_ns=p.pod_namespace,
            tpu_pod=p.tpu_pod_name,
            tpu_worker=int(p.tpu_worker_id or 0),
            slice_id=p.devices[0].slice_id if p.devices else 0,
            org_id=self.org_of_group(self.registry.group_of(agent_id)),
        ))

    def devices_of(self, agent_id: int) -> list[dict]:
        """The TPU devices an agent last reported in its platform data."""
        with self._platform_lock:
            p = self._platforms.get(agent_id)
            return [{"device_id": d.device_id, "device_kind": d.device_kind,
                     "hbm_bytes": d.hbm_bytes}
                    for d in (p.devices if p is not None else ())]

    def _merged_platform_locked(self) -> pb.PlatformData:
        merged = pb.PlatformData()
        for p in self._platforms.values():
            merged.devices.extend(p.devices)
            merged.slice_count = max(merged.slice_count, p.slice_count)
        return merged

    # -- server lifecycle -----------------------------------------------------

    def running(self) -> bool:
        return self._loop_thread is not None and \
            self._loop_thread.is_alive()

    def start(self) -> "Controller":
        self._loop_thread = threading.Thread(
            target=self._run_loop, name="df-controller-aio", daemon=True)
        self._loop_thread.start()
        if not self._loop_ready.wait(timeout=10):
            raise RuntimeError("controller event loop failed to start")
        log.info("controller sync up on :%d (aio)", self.port)
        return self

    def _run_loop(self) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        try:
            loop.run_until_complete(self._serve())
        finally:
            loop.close()

    async def _serve(self) -> None:
        async def sync_h(request, context):
            return self.Sync(request, context)

        async def gpid_h(request, context):
            return self.GpidSync(request, context)

        async def prom_h(request, context):
            return self.prom_encoder.handle(request)

        async def podmap_h(request, context):
            return self.PodMap(request, context)

        async def ntp_h(request, context):
            return self.Ntp(request, context)

        async def pkg_h(request, context):
            got = self.packages.get(request.name, request.version)
            resp = pb.PackageResponse()
            if got is not None:
                resp.version, resp.data, resp.sha256 = got
                resp.found = True
            return resp

        handlers = {
            "Sync": grpc.unary_unary_rpc_method_handler(
                sync_h,
                request_deserializer=pb.SyncRequest.FromString,
                response_serializer=pb.SyncResponse.SerializeToString),
            "GpidSync": grpc.unary_unary_rpc_method_handler(
                gpid_h,
                request_deserializer=pb.GpidSyncRequest.FromString,
                response_serializer=pb.GpidSyncResponse.SerializeToString),
            "PromEncode": grpc.unary_unary_rpc_method_handler(
                prom_h,
                request_deserializer=pb.PromEncodeRequest.FromString,
                response_serializer=pb.PromEncodeResponse.SerializeToString),
            "PodMap": grpc.unary_unary_rpc_method_handler(
                podmap_h,
                request_deserializer=pb.PodMapRequest.FromString,
                response_serializer=pb.PodMapResponse.SerializeToString),
            "Ntp": grpc.unary_unary_rpc_method_handler(
                ntp_h,
                request_deserializer=pb.NtpRequest.FromString,
                response_serializer=pb.NtpResponse.SerializeToString),
            "FetchPackage": grpc.unary_unary_rpc_method_handler(
                pkg_h,
                request_deserializer=pb.PackageRequest.FromString,
                response_serializer=pb.PackageResponse.SerializeToString),
            "Push": grpc.unary_stream_rpc_method_handler(
                self.Push,
                request_deserializer=pb.SyncRequest.FromString,
                response_serializer=pb.SyncResponse.SerializeToString),
        }
        generic = grpc.method_handlers_generic_handler(
            "deepflow_tpu.Synchronizer", handlers)
        server = grpc.aio.server(options=[
            ("grpc.max_receive_message_length", 80 << 20),
            ("grpc.max_send_message_length", 80 << 20)])
        server.add_generic_rpc_handlers((generic,))
        self.port = server.add_insecure_port(f"{self.host}:{self.port}")
        await server.start()
        self._aio_server = server
        self._loop = asyncio.get_running_loop()
        self._stop_evt = asyncio.Event()
        self._loop_ready.set()
        await self._stop_evt.wait()
        await server.stop(grace=0.5)

    def stop(self) -> None:
        loop = self._loop
        if loop is not None and loop.is_running() and \
                self._stop_evt is not None:
            loop.call_soon_threadsafe(self._stop_evt.set)
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=5.0)
            self._loop_thread = None
        self._aio_server = None
        self._loop = None
        self._push_conds.clear()  # Conditions are bound to the dead loop
        self._loop_ready.clear()
