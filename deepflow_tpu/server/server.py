"""deepflow-tpu server wiring: receiver + decoders + querier (+ controller).

Reference analog: server/ingester/ingester/ingester.go:69 (Start: configs,
receiver, modules) combined with server/cmd/server/main.go (one process).
"""

from __future__ import annotations

import json
import logging
import threading
import time

from deepflow_tpu.codec import MessageType
from deepflow_tpu.server.decoders import (
    EventDecoder, FlowLogDecoder, MetricsDecoder, ProfileDecoder,
    StatsDecoder, StepMetricsDecoder, TpuSpanDecoder)
from deepflow_tpu.server.platform_info import PlatformInfoTable
from deepflow_tpu.server.querier import QuerierAPI, QuerierHTTP
from deepflow_tpu.server.receiver import Receiver
from deepflow_tpu.store.db import Database

log = logging.getLogger("df.server")


class Server:
    def __init__(self, host: str = "127.0.0.1", ingest_port: int = 20033,
                 query_port: int = 20416, data_dir: str | None = None,
                 sync_port: int = 20035, enable_controller: bool = False,
                 ha_lease_path: str | None = None,
                 ha_k8s_lease: str | None = None,
                 ingest_workers: int | None = None,
                 query_host: str | None = None,
                 selfmon: bool | None = None,
                 deadman_window_s: float = 15.0,
                 selfstats_interval_s: float = 10.0,
                 api_token: str | None = None,
                 shard_id: int = 0,
                 cluster_seed: str | None = None,
                 cluster_advertise: str | None = None,
                 fanout_timeout_s: float = 5.0,
                 fanout_hedge_delay_s: float = 0.25,
                 replication: int = 0,
                 storage: bool = False,
                 flush_interval_s: float = 1.0,
                 compact_interval_s: float = 60.0,
                 scrub_interval_s: float = 30.0,
                 storage_max_bytes: int = 0,
                 role: str = "ingest",
                 objstore: str | None = None,
                 objstore_mirrors=None,
                 segcache_max_bytes: int = 256 << 20,
                 publish_interval_s: float = 2.0,
                 readtier_poll_s: float = 2.0,
                 qos_config=None) -> None:
        # disaggregated storage (store/objstore.py + store/segcache.py):
        # - role="ingest" (+ --objstore): after every tier commit the
        #   SegmentPublisher mirrors adopted segments + dict dumps into
        #   the shared object store and swaps this shard's pointer.
        # - role="querier": a STATELESS read replica — no receiver, no
        #   decoders, no flusher, no local durability. It polls shard
        #   pointers, adopts published segments into RemoteTableTiers
        #   and serves sealed history; ingest shards answer only their
        #   live/unpublished rows via the publish-gen handshake.
        self.role = role if role in ("ingest", "querier") else "ingest"
        self.objstore_path = objstore
        # read-only alternate objstore roots (other replicas' stores):
        # fetches fail over to them when the primary copy is missing or
        # corrupt — blobs are immutable, so any copy is byte-identical
        self.objstore_mirrors = list(objstore_mirrors or [])
        self.segcache_max_bytes = max(1 << 20, int(segcache_max_bytes))
        self.publish_interval_s = publish_interval_s
        self.readtier_poll_s = readtier_poll_s
        self.objstore = None
        self.publisher = None
        self.segcache = None
        self.readtier = None
        self.partial_cache = None
        self._pub_stop = threading.Event()
        self._pub_thread: threading.Thread | None = None
        self._poll_stop = threading.Event()
        self._poll_thread: threading.Thread | None = None
        if self.role == "querier" and not objstore:
            raise ValueError("role=querier requires an --objstore path")
        # flow-log decode parallelism for THIS server instance; None
        # defers to the DF_INGEST_WORKERS env knob read at import time
        self.ingest_workers = ingest_workers
        # HA: with a lease (file path on a shared volume, OR a K8s Lease
        # object name for clusters without one), cluster SINGLETONS
        # (controller, rollups, janitor) run only on the elected leader;
        # every node serves ingest + query (reference: election.go:175)
        self.ha_lease_path = ha_lease_path
        self.ha_k8s_lease = ha_k8s_lease
        self.election = None
        # cluster federation: this node's shard identity + how to find
        # the seed (leader controller). Enabled by passing a seed and/or
        # an advertise address — a lone seed is a working 1-node cluster
        self.shard_id = shard_id
        self.cluster_seed = cluster_seed
        self.cluster_advertise = cluster_advertise
        self._cluster_on = (cluster_seed is not None
                            or cluster_advertise is not None)
        self._fanout_timeout_s = fanout_timeout_s
        self._fanout_hedge_delay_s = fanout_hedge_delay_s
        # replicated ingest: > 0 turns on the consistent-hash ring
        # (cluster/hashring.py). The elected leader (or the seed, when
        # no election is configured) builds/bumps the ring from the peer
        # directory; everyone else adopts it via the join exchange.
        self.replication = max(0, int(replication))
        self.membership = None
        self.fanout = None
        self.federation = None
        self._ring_stop = threading.Event()
        self._ring_thread: threading.Thread | None = None
        # persistent tiered storage (store/tiered.py): sealed chunks are
        # flushed into mmap-able columnar segments, and acks are released
        # only after the manifest commit that makes their rows durable
        self.storage = bool(storage and data_dir
                            and self.role == "ingest")
        self.flush_interval_s = flush_interval_s
        self.compact_interval_s = compact_interval_s
        self.scrub_interval_s = scrub_interval_s
        self.storage_max_bytes = max(0, int(storage_max_bytes))
        # a querier's tables are pure views over adopted remote
        # segments: no local persistence, no recovery — its data_dir
        # (when given) only roots the mmap segment cache, which lives
        # in a <data_dir>/segcache subdirectory it wipes on startup
        self._cache_root = data_dir if self.role == "querier" else None
        self.db = Database(
            data_dir=None if self.role == "querier" else data_dir,
            shard_id=shard_id, storage=self.storage)
        self.flusher = None
        self.compactor = None
        self.scrubber = None
        self.durability = None
        if self.storage:
            from deepflow_tpu.server.flusher import DurabilityGate
            self.durability = DurabilityGate()
        self.platform = PlatformInfoTable()
        from deepflow_tpu.server.platform_info import (PodIpIndex,
                                                       ResourceIndex)
        self.pod_index = PodIpIndex()  # K8s genesis resource model
        # IP-keyed universal-tag resolution (pods + services + nodes +
        # subnets) shared by every ingest decoder
        self.resources = ResourceIndex(self.pod_index)
        self.genesis = None            # started via start_genesis()
        # self-telemetry spine: per-hop frame ledger + stage heartbeats
        # + deadman detection (see deepflow_tpu/telemetry.py). One
        # Telemetry per Server instance — tests run several per process.
        from deepflow_tpu.telemetry import DeadmanDetector, Telemetry
        self.telemetry = Telemetry("server", enabled=selfmon)
        self.deadman = DeadmanDetector(self.telemetry,
                                       window_s=deadman_window_s)
        self._selfstats_interval_s = selfstats_interval_s
        self._selfstats_stop = threading.Event()
        self._selfstats_thread: threading.Thread | None = None
        self.receiver = Receiver(host=host, port=ingest_port,
                                 telemetry=self.telemetry)
        # closed-loop overload control (deepflow_tpu/qos): admission
        # fair-queuing in front of the decoders, a pressure controller
        # feeding per-tenant backpressure into Controller.Sync, and an
        # adaptive sampler the flow decoders consult under pressure.
        # qos_config: a QosConfig, a JSON path, or None (defaults +
        # DF_QOS_CONFIG). Querier replicas take no agent traffic.
        from deepflow_tpu.qos import Qos, QosConfig
        if isinstance(qos_config, str):
            qos_config = QosConfig.load(qos_config)
        elif qos_config is None:
            qos_config = QosConfig.load()
        self.qos = (Qos(qos_config, telemetry=self.telemetry)
                    if self.role == "ingest" else None)
        self.decoders = []
        self.dedup = None  # shared DedupWindow, built in start()
        self.controller = None
        if enable_controller:
            try:
                from deepflow_tpu.server.controller import Controller
            except ImportError as e:  # no grpcio: degrade, keep ingest+query
                log.warning("controller disabled (%s)", e)
            else:
                self.controller = Controller(
                    self.platform, host=host, port=sync_port,
                    pod_index=self.pod_index,
                    ring_provider=self._current_ring,
                    qos=self.qos)
        from deepflow_tpu.server.alerting import (AlertEngine,
                                                  StepRegressionDetector)
        from deepflow_tpu.server.exporters import ExporterManager
        from deepflow_tpu.server.tracetree import TraceTreeBuilder
        self.exporters = ExporterManager(telemetry=self.telemetry)
        self.alerts = AlertEngine(self.db)
        # step health: continuous regression watch over tpu_step_metrics
        self.step_detector = StepRegressionDetector(self.db)
        # ingest-time trace precompute (reference: tracetree_writer.go)
        self.trace_trees = TraceTreeBuilder(self.db)
        self.api = QuerierAPI(self.db, stats_provider=self._stats,
                              controller=self.controller,
                              exporters=self.exporters, alerts=self.alerts,
                              trace_trees=self.trace_trees,
                              telemetry=self.telemetry,
                              api_token=api_token,
                              shard_id=shard_id)
        self.http = QuerierHTTP(self.api,
                                host=query_host if query_host else host,
                                port=query_port)
        from deepflow_tpu.server.datasource import RollupJob
        from deepflow_tpu.server.janitor import Janitor
        self.rollup = RollupJob(self.db)
        self.janitor = Janitor(self.db, telemetry=self.telemetry,
                               tier_max_bytes=self.storage_max_bytes)
        # built after the api (rollup needs the db the api already holds)
        self.api.rollup = self.rollup
        self.api.storage_provider = self._storage_stats
        # standing-query registry (query/standing.py): shares the api's
        # QueryCache so standing folds and ad-hoc queries reuse the same
        # warm bucket partials (and the distributed partial cache)
        from deepflow_tpu.query.standing import StandingQueryRegistry
        self.standing = StandingQueryRegistry(
            self.db, self.api.query_cache, telemetry=self.telemetry,
            resolver=self.api._resolve_table)
        self.api.standing = self.standing
        self.alerts.standing = self.standing  # push-evaluated rules
        # /v1/health qos block + /v1/qos tenant table + dfctl qos
        self.api.qos = self.qos
        self.api.drop_attribution = self.receiver.drop_attribution
        self._started = False

    def start_genesis(self, api_base: str | None = None, token: str = "",
                      ca_path: str = "") -> bool:
        """Attach the K8s list-watch (in-cluster auto-config when args are
        empty). Returns False when no cluster is reachable."""
        from deepflow_tpu.server.genesis import K8sGenesis
        try:
            def _events(rows):
                self.db.table("event.event").append_rows(rows)

            self.genesis = K8sGenesis(self.pod_index, api_base=api_base,
                                      token=token, ca_path=ca_path,
                                      event_sink=_events,
                                      resources=self.resources,
                                      telemetry=self.telemetry).start()
            return True
        except (RuntimeError, ValueError) as e:
            # ValueError: https without ca (e.g. serviceaccount ca.crt
            # missing) — degrade to untagged flows, never abort server boot
            log.warning("k8s genesis not started: %s", e)
            return False

    def _stats(self) -> dict:
        return {
            "receiver": dict(self.receiver.stats),
            "decoders": {d.MSG_TYPE.name: dict(d.stats)
                         for d in self.decoders},
            "janitor": dict(self.janitor.stats),
            "flusher": (dict(self.flusher.stats)
                        if self.flusher is not None else None),
            "compactor": (dict(self.compactor.stats)
                          if self.compactor is not None else None),
            "scrubber": (dict(self.scrubber.stats)
                         if self.scrubber is not None else None),
            "genesis": (dict(self.genesis.stats)
                        if self.genesis is not None else None),
            "qos": (self.qos.snapshot()
                    if self.qos is not None else None),
            "drop_attribution": self.receiver.drop_attribution(),
        }

    def _flusher_backlog(self) -> float:
        """Durability-gate depth as a 0..1 pressure signal: acks the
        flusher has not yet released.  4096 pending seqs ≈ saturated.
        Sustained commit failure (full/faulty disk) saturates the
        signal directly — the gate may still be shallow right after the
        first failed flush, but nothing will drain it, so pressure must
        reach the agents before the spool does all the absorbing."""
        if self.durability is None:
            return 0.0
        depth = min(1.0, len(self.durability) / 4096.0)
        if self.flusher is not None and self.flusher.consec_errors:
            depth = max(depth, min(
                1.0, self.flusher.consec_errors / 3.0))
        return depth

    def _storage_stats(self) -> dict | None:
        """The /v1/health storage block: tier state + rollup horizons."""
        if self.db.tier_store is None:
            return None
        snap = self.db.tier_store.snapshot()
        snap["gate_pending"] = (len(self.durability)
                                if self.durability is not None else 0)
        if self.flusher is not None:
            snap["flush_consec_errors"] = self.flusher.consec_errors
        if self.scrubber is not None:
            snap["scrub"] = self.scrubber.snapshot()
        snap["rollup_horizons"] = {
            f"{fam}.{sfx}": wm
            for (fam, sfx), wm in self.rollup.horizons().items()}
        return snap

    def _selfstats_loop(self) -> None:
        """Write the server's OWN telemetry into deepflow_system — the
        analog of the reference's ckmonitor/self stats: the server has no
        agent in front of it, so it writes rows directly rather than
        shipping a StatsBatch to itself."""
        hb = self.telemetry.heartbeat(
            "selfstats", interval_hint_s=self._selfstats_interval_s)
        while not self._selfstats_stop.wait(self._selfstats_interval_s):
            hb.beat()
            try:
                self._write_selfstats()
            except Exception:
                log.exception("selfstats write failed")

    def _write_selfstats(self) -> None:
        tags = self.platform.tags_for(0)
        now = time.time_ns()
        rows = []
        for name, mtags, values in self.telemetry.stats_metrics():
            tag_json = json.dumps(mtags, sort_keys=True)
            for vname, v in values.items():
                rows.append({"time": now, "metric_name": name,
                             "tag_json": tag_json, "value_name": vname,
                             "value": v, **tags})
        if rows:
            self.db.table("deepflow_system.deepflow_system") \
                .append_rows(rows)

    def _current_ring(self):
        """The adopted replication ring, or None (handed as a zero-arg
        callable to decoders/controller built before membership is)."""
        m = self.membership
        return m.ring if m is not None else None

    def _ring_tick(self) -> None:
        """Leader-only ring maintenance: rebuild the ring whenever the
        peer DIRECTORY changes (join, address move, restart). A shard
        merely going silent does NOT bump the epoch — failover is the
        query-time claim shift to the surviving replica, not a
        rebalance. Fenced: the ring carries the election token, and
        adoption everywhere is forward-only on (token, epoch)."""
        m = self.membership
        if m is None:
            return
        if self.election is not None:
            if not self.election.is_leader:
                return
            token = self.election.token
        elif not m.is_seed:
            return
        else:
            token = 0
        from deepflow_tpu.cluster.hashring import HashRing
        snap = m.directory.snapshot()
        members = {p["shard_id"]: {"addr": p["addr"],
                                   "ingest": p.get("ingest_addr", "")}
                   for p in snap["peers"]
                   # queriers take no agent traffic: never ring owners
                   if p.get("role", "ingest") == "ingest"}
        ring = HashRing.build(m.ring, members, self.replication, token)
        if ring is not m.ring and m.publish_ring(ring):
            log.info("ring: epoch %d published (token %d, members %s)",
                     ring.epoch, ring.token, sorted(ring.members))

    def _ring_loop(self) -> None:
        while not self._ring_stop.wait(1.0):
            try:
                self._ring_tick()
            except Exception:
                log.exception("ring maintenance failed")

    def _ack_state_path(self) -> str | None:
        import os
        return (os.path.join(self.db.data_dir, "ack_state.json")
                if self.db.data_dir else None)

    def _load_ack_state(self) -> dict[int, int]:
        """Persisted per-agent contiguous-seq watermarks. Seeding BOTH the
        receiver's ack tracker and the decoders' dedup floors is what
        makes retransmits of pre-restart frames exactly-once: the rows
        are already in the (persisted) tables."""
        path = self._ack_state_path()
        if not path:
            return {}
        import os
        if not os.path.exists(path):
            return {}
        try:
            with open(path) as f:
                raw = json.load(f)
            return {int(k): int(v) for k, v in raw.items()}
        except (OSError, ValueError):
            # a torn/corrupt floors file is treated as ABSENT, never
            # fatal: floors restart from the tier manifest's copy (or
            # zero) and dedup re-absorbs the retransmits. Ledgered so
            # the recovery is visible, not silent.
            log.warning("ack state unreadable; starting fresh", exc_info=True)
            self.telemetry.hop("storage").account(
                emitted=1, dropped=1, reason="state_corrupt")
            return {}

    def _save_ack_state(self) -> None:
        # atomic: temp file + fsync + rename. A crash mid-write must
        # leave either the old state or the new — a truncated floors
        # file would poison dedup/ack seeding on the next boot.
        path = self._ack_state_path()
        if not path:
            return
        import os
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump({str(k): v for k, v in
                           self.receiver.seq_tracker.snapshot().items()}, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            log.warning("ack state save failed", exc_info=True)
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def start(self) -> "Server":
        if self.db.data_dir:
            self.db.load()  # resume persisted tables
        # storage-scope chaos (DF_CHAOS tier_enospc/objstore_eio knobs)
        # hooks into the tier commit and blob publish paths; None (the
        # default) costs the hot paths one attribute check
        from deepflow_tpu.chaos import chaos_from_env
        chaos = chaos_from_env()
        if self.db.tier_store is not None:
            self.db.tier_store.chaos = chaos
            if self.db.tier_store.stats.get("manifest_corrupt"):
                # recovery met an unreadable MANIFEST.json and scavenged
                # the segment files instead — ledgered, never silent
                self.telemetry.hop("storage").account(
                    emitted=1, dropped=1, reason="state_corrupt")
        if self.objstore_path is not None:
            from deepflow_tpu.store.objstore import ObjStore
            self.objstore = ObjStore(self.objstore_path,
                                     mirrors=self.objstore_mirrors)
            self.objstore.chaos = chaos
        if self.role == "querier":
            self._start_readtier()
        else:
            self._start_ingest()
        self.http.start()
        if self._cluster_on:
            self._start_cluster()
        # both roles: queriers serve /v1/subscribe push traffic too
        self.standing.start()
        if self.role == "ingest":
            self.alerts.start()
            self.step_detector.start()
        self.deadman.start()
        if self.telemetry.enabled:
            self._selfstats_stop.clear()
            self._selfstats_thread = threading.Thread(
                target=self._selfstats_loop, name="df-selfstats",
                daemon=True)
            self._selfstats_thread.start()
        if self.ha_k8s_lease:
            import os as _os_e
            from deepflow_tpu.server.election import K8sLeaseElection
            try:
                self.election = K8sLeaseElection(
                    self.ha_k8s_lease,
                    namespace=_os_e.environ.get("POD_NAMESPACE",
                                                "default"),
                    on_elected=self._start_singletons,
                    on_deposed=self._stop_singletons).start()
            except (RuntimeError, ValueError) as e:
                log.warning("k8s lease election unavailable (%s); "
                            "running singletons locally", e)
                self._start_singletons()
        elif self.ha_lease_path:
            from deepflow_tpu.server.election import LeaderElection
            self.election = LeaderElection(
                self.ha_lease_path,
                on_elected=self._start_singletons,
                on_deposed=self._stop_singletons).start()
        else:
            self._start_singletons()
        import os as _os
        if _os.environ.get("KUBERNETES_SERVICE_HOST"):
            self.start_genesis()  # in-cluster: watch automatically
        self._started = True
        log.info("server up: role %s ingest :%d query :%d", self.role,
                 self.receiver.port if self.role == "ingest" else 0,
                 self.http.port)
        return self

    def _start_ingest(self) -> None:
        floors = self._load_ack_state()
        if self.storage:
            # the tier manifest carries floors committed ATOMICALLY with
            # the rows they cover — after a SIGKILL it is ahead of
            # ack_state.json (which only a clean stop writes). Max-wins
            # merge: both floors describe rows that are durable.
            for agent_id, contig in self.db.tier_store.ack_floors.items():
                if contig > floors.get(agent_id, -1):
                    floors[agent_id] = contig
        for agent_id, contig in floors.items():
            self.receiver.seq_tracker.seed(agent_id, contig)
        from deepflow_tpu.server.decoders import DedupWindow
        # ONE window shared by every decoder/worker: seq space is
        # per-agent, and a retransmit must dedup no matter which decoder
        # type it lands on
        self.dedup = DedupWindow(floors=floors)
        # SEQ_BASE announcements advance dedup floors too (receiver
        # handles those control frames inline)
        self.receiver.dedup = self.dedup
        # register all queues BEFORE listening: no drop window on restart
        from deepflow_tpu.server.decoders import PcapDecoder
        pairs = [
            (PcapDecoder, MessageType.PCAP),
            (ProfileDecoder, MessageType.PROFILE),
            (TpuSpanDecoder, MessageType.TPU_SPAN),
            (StepMetricsDecoder, MessageType.STEP_METRICS),
            (FlowLogDecoder, MessageType.L4_LOG),
            (FlowLogDecoder, MessageType.L7_LOG),
            (MetricsDecoder, MessageType.METRICS),
            (StatsDecoder, MessageType.DFSTATS),
            (EventDecoder, MessageType.EVENT),
        ]
        qos_on = self.qos is not None and self.qos.enabled
        if qos_on:
            # builds admission/pressure/sampler against the receiver's
            # deliver + ledger surfaces; must precede decoder
            # construction (flow decoders hold the sampler) and
            # receiver.start() (no un-admitted dispatch window)
            self.receiver.attach_qos(self.qos,
                                     flusher_backlog=self._flusher_backlog)
        for cls, mtype in pairs:
            kw = {}
            lanes = 1
            if cls is FlowLogDecoder:
                if qos_on:
                    kw["qos_sampler"] = self.qos.sampler
                workers = self.ingest_workers or FlowLogDecoder.WORKERS
                if self.ingest_workers:
                    kw["workers"] = self.ingest_workers
                # one lane queue per decode worker: each TCP connection
                # pins to a lane, so N agents decode on N workers and a
                # single hot agent cannot serialize the native path
                lanes = workers
            q = self.receiver.register(mtype, lanes=lanes)
            d = cls(q, self.db, self.platform, exporters=self.exporters,
                    pod_index=self.pod_index, resources=self.resources,
                    gpid_table=(self.controller.gpids
                                if self.controller else None),
                    telemetry=self.telemetry, dedup=self.dedup,
                    seq_tracker=self.receiver.seq_tracker,
                    ring=self._current_ring,
                    durability=self.durability, tracer=self.api.qtracer,
                    **kw)
            d.MSG_TYPE = mtype  # FlowLogDecoder serves two types
            self.decoders.append(d.start())
        if self.storage:
            from deepflow_tpu.server.flusher import Compactor, Flusher
            self.flusher = Flusher(self.db, gate=self.durability,
                                   seq_tracker=self.receiver.seq_tracker,
                                   interval_s=self.flush_interval_s,
                                   telemetry=self.telemetry)
            self.flusher.seed_floors(floors)
            self.flusher.start()
            if self.compact_interval_s > 0:
                self.compactor = Compactor(
                    self.db, interval_s=self.compact_interval_s,
                    telemetry=self.telemetry).start()
            if self.scrub_interval_s > 0:
                from deepflow_tpu.store.scrub import Scrubber
                self.scrubber = Scrubber(
                    self.db, objstore=self.objstore,
                    shard_id=self.shard_id,
                    interval_s=self.scrub_interval_s,
                    telemetry=self.telemetry).start()
                self.api.scrubber = self.scrubber
        if qos_on:
            self.qos.start()
        self.receiver.start()
        if self.objstore is not None and self.storage:
            # publish sealed state to the shared store so stateless
            # querier replicas can adopt it (see store/objstore.py)
            from deepflow_tpu.store.objstore import SegmentPublisher
            self.publisher = SegmentPublisher(self.objstore,
                                              self.shard_id)
            self.api.publisher = self.publisher
            self._pub_stop.clear()
            self._pub_thread = threading.Thread(
                target=self._publish_loop, name="df-publish",
                daemon=True)
            self._pub_thread.start()

    def _start_readtier(self) -> None:
        """Querier role: no receiver/decoders/flusher. The node adopts
        published segments from the object store into a byte-budgeted
        local cache and serves sealed history over them."""
        import os
        import tempfile
        from deepflow_tpu.store.segcache import ReadTier, SegmentCache
        # a dedicated subdirectory, NEVER data_dir itself: the cache
        # wipes its root on startup, and a --data-dir pointing at an
        # existing tier (e.g. an ingest node's) must survive a querier
        # started against it by mistake
        root = (os.path.join(self._cache_root, "segcache")
                if self._cache_root
                else tempfile.mkdtemp(prefix="df-segcache-"))
        self.segcache = SegmentCache(
            root, self.objstore, max_bytes=self.segcache_max_bytes,
            telemetry=self.telemetry)
        self.readtier = ReadTier(self.db, self.objstore, self.segcache,
                                 shard_id=self.shard_id)
        self.api.readtier = self.readtier
        if self.scrub_interval_s > 0:
            # a querier scrubs its CACHED copies: a corrupt one is
            # discarded and transparently re-fetched on the next pin
            from deepflow_tpu.store.scrub import Scrubber
            self.scrubber = Scrubber(
                self.db, segcache=self.segcache,
                shard_id=self.shard_id,
                interval_s=self.scrub_interval_s,
                telemetry=self.telemetry).start()
            self.api.scrubber = self.scrubber
        try:
            self.readtier.poll()  # first adoption before serving
        except Exception:
            log.exception("initial read-tier poll failed")
        self._poll_stop.clear()
        self._poll_thread = threading.Thread(
            target=self._poll_loop, name="df-readtier", daemon=True)
        self._poll_thread.start()

    def _publish_loop(self) -> None:
        while not self._pub_stop.wait(self.publish_interval_s):
            try:
                self.publisher.maybe_publish(self.db.tier_store)
            except Exception:
                log.exception("segment publish failed")

    def _poll_loop(self) -> None:
        while not self._poll_stop.wait(self.readtier_poll_s):
            try:
                self.readtier.poll()
            except Exception:
                log.exception("read-tier poll failed")

    def _start_cluster(self) -> None:
        # after http.start(): with --query-port 0 the advertise
        # address needs the REAL bound port
        from deepflow_tpu.cluster.federation import FederationCoordinator
        from deepflow_tpu.cluster.membership import ClusterMembership
        from deepflow_tpu.cluster.remote import FanOut
        adv = (self.cluster_advertise
               or f"127.0.0.1:{self.http.port}")
        self.membership = ClusterMembership(
            self.shard_id, adv, seed=self.cluster_seed,
            role=self.role, telemetry=self.telemetry)
        if self.role == "ingest":
            # agents ship frames to the RECEIVER port; peers gossip it
            # so the ring can hand agent-facing ingest addrs around.
            # Queriers take no agent traffic and stay out of the ring.
            self.membership.ingest_addr = (
                f"{adv.rsplit(':', 1)[0]}:{self.receiver.port}")
        self.membership.start()
        self.fanout = FanOut(
            telemetry=self.telemetry,
            timeout_s=self._fanout_timeout_s,
            hedge_delay_s=self._fanout_hedge_delay_s,
            api_token=self.api.api_token or None)
        self.federation = FederationCoordinator(
            self.db, self.membership, self.fanout,
            shard_id=self.shard_id)
        self.api.membership = self.membership
        self.api.federation = self.federation
        # federated standing refreshes ride the if_state machinery:
        # only shards whose change token moved recompute
        self.standing.federation = self.federation
        if self.readtier is not None:
            # read-tier coordinator: freeze adopted snapshots across the
            # scatter, send the publish-gen handshake, and join the
            # cluster-wide partial-aggregate cache
            self.federation.readtier = self.readtier
            self.federation.query_cache = self.api.query_cache
            from deepflow_tpu.cluster.partialcache import PartialCache
            self.partial_cache = PartialCache(
                self.api.query_cache, self.membership,
                self.federation.dict_sync, self.db,
                shard_id=self.shard_id, telemetry=self.telemetry,
                api_token=self.api.api_token or None)
            self.partial_cache.readtier = self.readtier
            self.api.partial_cache = self.partial_cache
        if self.replication > 0 and self.role == "ingest":
            self._ring_stop.clear()
            self._ring_thread = threading.Thread(
                target=self._ring_loop, name="df-ring", daemon=True)
            self._ring_thread.start()

    def _start_singletons(self) -> None:
        """Leader-only components (no-op when already running)."""
        if self.role == "ingest" and not self.rollup.running():
            self.rollup.start()
        if not self.janitor.running():
            self.janitor.start()
        if self.controller and not self.controller.running():
            self.controller.start()

    def _stop_singletons(self) -> None:
        self.rollup.stop()
        self.janitor.stop()
        if self.controller:
            self.controller.stop()

    def stop(self) -> None:
        if self.election is not None:
            self.election.stop()
            self.election = None
        if self.genesis is not None:
            self.genesis.stop()
            self.genesis = None
        if not self._started:
            return
        self.deadman.stop()
        self._pub_stop.set()
        if self._pub_thread is not None:
            self._pub_thread.join(timeout=2.0)
            self._pub_thread = None
        self._poll_stop.set()
        if self._poll_thread is not None:
            self._poll_thread.join(timeout=2.0)
            self._poll_thread = None
        self._ring_stop.set()
        if self._ring_thread is not None:
            self._ring_thread.join(timeout=2.0)
            self._ring_thread = None
        if self.membership is not None:
            self.membership.stop()
        if self.fanout is not None:
            self.fanout.close()
        self._selfstats_stop.set()
        if self._selfstats_thread is not None:
            self._selfstats_thread.join(timeout=2.0)
            self._selfstats_thread = None
        if self.role == "ingest":
            self.receiver.stop()
        if self.qos is not None:
            # after receiver.stop() (no new submissions), before the
            # decoder drain: parked admission frames flush into the
            # decoder queues so the drain below commits them
            self.qos.stop()
        for d in self.decoders:
            d.stop()  # joins workers, then drains the queue: acked
            # frames must reach the tables before the db persists
            if hasattr(d, "flush"):
                d.flush()  # stateful reducers drain pending windows
                # BEFORE the db persists (the file_agg tail otherwise
                # vanishes on every restart)
        if self.scrubber is not None:
            # before the final flush: a quarantine is a manifest commit
            # too — stop the scrubber racing the shutdown renames
            self.scrubber.stop()
            self.scrubber = None
        if self.compactor is not None:
            # before the final flush: a mid-commit compaction and the
            # flush both rename the manifest; stop the race first
            self.compactor.stop()
            self.compactor = None
        if self.flusher is not None:
            # after the decoder drain: the final flush commits everything
            # they wrote (and parked) and releases the last gated seqs,
            # so the ack state written below matches durable rows
            self.flusher.stop()
            self.flusher = None
        if self.publisher is not None and self.db.tier_store is not None:
            # after the final flush: publish whatever it sealed so
            # queriers see the full history across a clean restart
            try:
                self.publisher.maybe_publish(self.db.tier_store)
            except Exception:
                log.exception("final segment publish failed")
        # persist ack watermarks AFTER the drain: every acked frame is
        # now in a table, so seeding dedup floors from this state on the
        # next start cannot mask an undecoded frame
        self._save_ack_state()
        # before http.stop(): closing every subscriber unblocks any SSE
        # handler thread parked in a long poll
        self.standing.stop()
        self.http.stop()
        self._stop_singletons()
        self.alerts.stop()
        self.step_detector.stop()
        self.exporters.stop()
        try:
            for err in self.db.flush():
                log.error("flush: %s", err)
            if self.db.data_dir:
                self.db.save()
        finally:
            self._started = False

    @property
    def ingest_port(self) -> int:
        return self.receiver.port

    @property
    def query_port(self) -> int:
        return self.http.port

    def wait_for_rows(self, table: str, n: int, timeout: float = 5.0) -> bool:
        """Test/ops helper: block until a table holds >= n rows."""
        deadline = time.monotonic() + timeout
        t = self.db.table(table)
        while time.monotonic() < deadline:
            if len(t) >= n:
                return True
            time.sleep(0.02)
        return len(t) >= n


def main() -> None:
    import argparse
    parser = argparse.ArgumentParser(description="deepflow-tpu server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--ingest-port", type=int, default=20033)
    # querier default: LOCALHOST. The query surface carries control-plane
    # mutations (repo upload, agent exec); exposing it is an explicit
    # opt-in (--query-host 0.0.0.0) best paired with --api-token.
    # See docs/SECURITY.md.
    parser.add_argument("--query-host", default="127.0.0.1",
                        help="querier bind address (default localhost; "
                             "set 0.0.0.0 to expose, ideally with "
                             "--api-token)")
    parser.add_argument("--query-port", type=int, default=20416)
    parser.add_argument("--api-token", default=None,
                        help="shared token gating /v1/repo upload and the "
                             "OTA upgrade exec (default: $DF_API_TOKEN)")
    parser.add_argument("--deadman-window-s", type=float, default=15.0,
                        help="flag a stage wedged after this many seconds "
                             "without a heartbeat")
    parser.add_argument("--sync-port", type=int, default=20035)
    parser.add_argument("--shard-id", type=int, default=0,
                        help="this node's cluster shard identity "
                             "(tags ingested rows; 0 = standalone)")
    parser.add_argument("--cluster-seed", default=None,
                        help="seed node addr host:query_port to join "
                             "(the leader controller's querier)")
    parser.add_argument("--advertise", default=None,
                        help="addr other shards reach THIS querier at "
                             "(default 127.0.0.1:<query-port>)")
    parser.add_argument("--fanout-timeout-s", type=float, default=5.0,
                        help="per-shard scatter-gather call deadline; "
                             "slower shards degrade to missing_shards")
    parser.add_argument("--replication", type=int, default=0,
                        help="replication factor R for ingested HIGH/MID "
                             "frames (0 = off): each agent ships to R "
                             "ring owners; queries stay exact through "
                             "R-1 simultaneous shard failures")
    parser.add_argument("--data-dir", default=None)
    parser.add_argument("--storage", action="store_true",
                        help="persistent tiered storage: flush sealed "
                             "chunks into on-disk columnar segments "
                             "under <data-dir>/segments and release "
                             "ingest acks only after the commit that "
                             "makes their rows durable")
    parser.add_argument("--flush-interval-s", type=float, default=1.0,
                        help="tier flush cadence (storage mode)")
    parser.add_argument("--compact-interval-s", type=float, default=60.0,
                        help="tier compaction cadence (storage mode): "
                             "merge small sealed segments into sorted "
                             "format-v2 runs; 0 disables")
    parser.add_argument("--scrub-interval-s", type=float, default=30.0,
                        help="background integrity-scrub cadence "
                             "(storage/querier modes): verify segment "
                             "block checksums, quarantine + repair "
                             "corrupt segments; 0 disables")
    parser.add_argument("--storage-max-mb", type=int, default=0,
                        help="on-disk tier size budget per node; the "
                             "janitor evicts oldest segments past it "
                             "(0 = TTL-only eviction)")
    parser.add_argument("--role", default="ingest",
                        choices=("ingest", "querier"),
                        help="ingest: full write path (receiver + "
                             "decoders + flusher); querier: stateless "
                             "read replica serving sealed history "
                             "fetched on demand from --objstore")
    parser.add_argument("--objstore", default=None,
                        help="shared object-store directory. Ingest "
                             "nodes publish sealed segments + manifest "
                             "pointers there; queriers adopt them "
                             "(required for --role querier)")
    parser.add_argument("--objstore-mirror", action="append",
                        default=None, metavar="DIR",
                        help="read-only alternate object-store root "
                             "(repeatable): fetches fail over to it "
                             "when the primary copy is missing or "
                             "fails checksum verification")
    parser.add_argument("--segcache-max-mb", type=int, default=256,
                        help="querier local segment-cache byte budget; "
                             "least-recently-used segments past it are "
                             "evicted (refetched on demand)")
    parser.add_argument("--publish-interval-s", type=float, default=2.0,
                        help="ingest publish cadence to --objstore")
    parser.add_argument("--readtier-poll-s", type=float, default=2.0,
                        help="querier manifest-pointer poll cadence")
    parser.add_argument("--ha-lease", default=None,
                        help="shared-volume lease FILE for leader election")
    parser.add_argument("--ha-k8s-lease", default=None,
                        help="K8s Lease object name for leader election "
                             "(no shared volume needed)")
    parser.add_argument("--qos-config", default=None,
                        help="JSON tenant-QoS policy (weights, "
                             "rate_fps quotas, pressure thresholds); "
                             "default $DF_QOS_CONFIG or built-ins. "
                             "DF_NO_QOS=1 disables the subsystem")
    parser.add_argument("--no-controller", action="store_true")
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args()
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    server = Server(host=args.host, ingest_port=args.ingest_port,
                    query_port=args.query_port, sync_port=args.sync_port,
                    query_host=args.query_host,
                    data_dir=args.data_dir,
                    ha_lease_path=args.ha_lease,
                    ha_k8s_lease=args.ha_k8s_lease,
                    api_token=args.api_token,
                    deadman_window_s=args.deadman_window_s,
                    shard_id=args.shard_id,
                    cluster_seed=args.cluster_seed,
                    cluster_advertise=args.advertise,
                    fanout_timeout_s=args.fanout_timeout_s,
                    replication=args.replication,
                    storage=args.storage,
                    flush_interval_s=args.flush_interval_s,
                    compact_interval_s=args.compact_interval_s,
                    scrub_interval_s=args.scrub_interval_s,
                    storage_max_bytes=args.storage_max_mb << 20,
                    role=args.role, objstore=args.objstore,
                    objstore_mirrors=args.objstore_mirror,
                    segcache_max_bytes=args.segcache_max_mb << 20,
                    publish_interval_s=args.publish_interval_s,
                    readtier_poll_s=args.readtier_poll_s,
                    qos_config=args.qos_config,
                    enable_controller=(not args.no_controller
                                       and args.role != "querier")).start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()
