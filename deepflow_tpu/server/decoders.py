"""Per-message-type decoders: pb payloads -> tag-injected store rows.

Reference analog: server/ingester/*/decoder (e.g. profile/decoder/decoder.go
:190 handleProfileData, flow_log/decoder/decoder.go:151 Run). Each decoder
owns one receiver queue, runs on its own thread, and writes columnar batches.
"""

from __future__ import annotations

import json
import logging
import os
import queue
import struct
import threading
import time

import numpy as np

from deepflow_tpu.codec import FrameHeader, MessageType
from deepflow_tpu.native import (
    ArenaStrings, IP_FALLBACK, IP_SRC_EMPTY, IP_DST_EMPTY)
from deepflow_tpu.proto import pb
from deepflow_tpu.query import qtrace
from deepflow_tpu.store.db import Database
from deepflow_tpu.store.schema import (
    L4_PROTOS, L7_PROTOS, PROFILE_EVENT_TYPES, RESPONSE_STATUS,
    SIDE_RESOLVE_NAMES, TPU_SPAN_KINDS, CLOSE_TYPES)
from deepflow_tpu.server.platform_info import PlatformInfoTable

log = logging.getLogger("df.decoder")


class DedupWindow:
    """Per-agent exactly-once guard: a contiguity-advancing ``floor``
    plus a bounded park set of decoded seqs above it.

    The at-least-once transport retransmits frames the server may
    already hold (unacked window replay after a reconnect, spool replay
    racing an in-flight ack); this window is what turns at-least-once
    frames into exactly-once rows.  Every seq at or below an agent's
    floor is a dup; seqs above it park in a per-agent set and are
    absorbed into the floor as the run becomes contiguous — so under
    normal (dense) decode traffic the floor tracks the stream and the
    park set holds only out-of-order residue.  Unlike the shared LRU
    this replaces, one agent's traffic can never evict another agent's
    still-live entries and reopen a dup hole.

    Floors move three ways: seeded from persisted ack state on server
    restart, advanced by ``advance_floor`` when a SEQ_BASE announcement
    declares a gap permanently dead (safe: acked => decoded, so nothing
    below the announced base can still be in a decoder queue), and
    advanced by ``seen`` contiguity.  If a park set still outgrows
    ``capacity`` (an un-announced permanent gap), the floor jumps to
    the oldest parked seq — bounded memory over perfect dup detection
    for seqs that old, same liveness-over-completeness stance as
    SeqAckTracker.MAX_OOS.

    One window is shared by ALL decoders (seq space is per-agent, not
    per-type) and workers, hence the lock."""

    def __init__(self, capacity: int = 65536,
                 floors: dict[int, int] | None = None) -> None:
        self.capacity = capacity
        self._lock = threading.Lock()
        # agent_id -> [floor, set of parked seqs > floor]
        self._state: dict[int, list] = {
            int(a): [int(f), set()] for a, f in (floors or {}).items()}
        self.stats = {"dups": 0, "tracked": 0, "floor_jumps": 0}

    def seen(self, agent_id: int, seq: int) -> bool:
        """Mark (agent, seq); True if it was already marked (a dup)."""
        with self._lock:
            st = self._state.get(agent_id)
            if st is None:
                st = self._state[agent_id] = [0, set()]
            floor, parked = st
            if seq <= floor or seq in parked:
                self.stats["dups"] += 1
                return True
            parked.add(seq)
            self.stats["tracked"] += 1
            if seq == floor + 1:
                while floor + 1 in parked:
                    floor += 1
                    parked.discard(floor)
                st[0] = floor
            elif len(parked) > self.capacity:
                # un-announced permanent gap: jump to the oldest parked
                # seq and absorb the contiguous run above it
                floor = min(parked)
                parked.discard(floor)
                while floor + 1 in parked:
                    floor += 1
                    parked.discard(floor)
                st[0] = floor
                self.stats["floor_jumps"] += 1
            return False

    def advance_floor(self, agent_id: int, floor: int) -> None:
        """Forward-only floor jump (SEQ_BASE / restored ack state)."""
        with self._lock:
            st = self._state.get(agent_id)
            if st is None:
                self._state[agent_id] = [floor, set()]
                return
            if floor <= st[0]:
                return
            parked = st[1]
            parked.difference_update({s for s in parked if s <= floor})
            while floor + 1 in parked:
                floor += 1
                parked.discard(floor)
            st[0] = floor


class Decoder:
    """Base: drain one queue, decode, write. Subclasses set MSG_TYPE."""

    MSG_TYPE: MessageType

    # one ingest.frame span per decoded frame (query/qtrace.py), for the
    # probe's low-rate frames only: a span a frame on the bulk flow and
    # metrics paths would cost more than the decode
    TRACE_FRAMES = False

    WORKERS = 1  # ingest parallelism (reference: per-type decoder queues
    # with N workers, flow_metrics.go:55-61). FlowLogDecoder overrides
    # via DF_INGEST_WORKERS: its native columnar parse (pbcols.cpp)
    # releases the GIL, so extra workers scale across cores — unlike the
    # python-object decode this comment used to caveat.
    # Row ORDER across workers is not guaranteed.

    def __init__(self, q: queue.Queue, db: Database,
                 platform: PlatformInfoTable, exporters=None,
                 pod_index=None, gpid_table=None,
                 workers: int | None = None, resources=None,
                 trace_trees=None, telemetry=None, dedup=None,
                 seq_tracker=None, ring=None, durability=None,
                 qos_sampler=None, tracer=None) -> None:
        # q: one Queue, or a LIST of lane queues (receiver connection
        # affinity — see Receiver.register(lanes=)). With N lanes and N
        # workers each worker owns one lane exclusively, so one hot
        # agent's connection can never serialize its siblings.
        self.queues: list[queue.Queue] = (
            list(q) if isinstance(q, (list, tuple)) else [q])
        self.q = self.queues[0]  # single-queue contract for tests/tools
        self.db = db
        self.platform = platform
        self.exporters = exporters
        self.pod_index = pod_index  # K8s genesis IP->pod (optional)
        self.resources = resources  # ResourceIndex: ip -> universal tags
        self.trace_trees = trace_trees  # TraceTreeBuilder (optional)
        self.gpid_table = gpid_table  # controller GpidAllocator (optional)
        self.dedup = dedup  # shared DedupWindow (optional): retransmit guard
        # receiver's SeqAckTracker (optional): seqs are observed HERE,
        # after decode+write, so an ack implies store presence — a hard
        # server crash can only lose frames the agent will retransmit
        self.seq_tracker = seq_tracker
        # DurabilityGate (optional, storage mode): seqs are PARKED here
        # after decode+write instead of observed — the flusher releases
        # them into seq_tracker only once the rows' tier commit landed,
        # so an ack then implies the rows survive SIGKILL
        self.durability = durability
        # replication (cluster/hashring.py): zero-arg callable returning
        # the current HashRing (or None). When set, every ingested row
        # is tagged with its agent's ring-primary owner_shard and the
        # ring epoch — the coordinates the query-time claim filter
        # dedups replica copies by.
        self.ring = ring
        # qos/sampling.AdaptiveSampler (optional): tail-aware head
        # sampling of bulk flow/L7 records when the frame's tenant is
        # under pressure (only FlowLogDecoder consults it)
        self.qos_sampler = qos_sampler
        self.tracer = tracer if self.TRACE_FRAMES else None
        self.workers = workers if workers is not None else self.WORKERS
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._stats_lock = threading.Lock()
        # handle_ns: total wall time inside handle(); append_ns: the part
        # spent in store appends (handle_ns - append_ns = pure decode).
        # Exposed so the ingest bench can localize regressions per stage.
        self.stats = {"batches": 0, "rows": 0, "errors": 0, "dups": 0,
                      "handle_ns": 0, "append_ns": 0}
        if telemetry is None:
            from deepflow_tpu.telemetry import Telemetry
            telemetry = Telemetry("server", enabled=False)
        self.telemetry = telemetry
        # hops are created in start(): MSG_TYPE may be assigned after
        # construction (FlowLogDecoder serves two message types)
        self._hop = None
        self._tw_hop = None

    def start(self) -> "Decoder":
        self._hop = self.telemetry.hop(f"decoder.{self.MSG_TYPE.name}")
        self._tw_hop = self.telemetry.hop("table_write")
        for i in range(max(1, self.workers)):
            t = threading.Thread(
                target=self._run, args=(i,),
                name=f"df-decoder-{self.MSG_TYPE.name}-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
        self._threads = []
        if self._hop is None:
            return  # never started: nothing accepted, nothing to drain
        # drain what's still queued (every lane): frames here were
        # ACCEPTED (and, on the durable path, acked) — exiting with a
        # non-empty queue would lose them on every restart even though
        # the agent was told not to retransmit
        drained, stamps = [], []
        for lane_q in self.queues:
            while True:
                try:
                    drained.extend(self._unwrap(lane_q.get_nowait(), stamps))
                except queue.Empty:
                    break
        if drained:
            self._handle_items(drained, stamps)

    def _handle_items(self, items: list, stamps: list | None = None) -> None:
        """Decode+write a list of (header, payload); shared by the worker
        loop and the shutdown drain. ``stamps`` holds each frame's
        (received, dequeued) wall ns where frames are traced."""
        batches = rows = errors = dups = 0
        t0 = time.perf_counter_ns()
        for i, (header, payload) in enumerate(items):
            if (self.dedup is not None and header.seq is not None
                    and self.dedup.seen(header.agent_id, header.seq)):
                dups += 1
                continue
            try:
                if stamps:
                    rows += self._handle_traced(header, payload, *stamps[i])
                else:
                    rows += self.handle(header, payload)
                batches += 1
            except Exception:
                errors += 1
                log.exception("decode error (%s)", self.MSG_TYPE.name)
        dt = time.perf_counter_ns() - t0
        if self.durability is not None:
            # storage mode: park AFTER the decode/write pass; the
            # flusher observes into the tracker post-commit (dups and
            # decode errors park too — a retransmit meets the same fate)
            for header, _ in items:
                if header.seq is not None:
                    self.durability.add(header.agent_id, header.seq)
        elif self.seq_tracker is not None:
            # observed AFTER the decode/write pass: dups and decode
            # errors count too (a retransmit would meet the same fate)
            for header, _ in items:
                if header.seq is not None:
                    self.seq_tracker.observe(header.agent_id, header.seq)
        if dups:
            self._hop.account(dropped=dups, reason="dup")
        self._hop.account(delivered=batches, dropped=errors,
                          reason="decode_error" if errors else "")
        with self._stats_lock:
            self.stats["batches"] += batches
            self.stats["rows"] += rows
            self.stats["errors"] += errors
            self.stats["dups"] += dups
            self.stats["handle_ns"] += dt

    def _handle_traced(self, header: FrameHeader, payload: bytes,
                       recv_ns: int, deq_ns: int) -> int:
        """``handle`` under an ingest.frame span: received by the
        receiver -> dequeued here -> rows appended. It joins the agent's
        send.frame on (agent_id, seq)."""
        with self.tracer.start_trace(
                "ingest.frame", agent_id=header.agent_id, seq=header.seq,
                msg_type=self.MSG_TYPE.name) as root:
            if isinstance(root, qtrace.Span):
                root.start_ns = recv_ns
            qtrace.record_span("ingest.queue", recv_ns, deq_ns)
            with qtrace.span("ingest.write") as write:
                n = self.handle(header, payload)
                write.annotate(rows=n)
        return n

    DRAIN_FRAMES = 64  # max frames one worker consumes per wakeup

    def _unwrap(self, item, stamps: list | None = None) -> list:
        """Accept both the receiver's ``(enqueue_ns, frames)`` shape and a
        bare frame list (tests feed decoder queues directly); account the
        dequeue on the ledger + queue-wait histogram. With a tracer, add
        each frame's (received, dequeued) wall ns to ``stamps``."""
        if isinstance(item, tuple):
            enq_ns, frames = item
            wait_ns = time.monotonic_ns() - enq_ns
            self._hop.account(emitted=len(frames), wait_ns=wait_ns)
        else:
            frames = item
            wait_ns = 0
            self._hop.account(emitted=len(frames))
        if self.tracer is not None and stamps is not None:
            now = time.time_ns()
            stamps.extend([(now - wait_ns, now)] * len(frames))
        return frames

    def _run(self, worker_idx: int = 0) -> None:
        hb = self.telemetry.heartbeat(
            f"decoder.{self.MSG_TYPE.name}.{worker_idx}")
        # lane affinity: worker i owns queue i (mod lanes). With
        # lanes == workers each lane has exactly one consumer, so frame
        # order within a connection is preserved end to end.
        lane_q = self.queues[worker_idx % len(self.queues)]
        handled = 0
        while not self._stop.is_set():
            hb.beat(progress=handled)
            stamps: list = []
            try:
                items = self._unwrap(lane_q.get(timeout=0.2), stamps)
            except queue.Empty:
                continue
            # greedy drain: the receiver enqueues LISTS of frames (one per
            # recv()), and each wakeup additionally drains whatever else is
            # already queued — bounded so one worker doesn't starve its
            # siblings under WORKERS > 1
            while len(items) < self.DRAIN_FRAMES:
                try:
                    items = items + self._unwrap(lane_q.get_nowait(), stamps)
                except queue.Empty:
                    break
            handled += len(items)
            self._handle_items(items, stamps)

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        raise NotImplementedError

    def _agent_tags(self, header: FrameHeader) -> dict:
        """Universal tags for this frame's agent, plus — when a ring is
        active — the replication coordinates (owner_shard, ring_epoch).
        Server-local sinks bypass this and stay ring_epoch 0: their rows
        exist in one copy and must be reported unconditionally."""
        tags = self.platform.tags_for(header.agent_id)
        ring = self.ring() if self.ring is not None else None
        if ring is not None:
            owners = ring.owners(header.agent_id)
            if owners:
                tags = dict(tags)
                tags["owner_shard"] = owners[0]
                tags["ring_epoch"] = ring.epoch
        return tags

    def _clock_offset(self, header: FrameHeader) -> int:
        """NTP normalization: ns to add to this agent's absolute
        timestamps (sub-ms offsets are measurement noise, not skew)."""
        off = self.platform.offset_for(header.agent_id)
        return off if abs(off) >= 1_000_000 else 0

    def write(self, table_name: str, rows: list[dict]) -> None:
        """Append + feed the re-export pipeline (reference: exporters)."""
        t0 = time.perf_counter_ns()
        self.db.table(table_name).append_rows(rows)
        dt = time.perf_counter_ns() - t0
        if self._tw_hop is not None:
            self._tw_hop.account(emitted=len(rows), delivered=len(rows),
                                 wait_ns=dt)
        with self._stats_lock:
            self.stats["append_ns"] += dt
        if self.exporters is not None and rows:
            self.exporters.feed(table_name, rows)

    def write_columns(self, table_name: str, cols: dict[str, list],
                      n: int) -> None:
        """Columnar append (the hot-path shape: one list per column, no
        per-row dicts). Row dicts are materialized for the re-export
        pipeline ONLY if an exporter actually wants this table."""
        t0 = time.perf_counter_ns()
        self.db.table(table_name).append_columns(cols, n)
        dt = time.perf_counter_ns() - t0
        if self._tw_hop is not None:
            self._tw_hop.account(emitted=n, delivered=n, wait_ns=dt)
        with self._stats_lock:
            self.stats["append_ns"] += dt
        if (self.exporters is not None and n
                and self.exporters.wants(table_name)):
            names = list(cols)
            # ndarray -> tolist(): exported cells must be PYTHON numbers
            # (np scalars would json-serialize via default=str as strings,
            # silently changing the export wire format)
            expanded = [v.tolist()
                        if isinstance(v, (np.ndarray, ArenaStrings))
                        else v if isinstance(v, list) else [v] * n
                        for v in cols.values()]
            self.exporters.feed(
                table_name,
                [dict(zip(names, vals)) for vals in zip(*expanded)])


class ProfileDecoder(Decoder):
    """ProfileBatch -> profile.in_process_profile."""

    MSG_TYPE = MessageType.PROFILE

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        batch = pb.ProfileBatch.FromString(payload)
        tags = self._agent_tags(header)
        off = self._clock_offset(header)
        rows = []
        for p in batch.profiles:
            rows.append({
                "time": p.timestamp_ns + off,
                "app_service": p.app_service or p.process_name,
                "process_name": p.process_name,
                "event_type": int(p.event_type),
                "profiler": p.profiler,
                "pid": p.pid,
                "tid": p.tid,
                "thread_name": p.thread_name,
                "stack": p.stack.decode("utf-8", "replace"),
                "value": p.value,
                "count": p.count,
                **tags,
            })
        self.write("profile.in_process_profile", rows)
        return len(rows)


class TpuSpanDecoder(Decoder):
    """TpuSpanBatch -> profile.tpu_hlo_span.

    Hot path: native columnar decode (native/ingest.cpp
    df_decode_span_cols) — span and memory-sample fields land in numpy
    arrays with the GIL released, string cells stay (arena, off, len)
    until the dictionary interns them in C++. Malformed/overflow batches
    ride the protobuf fallback; both paths must write identical rows
    (golden parity test)."""

    MSG_TYPE = MessageType.TPU_SPAN
    TRACE_FRAMES = True

    def __init__(self, *a, **kw) -> None:
        super().__init__(*a, **kw)
        self._tl = threading.local()  # per-worker native decode buffers

    def _fast_decoder(self):
        dec = getattr(self._tl, "spancols", False)
        if dec is False:
            try:
                from deepflow_tpu.native import SpanColumnDecoder
                dec = SpanColumnDecoder()
            except Exception:
                dec = None
            self._tl.spancols = dec
        return dec

    def _handle_cols(self, header: FrameHeader, n: int, cols: dict,
                     n_mem: int, arena) -> int:
        tags = self._agent_tags(header)
        off = self._clock_offset(header)

        def lazy(name: str):
            lens = cols[f"{name}_len"]
            if not lens.any():
                return ""
            return ArenaStrings(arena, cols[f"{name}_off"], lens)

        def shifted(t: np.ndarray) -> np.ndarray:
            if not off:
                return t
            return (t.astype(np.int64) + off).astype(np.uint64)

        if n:
            pname = lazy("process_name")
            out = {
                "time": shifted(cols["start_ns"]),
                "duration_ns": cols["duration_ns"],
                "device_id": cols["device_id"],
                "chip_id": cols["chip_id"],
                "core_id": cols["core_id"],
                "kind": cols["kind"],
                "hlo_module": lazy("hlo_module"),
                "hlo_op": lazy("hlo_op"),
                "hlo_category": lazy("hlo_category"),
                "flops": cols["flops"],
                "bytes_accessed": cols["bytes_accessed"],
                "program_id": cols["program_id"],
                "run_id": cols["run_id"],
                "collective": lazy("collective"),
                "bytes_transferred": cols["bytes_transferred"],
                "replica_group_size": cols["replica_group_size"],
                "step": cols["step"],
                "pid": cols["pid"],
                "process_name": pname,
                "app_service": pname,
            }
            out.update(tags)
            # span-labeled slice wins; the agent's universal tag fills
            # the rest (same precedence as the pb path)
            sl = cols["slice_id"]
            out["slice_id"] = np.where(sl != 0, sl,
                                       np.uint32(tags.get("slice_id", 0)))
            self.write_columns("profile.tpu_hlo_span", out, n)
        if n_mem:
            mem = {
                "time": shifted(cols["m_timestamp_ns"]),
                "device_id": cols["m_device_id"],
                "bytes_in_use": cols["m_bytes_in_use"],
                "peak_bytes_in_use": cols["m_peak_bytes_in_use"],
                "bytes_limit": cols["m_bytes_limit"],
                "largest_free_block": cols["m_largest_free_block"],
                "num_allocs": cols["m_num_allocs"],
                "pid": cols["m_pid"],
                "process_name": lazy("m_pname"),
            }
            mem.update(tags)
            self.write_columns("profile.tpu_memory", mem, n_mem)
        return n + n_mem

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        fast = self._fast_decoder()
        if fast is not None:
            try:
                res = fast.decode(payload)
            except Exception:
                res = None
            if res is not None:
                return self._handle_cols(header, *res)
        batch = pb.TpuSpanBatch.FromString(payload)
        tags = self._agent_tags(header)
        off = self._clock_offset(header)
        rows = []
        for s in batch.spans:
            rows.append({
                "time": s.start_ns + off,
                "duration_ns": s.duration_ns,
                "device_id": s.device_id,
                "chip_id": s.chip_id,
                "core_id": s.core_id,
                "kind": int(s.kind),
                "hlo_module": s.hlo_module,
                "hlo_op": s.hlo_op,
                "hlo_category": s.hlo_category,
                "flops": s.flops,
                "bytes_accessed": s.bytes_accessed,
                "program_id": s.program_id,
                "run_id": s.run_id,
                "collective": s.collective,
                "bytes_transferred": s.bytes_transferred,
                "replica_group_size": s.replica_group_size,
                "step": s.step,
                "pid": s.pid,
                "process_name": s.process_name,
                "app_service": s.process_name,
                **{**tags, "slice_id": s.slice_id or tags.get("slice_id", 0)},
            })
        self.write("profile.tpu_hlo_span", rows)
        mem_rows = []
        for m in batch.memory:
            mem_rows.append({
                "time": m.timestamp_ns + off,
                "device_id": m.device_id,
                "bytes_in_use": m.bytes_in_use,
                "peak_bytes_in_use": m.peak_bytes_in_use,
                "bytes_limit": m.bytes_limit,
                "largest_free_block": m.largest_free_block,
                "num_allocs": m.num_allocs,
                "pid": m.pid,
                "process_name": m.process_name,
                **tags,
            })
        if mem_rows:
            self.write("profile.tpu_memory", mem_rows)
        return len(rows) + len(mem_rows)


class StepMetricsDecoder(Decoder):
    """STEP_METRICS JSON payloads -> profile.tpu_step_metrics.

    The payload is NOT protobuf (stepmetrics.py explains why); malformed
    frames raise ValueError and land on the decoder ledger as
    dropped/decode_error like any other bad payload."""

    MSG_TYPE = MessageType.STEP_METRICS
    TRACE_FRAMES = True

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        from deepflow_tpu.tpuprobe.stepmetrics import decode_step_payload
        obj = decode_step_payload(payload)
        tags = self._agent_tags(header)
        off = self._clock_offset(header)
        pid = int(obj.get("pid") or 0)
        pname = str(obj.get("process_name") or "")
        rows = []
        for r in obj["records"]:
            t0 = int(r.get("time") or 0)
            t1 = int(r.get("end_ns") or 0)
            rows.append({
                "time": t0 + off,
                "end_ns": t1 + off,
                "latency_ns": int(r.get("latency_ns") or max(0, t1 - t0)),
                "run_id": int(r.get("run_id") or 0),
                "step": int(r.get("step") or 0),
                "job": str(r.get("job") or ""),
                "device_count": int(r.get("device_count") or 0),
                "device_skew_ns": int(r.get("device_skew_ns") or 0),
                "compute_ns": int(r.get("compute_ns") or 0),
                "collective_ns": int(r.get("collective_ns") or 0),
                "straggler_device": int(r.get("straggler_device") or 0),
                "straggler_lag_ns": int(r.get("straggler_lag_ns") or 0),
                "top_hlos": json.dumps(r.get("top_hlos") or [],
                                       separators=(",", ":")),
                "pid": pid,
                "process_name": pname,
                **tags,
            })
        self.write("profile.tpu_step_metrics", rows)
        return len(rows)


class PcapDecoder(Decoder):
    """PcapUpload -> data_dir/pcaps/<name>.pcap.gz (or memory when no
    data_dir). Reference: ingester pcap module."""

    MSG_TYPE = MessageType.PCAP
    MAX_MEMORY = 64
    _store_lock = threading.Lock()  # handle() must be safe under workers>1

    @staticmethod
    def _safe_name(name: str) -> str:
        """Wire-controlled names must never traverse paths."""
        import re
        cleaned = re.sub(r"[^A-Za-z0-9._-]", "_", os.path.basename(name))
        return cleaned.lstrip(".") or "unnamed"

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        up = pb.PcapUpload.FromString(payload)
        safe = self._safe_name(up.name)
        entry = {"name": safe, "agent_id": up.agent_id or
                 header.agent_id, "start_ns": up.start_ns,
                 "packet_count": up.packet_count,
                 "bytes_gz": len(up.pcap_gz)}
        with self._store_lock:
            store = getattr(self.db, "pcap_store", None)
            if store is None:
                store = self.db.pcap_store = {"dir": None, "entries": []}
                if self.db.data_dir:
                    store["dir"] = os.path.join(self.db.data_dir, "pcaps")
                    os.makedirs(store["dir"], exist_ok=True)
            if store["dir"]:
                path = os.path.join(store["dir"], f"{safe}.pcap.gz")
                with open(path, "wb") as f:
                    f.write(up.pcap_gz)
                entry["path"] = path
            else:
                entry["data"] = up.pcap_gz
            store["entries"].append(entry)
            for old in store["entries"][:-self.MAX_MEMORY]:
                p = old.get("path")  # evicted captures must not leak disk
                if p:
                    try:
                        os.unlink(p)
                    except OSError:
                        pass
            del store["entries"][:-self.MAX_MEMORY]
        return 1


class FlowLogDecoder(Decoder):
    """FlowLogBatch -> flow_log.l4_flow_log / l7_flow_log. Registered for
    both L4_LOG and L7_LOG message types.

    Hot path: the native columnar wire decoder (native/pbcols.cpp) parses
    L4 rows straight into numpy arrays with the GIL RELEASED — which is
    what makes WORKERS > 1 genuinely scale across cores (reference: the
    Go ingester fans decode across cores,
    flow_metrics/flow_metrics.go:55-61; Python-object decode was
    GIL-bound). v6 or malformed batches fall back to the protobuf path.
    """

    MSG_TYPE = MessageType.L4_LOG
    # decode workers: >1 scales on multi-core hosts because the native
    # parse releases the GIL (set DF_INGEST_WORKERS to the core budget)
    try:
        WORKERS = max(1, int(os.environ.get("DF_INGEST_WORKERS", "1")
                             or 1))
    except ValueError:
        WORKERS = 1  # malformed env must not take the server down

    _IP_MEMO_MAX = 1 << 20  # distinct v4 addresses before a full reset

    def __init__(self, *args, **kw) -> None:
        super().__init__(*args, **kw)
        self._tl = threading.local()  # per-worker native decode buffers
        # uint32 ip -> (dotted str, packed bytes), memoized ACROSS batches
        # (real fleets see a bounded address set, so after warmup the per
        # batch cost drops to dict gets). Shared by workers: dict get/set
        # are GIL-atomic, a racy duplicate insert is harmless.
        self._ip_memo: dict[int, tuple[str, bytes]] = {}

    def _ip_views(self, ip4s: np.ndarray, ip4d: np.ndarray):
        """(src dotted, dst dotted, src packed, dst packed) row lists for
        two uint32 address columns; packed lists are None when no gpid
        table is attached (the only consumer of the bytes form)."""
        memo = self._ip_memo
        for u in np.unique(np.concatenate((ip4s, ip4d))).tolist():
            if u not in memo:
                if len(memo) >= self._IP_MEMO_MAX:
                    memo.clear()
                memo[u] = ("%d.%d.%d.%d" % (u >> 24 & 255, u >> 16 & 255,
                                            u >> 8 & 255, u & 255),
                           struct.pack(">I", u))
        src = [memo[x] for x in ip4s.tolist()]
        dst = [memo[x] for x in ip4d.tolist()]
        src_s = [t[0] for t in src]
        dst_s = [t[0] for t in dst]
        if self.gpid_table is None:
            return src_s, dst_s, None, None
        return src_s, dst_s, [t[1] for t in src], [t[1] for t in dst]

    def _fast_decoder(self):
        """Per-thread L4ColumnDecoder (its buffers are not shareable)."""
        dec = getattr(self._tl, "l4cols", False)
        if dec is False:
            try:
                from deepflow_tpu.native import L4ColumnDecoder
                dec = L4ColumnDecoder()
            except Exception:
                dec = None
            self._tl.l4cols = dec
        return dec

    def _fast_l7_decoder(self):
        """Per-thread L7ColumnDecoder (its buffers are not shareable)."""
        dec = getattr(self._tl, "l7cols", False)
        if dec is False:
            try:
                from deepflow_tpu.native import L7ColumnDecoder
                dec = L7ColumnDecoder()
            except Exception:
                dec = None
            self._tl.l7cols = dec
        return dec

    def _endpoint_cols(self, items, keys, src_s, dst_s) -> dict:
        """Protobuf-object front end of the shared resolution ladder."""
        return self._resolve_endpoint_cols(
            len(items),
            [bytes(k.ip_src) for k in keys],
            [bytes(k.ip_dst) for k in keys],
            [k.port_src for k in keys], [k.port_dst for k in keys],
            [int(k.proto) for k in keys],
            [f.gpid_0 for f in items], [f.gpid_1 for f in items],
            [f.pod_0 for f in items], [f.pod_1 for f in items],
            src_s, dst_s)

    def _resolve_endpoint_cols(self, n, ipb0, ipb1, ports0, ports1,
                               protos, agent_g0, agent_g1, pod0, pod1,
                               src_s, dst_s) -> dict:
        """gprocess/resource columns shared by the l4/l7 branches AND the
        native columnar fast path — ONE ladder, so the two decode paths
        cannot diverge on how the same traffic resolves. Agent values win
        for pod/gpid; everything else resolves via the controller gpid
        table / genesis ResourceIndex, deduped per distinct endpoint
        (reference: grpc_platformdata.go QueryIPV4Infos per-side fill).
        pod0/pod1 may be lists or a scalar broadcast; ipb0/ipb1 (bytes
        form, consumed only by the gpid join) may be None when no gpid
        table is attached."""
        def aslist(p):
            return _aslist(p, n)
        cols: dict = {}
        if self.gpid_table is None:
            cols["gprocess_id_0"] = agent_g0
            cols["gprocess_id_1"] = agent_g1
            cols["process_kname_0"] = ""
            cols["process_kname_1"] = ""
        else:
            # socket-inode scan entries give every flow endpoint a
            # gpid AND a process name, preload or not (reference:
            # linux_socket.rs scan -> grpc_platformdata.go join)
            nl = self.gpid_table.name_lookup
            cache: dict = {}

            def side(ipbs, ports, agents):
                gpids, names = [], []
                for ipb, port, proto, ag in zip(ipbs, ports, protos,
                                                agents):
                    k = (ipb, port, proto)
                    v = cache.get(k)
                    if v is None:
                        v = cache[k] = nl(ipb, port, proto)
                    gpids.append(ag or v[0])
                    names.append(v[1])
                return gpids, names
            cols["gprocess_id_0"], cols["process_kname_0"] = side(
                ipb0, ports0, agent_g0)
            cols["gprocess_id_1"], cols["process_kname_1"] = side(
                ipb1, ports1, agent_g1)
        if self.resources is not None and not self.resources.is_empty():
            res = self.resources.batch_resolver()
            rcache: dict = {}

            def resolve(s):
                t = rcache.get(s)
                if t is None:
                    t = rcache[s] = res(s)
                return t
            t0 = [resolve(s) for s in src_s]
            t1 = [resolve(s) for s in dst_s]
            cols["pod_0"] = [p or t.pod
                             for p, t in zip(aslist(pod0), t0)]
            cols["pod_1"] = [p or t.pod
                             for p, t in zip(aslist(pod1), t1)]
            for name in SIDE_RESOLVE_NAMES:
                cols[f"{name}_0"] = [getattr(t, name) for t in t0]
                cols[f"{name}_1"] = [getattr(t, name) for t in t1]
        elif self.resources is not None:
            # nothing can resolve: agent values / constant broadcast
            cols["pod_0"], cols["pod_1"] = pod0, pod1
            for name in SIDE_RESOLVE_NAMES:
                cols[f"{name}_0"] = ""
                cols[f"{name}_1"] = ""
        elif self.pod_index is not None and len(self.pod_index):
            pods = self.pod_index.snapshot()

            def pod_of(ip_str: str) -> str:
                pod = pods.get(ip_str)
                return pod.name if pod is not None else ""
            cols["pod_0"] = [p or pod_of(s)
                             for p, s in zip(aslist(pod0), src_s)]
            cols["pod_1"] = [p or pod_of(s)
                             for p, s in zip(aslist(pod1), dst_s)]
        else:
            cols["pod_0"], cols["pod_1"] = pod0, pod1
        return cols

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        sampler = self.qos_sampler
        if sampler is not None and sampler.rate_for(header.org_id) < 1.0:
            # tenant under pressure: ride the pb row path so each record
            # can be judged individually — error/slow exemplars always
            # kept, bulk head-sampled deterministically by flow_id.  The
            # extra pb decode only happens for tenants ALREADY being
            # shed (rate < 1); nominal traffic keeps the native path
            # (the <2% overhead gate measures exactly this branch check).
            batch = pb.FlowLogBatch.FromString(payload)
            self._sample_batch(batch, header.org_id)
            return self._handle_pb(batch, header)
        fast = self._fast_decoder()
        if fast is not None:
            try:
                res = fast.decode(payload)
            except Exception:
                res = None
            # v6 rows ride the pb path (printable-string formatting and
            # 128-bit handling are not worth a native fork; v6 flows are
            # the rare case in TPU fleets)
            if res is not None and not res[1]["is_v6"].any():
                n_l4, cols, l7segs, arena = res
                tags = self._agent_tags(header)
                off = self._clock_offset(header)
                n = 0
                if n_l4:
                    n += self._handle_l4_cols(cols, n_l4, arena, tags, off)
                if l7segs:
                    # L4 columns are consumed above, so the L7 decoder's
                    # separate buffers may now be filled from the same
                    # payload (it walks only top-level field-2 records)
                    l7fast = self._fast_l7_decoder()
                    l7res = None
                    if l7fast is not None:
                        try:
                            l7res = l7fast.decode(payload)
                        except Exception:
                            l7res = None
                    if l7res is not None and l7res[0] and \
                            not l7res[1]["is_v6"].any():
                        n += self._handle_l7_cols(
                            l7res[1], l7res[0], l7res[2], tags, off)
                    else:  # v6 / overflow: pb-parse exactly those bytes
                        l7 = [pb.L7FlowLog.FromString(payload[o:o + ln])
                              for o, ln in l7segs]
                        n += self._handle_l7_list(l7, tags, off)
                return n
        batch = pb.FlowLogBatch.FromString(payload)
        return self._handle_pb(batch, header)

    def _sample_batch(self, batch, org_id: int) -> None:
        """Tail-aware adaptive sampling, in place: keep every error/slow
        exemplar, head-sample the bulk rest by flow_id (deterministic —
        a retransmitted copy makes the same call).  Every dropped record
        is ledgered on the qos.sample hop (reason=adaptive_sample) and
        the applied rate is recorded per org so queriers can reweight
        (kept_bulk / rate + exemplars)."""
        s = self.qos_sampler
        slow_ns = s.config.slow_exemplar_ms * 1e6
        if batch.l4:
            kept = [f for f in batch.l4 if s.keep(
                org_id, f.flow_id,
                exemplar=(f.retrans_tx or f.retrans_rx or f.zero_win_tx
                          or f.zero_win_rx
                          or f.rtt_us * 1000 >= slow_ns))]
            if len(kept) < len(batch.l4):
                del batch.l4[:]
                batch.l4.extend(kept)
        if batch.l7:
            err = (pb.CLIENT_ERROR, pb.SERVER_ERROR, pb.TIMEOUT)
            kept = [f for f in batch.l7 if s.keep(
                org_id, f.flow_id,
                exemplar=(f.response_status in err
                          or f.end_time_ns - f.start_time_ns >= slow_ns))]
            if len(kept) < len(batch.l7):
                del batch.l7[:]
                batch.l7.extend(kept)

    def _handle_pb(self, batch, header: FrameHeader) -> int:
        tags = self._agent_tags(header)
        # NTP normalization: shift this agent's absolute timestamps onto
        # the controller clock (reference corrects on-agent in rpc/ntp.rs;
        # here ingest-side so every telemetry family is covered at one
        # choke point). Sub-ms offsets are noise, not skew.
        off = self._clock_offset(header)
        n = 0
        if batch.l4:  # pure-pb fallback path (v6 / no native lib)
            # columnar build: one C-speed comprehension per column instead
            # of per-row dicts (measured ~3x on the ingest bench; row
            # building was the GIL-bound bottleneck, see Decoder.WORKERS)
            l4 = list(batch.l4)
            keys = [f.key for f in l4]
            src_d = [_ip_decode(k.ip_src) for k in keys]
            dst_d = [_ip_decode(k.ip_dst) for k in keys]
            src_s = [t[0] for t in src_d]
            dst_s = [t[0] for t in dst_d]
            endpoint_cols = self._endpoint_cols(l4, keys, src_s, dst_s)
            cols = {
                "time": [f.end_time_ns + off for f in l4],
                "flow_id": [f.flow_id for f in l4],
                "ip_src": src_s,
                "ip_dst": dst_s,
                "ip4_src": [t[1] for t in src_d],
                "ip4_dst": [t[1] for t in dst_d],
                "port_src": [k.port_src for k in keys],
                "port_dst": [k.port_dst for k in keys],
                "protocol": [int(k.proto) for k in keys],
                "tap_port": [k.tap_port for k in keys],
                "start_time": [f.start_time_ns + off for f in l4],
                "end_time": [f.end_time_ns + off for f in l4],
                "packet_tx": [f.packet_tx for f in l4],
                "packet_rx": [f.packet_rx for f in l4],
                "byte_tx": [f.byte_tx for f in l4],
                "byte_rx": [f.byte_rx for f in l4],
                "l7_request": [f.l7_request for f in l4],
                "l7_response": [f.l7_response for f in l4],
                "rtt": [f.rtt_us for f in l4],
                "art": [f.art_us for f in l4],
                "retrans_tx": [f.retrans_tx for f in l4],
                "retrans_rx": [f.retrans_rx for f in l4],
                "zero_win_tx": [f.zero_win_tx for f in l4],
                "zero_win_rx": [f.zero_win_rx for f in l4],
                "close_type": [_close_type_idx(f.close_type) for f in l4],
                "syn_count": [f.syn_count for f in l4],
                "synack_count": [f.synack_count for f in l4],
                "tunnel_type": [min(int(k.tunnel_type), 4) for k in keys],
                "tunnel_id": [k.tunnel_id for k in keys],
                **endpoint_cols,
            }
            cols.update(tags)  # constant per batch: scalar broadcast
            self.write_columns("flow_log.l4_flow_log", cols, len(l4))
            n += len(l4)
        if batch.l7:
            n += self._handle_l7_list(list(batch.l7), tags, off)
        return n

    def _handle_l4_cols(self, cols: dict, n: int, arena, tags: dict,
                        off: int) -> int:
        """Native columnar L4 path: numpy views from pbcols.cpp become
        store columns directly. Per-row Python work is deduped — ip
        strings and gpid endpoints resolve once per DISTINCT value, which
        is how real traffic behaves (bounded host/endpoint sets)."""
        ip4s, ip4d = cols["ip4_src"], cols["ip4_dst"]
        src_s, dst_s, ipb0, ipb1 = self._ip_views(ip4s, ip4d)

        # agent-labeled pods (usually empty -> scalar broadcast)
        def pods(which: str):
            lens = cols[f"{which}_len"]
            if not lens.any():
                return ""
            ab = arena.tobytes()
            return [ab[o:o + ln].decode("utf-8", "replace") if ln else ""
                    for o, ln in zip(cols[f"{which}_off"].tolist(),
                                     lens.tolist())]
        pod0, pod1 = pods("pod0"), pods("pod1")

        ep = self._resolve_endpoint_cols(
            n, ipb0, ipb1,
            cols["port_src"].tolist(), cols["port_dst"].tolist(),
            cols["proto"].tolist(),
            cols["gpid_0"].tolist(), cols["gpid_1"].tolist(),
            pod0, pod1, src_s, dst_s)

        if off:
            t_end = (cols["end_time_ns"].astype(np.int64)
                     + off).astype(np.uint64)
            t_start = (cols["start_time_ns"].astype(np.int64)
                       + off).astype(np.uint64)
        else:
            t_end, t_start = cols["end_time_ns"], cols["start_time_ns"]
        out = {
            "time": t_end,
            "flow_id": cols["flow_id"],
            "ip_src": src_s,
            "ip_dst": dst_s,
            "ip4_src": ip4s,
            "ip4_dst": ip4d,
            "port_src": cols["port_src"],
            "port_dst": cols["port_dst"],
            "protocol": cols["proto"],
            "tap_port": cols["tap_port"],
            "start_time": t_start,
            "end_time": t_end,
            "packet_tx": cols["packet_tx"],
            "packet_rx": cols["packet_rx"],
            "byte_tx": cols["byte_tx"],
            "byte_rx": cols["byte_rx"],
            "l7_request": cols["l7_request"],
            "l7_response": cols["l7_response"],
            "rtt": cols["rtt_us"],
            "art": cols["art_us"],
            "retrans_tx": cols["retrans_tx"],
            "retrans_rx": cols["retrans_rx"],
            "zero_win_tx": cols["zero_win_tx"],
            "zero_win_rx": cols["zero_win_rx"],
            "close_type": cols["close_type"],
            "syn_count": cols["syn_count"],
            "synack_count": cols["synack_count"],
            "tunnel_type": np.minimum(cols["tunnel_type"], 4),
            "tunnel_id": cols["tunnel_id"],
            **ep,
        }
        out.update(tags)
        self.write_columns("flow_log.l4_flow_log", out, n)
        return n

    def _handle_l7_cols(self, cols: dict, n: int, arena, tags: dict,
                        off: int) -> int:
        """Native columnar L7 path (pbcols.cpp DfL7Cols): numpy views
        become store columns directly; the ~35-key per-row dict build of
        the pb path disappears. String cells decode from the shared arena
        once per DISTINCT value (request types, domains, endpoints repeat
        heavily in real traffic). Must stay row-identical to
        _handle_l7_list — the golden parity test enforces it."""
        ab_cell: list = []  # arena.tobytes() computed only if strs() needs it
        smemo: dict[bytes, str] = {}

        def strs(name: str):
            """Arena (off,len) pairs -> python strings; scalar "" when the
            whole column is empty (constant broadcast, store-supported).
            Only for columns that MUST be python strings (the resolution
            ladder, kname merge) — store-bound columns use lazy() below."""
            lens = cols[f"{name}_len"]
            if not lens.any():
                return ""
            if not ab_cell:
                ab_cell.append(arena.tobytes())
            ab = ab_cell[0]
            get = smemo.get
            out = []
            for o, ln in zip(cols[f"{name}_off"].tolist(), lens.tolist()):
                if not ln:
                    out.append("")
                    continue
                b = ab[o:o + ln]
                s = get(b)
                if s is None:
                    s = smemo[b] = b.decode("utf-8", "replace")
                out.append(s)
            return out

        def lazy(name: str):
            """Store-bound string column: stays (arena, off, len) all the
            way into Dictionary.encode_arena, so cells are interned in C++
            under one lock and never become Python strings on the hot
            path. Scalar "" broadcast when the whole column is empty."""
            lens = cols[f"{name}_len"]
            if not lens.any():
                return ""
            return ArenaStrings(arena, cols[f"{name}_off"], lens)

        ip4s, ip4d = cols["ip4_src"], cols["ip4_dst"]
        src_s, dst_s, ipb0, ipb1 = self._ip_views(ip4s, ip4d)
        ep = self._resolve_endpoint_cols(
            n, ipb0, ipb1,
            cols["port_src"].tolist(), cols["port_dst"].tolist(),
            cols["proto"].tolist(),
            cols["gpid_0"].tolist(), cols["gpid_1"].tolist(),
            strs("pod_0"), strs("pod_1"), src_s, dst_s)

        if off:
            t_start = (cols["start_time_ns"].astype(np.int64)
                       + off).astype(np.uint64)
        else:
            t_start = cols["start_time_ns"]
        dur = np.maximum(
            cols["end_time_ns"].astype(np.int64)
            - cols["start_time_ns"].astype(np.int64), 0).astype(np.uint64)

        def kname_merge(agent_kn, resolved):
            """Agent-observed kernel thread name wins (sslprobe path);
            the socket-scan join fills the rest — same precedence as the
            pb path."""
            if not isinstance(agent_kn, list):  # all-empty broadcast
                return resolved
            return [a or r for a, r in
                    zip(agent_kn, _aslist(resolved, n))]

        out = {
            "time": t_start,
            "flow_id": cols["flow_id"],
            "ip_src": src_s,
            "ip_dst": dst_s,
            "port_src": cols["port_src"],
            "port_dst": cols["port_dst"],
            "tunnel_type": np.minimum(cols["tunnel_type"], 4),
            "tunnel_id": cols["tunnel_id"],
            "l7_protocol": cols["l7_protocol"],
            "version": lazy("version"),
            "request_type": lazy("request_type"),
            "request_domain": lazy("request_domain"),
            "request_resource": lazy("request_resource"),
            "endpoint": lazy("endpoint"),
            "request_id": cols["request_id"],
            "response_status": cols["response_status"],
            "response_code": cols["response_code"],
            "response_exception": lazy("response_exception"),
            "response_result": lazy("response_result"),
            "response_duration": dur,
            "trace_id": lazy("trace_id"),
            "span_id": lazy("span_id"),
            "parent_span_id": lazy("parent_span_id"),
            "x_request_id": lazy("x_request_id"),
            "syscall_trace_id_request": cols["syscall_trace_id_request"],
            "syscall_trace_id_response": cols["syscall_trace_id_response"],
            "syscall_thread_0": cols["syscall_thread_0"],
            "syscall_thread_1": cols["syscall_thread_1"],
            "captured_request_byte": cols["captured_request_byte"],
            "captured_response_byte": cols["captured_response_byte"],
            **ep,
            "process_kname_0": kname_merge(strs("process_kname_0"),
                                           ep["process_kname_0"]),
            "process_kname_1": kname_merge(strs("process_kname_1"),
                                           ep["process_kname_1"]),
            "attrs": lazy("attrs_json"),
        }
        out.update(tags)
        self.write_columns("flow_log.l7_flow_log", out, n)
        if self.trace_trees is not None:
            self._feed_trace_trees(out, n)
        return n

    def _handle_l7_list(self, l7: list, tags: dict, off: int) -> int:
        keys = [f.key for f in l7]
        src_s = [_ip_str(k.ip_src) for k in keys]
        dst_s = [_ip_str(k.ip_dst) for k in keys]
        endpoint_cols = self._endpoint_cols(l7, keys, src_s, dst_s)
        cols = {
            "time": [f.start_time_ns + off for f in l7],
            "flow_id": [f.flow_id for f in l7],
            "ip_src": src_s,
            "ip_dst": dst_s,
            "port_src": [k.port_src for k in keys],
            "port_dst": [k.port_dst for k in keys],
            "tunnel_type": [min(int(k.tunnel_type), 4) for k in keys],
            "tunnel_id": [k.tunnel_id for k in keys],
            "l7_protocol": [int(f.l7_protocol) for f in l7],
            "version": [f.version for f in l7],
            "request_type": [f.request_type for f in l7],
            "request_domain": [f.request_domain for f in l7],
            "request_resource": [f.request_resource for f in l7],
            "endpoint": [f.endpoint for f in l7],
            "request_id": [f.request_id for f in l7],
            "response_status": [int(f.response_status) for f in l7],
            "response_code": [f.response_code for f in l7],
            "response_exception": [f.response_exception for f in l7],
            "response_result": [f.response_result for f in l7],
            "response_duration": [
                max(0, f.end_time_ns - f.start_time_ns) for f in l7],
            "trace_id": [f.trace_id for f in l7],
            "span_id": [f.span_id for f in l7],
            "parent_span_id": [f.parent_span_id for f in l7],
            "x_request_id": [f.x_request_id for f in l7],
            "syscall_trace_id_request": [
                f.syscall_trace_id_request for f in l7],
            "syscall_trace_id_response": [
                f.syscall_trace_id_response for f in l7],
            "syscall_thread_0": [f.syscall_thread_0 for f in l7],
            "syscall_thread_1": [f.syscall_thread_1 for f in l7],
            "captured_request_byte": [
                f.captured_request_byte for f in l7],
            "captured_response_byte": [
                f.captured_response_byte for f in l7],
            **endpoint_cols,
            # agent-observed kernel thread name wins (sslprobe path);
            # the socket-scan join fills the rest (may be a scalar "")
            "process_kname_0": [
                f.process_kname_0 or n for f, n in zip(
                    l7, _aslist(endpoint_cols["process_kname_0"],
                                len(l7)))],
            "process_kname_1": [
                f.process_kname_1 or n for f, n in zip(
                    l7, _aslist(endpoint_cols["process_kname_1"],
                                len(l7)))],
            "attrs": [f.attrs_json for f in l7],
        }
        cols.update(tags)  # constant per batch: scalar broadcast
        self.write_columns("flow_log.l7_flow_log", cols, len(l7))
        if self.trace_trees is not None:
            self._feed_trace_trees(cols, len(l7))
        return len(l7)

    def _feed_trace_trees(self, cols: dict, n: int) -> None:
        """Traced rows (non-empty trace_id: typically a small subset)
        feed the ingest-time trace_tree precompute."""
        from deepflow_tpu.server.tracetree import span_from_l7

        def at(col, i):
            """Columns may be scalars (constant broadcast), lists,
            ndarrays, or lazy ArenaStrings (native columnar path)."""
            if isinstance(col, (list, np.ndarray, ArenaStrings)):
                return col[i]
            return col
        tids = cols["trace_id"]
        if isinstance(tids, ArenaStrings):
            if not tids.lens.any():
                return  # no row is traced: skip the scan entirely
            tids = tids.tolist()
        elif isinstance(tids, str):
            if not tids:
                return  # all-empty broadcast: nothing is traced
            tids = [tids] * n
        for i in range(n):
            tid = tids[i]
            if not tid:
                continue
            proto_i = int(at(cols["l7_protocol"], i))
            status_i = int(at(cols["response_status"], i))
            self.trace_trees.add_span(tid, span_from_l7({
                "time": at(cols["time"], i),
                "flow_id": at(cols["flow_id"], i),
                "request_id": at(cols["request_id"], i),
                "span_id": at(cols["span_id"], i),
                "parent_span_id": at(cols["parent_span_id"], i),
                "request_type": at(cols["request_type"], i),
                "endpoint": at(cols["endpoint"], i),
                "request_resource": at(cols["request_resource"], i),
                "app_service": at(cols["app_service"], i)
                if "app_service" in cols else "",
                "service_1": at(cols.get("service_1", ""), i),
                "host": at(cols.get("host", ""), i),
                "l7_protocol": (L7_PROTOS[proto_i]
                                if 0 <= proto_i < len(L7_PROTOS)
                                else "unknown"),
                "response_status": (RESPONSE_STATUS[status_i]
                                    if 0 <= status_i < len(RESPONSE_STATUS)
                                    else "unknown"),
                "response_code": at(cols["response_code"], i),
                "response_duration": at(cols["response_duration"], i),
                "ip_src": at(cols["ip_src"], i),
                "ip_dst": at(cols["ip_dst"], i),
                "x_request_id": at(cols["x_request_id"], i),
            }))


class MetricsDecoder(Decoder):
    """DocumentBatch -> flow_metrics.network/application 1s tables.
    1m rollups are produced by the datasource rollup job, not here.

    Hot path: native columnar decode (native/ingest.cpp
    df_decode_doc_cols) — FlowMeter/AppMeter fields land in numpy arrays
    under their store column names with the GIL released; HasField
    presence rides has_flow/has_app flag columns, ip emptiness rides
    ip_flags bits. Batches with non-v4 addresses (IP_FALLBACK bit) take
    the protobuf fallback whole, keeping v6 formatting parity exact."""

    MSG_TYPE = MessageType.METRICS

    _IP_MEMO_MAX = 1 << 20

    def __init__(self, *a, **kw) -> None:
        super().__init__(*a, **kw)
        self._tl = threading.local()  # per-worker native decode buffers
        self._ip_memo: dict[int, str] = {}  # u32 -> dotted, across batches

    def _fast_decoder(self):
        dec = getattr(self._tl, "doccols", False)
        if dec is False:
            try:
                from deepflow_tpu.native import DocColumnDecoder
                dec = DocColumnDecoder()
            except Exception:
                dec = None
            self._tl.doccols = dec
        return dec

    def _dotted(self, u32s: np.ndarray, flags: np.ndarray,
                empty_bit: int) -> list:
        """u32 addresses -> dotted strings; rows whose ip_flags carry
        empty_bit render "" (pb parity: absent/empty wire bytes decode
        to the empty string, not 0.0.0.0)."""
        memo = self._ip_memo
        out = []
        for u, fl in zip(u32s.tolist(), flags.tolist()):
            if fl & empty_bit:
                out.append("")
                continue
            s = memo.get(u)
            if s is None:
                if len(memo) >= self._IP_MEMO_MAX:
                    memo.clear()
                s = memo[u] = "%d.%d.%d.%d" % (
                    u >> 24 & 255, u >> 16 & 255, u >> 8 & 255, u & 255)
            out.append(s)
        return out

    def _handle_cols(self, header: FrameHeader, n: int, cols: dict,
                     arena) -> int:
        tags = self._agent_tags(header)
        off_s = round(self._clock_offset(header) / 1e9)
        flags = cols["ip_flags"]
        src_all = self._dotted(cols["ip4_src"], flags, IP_SRC_EMPTY)
        dst_all = self._dotted(cols["ip4_dst"], flags, IP_DST_EMPTY)
        if off_s:
            time_all = (cols["timestamp_s"].astype(np.int64)
                        + off_s).astype(np.uint64)
        else:
            time_all = cols["timestamp_s"]
        resolver = None
        if self.resources is not None and not self.resources.is_empty():
            resolver = self.resources.batch_resolver()

        def base_cols(idx: np.ndarray) -> tuple[dict, int]:
            ii = idx.tolist()
            src_s = [src_all[i] for i in ii]
            dst_s = [dst_all[i] for i in ii]
            out = {
                "time": time_all[idx],
                "ip_src": src_s,
                "ip_dst": dst_s,
                "server_port": cols["port"][idx],
            }
            if resolver is not None:
                t0 = [resolver(s) for s in src_s]
                t1 = [resolver(s) for s in dst_s]
                out["pod_0"] = [t.pod for t in t0]
                out["pod_1"] = [t.pod for t in t1]
                for name in SIDE_RESOLVE_NAMES:
                    out[f"{name}_0"] = [getattr(t, name) for t in t0]
                    out[f"{name}_1"] = [getattr(t, name) for t in t1]
            elif self.resources is not None:
                out["pod_0"] = ""
                out["pod_1"] = ""
                for name in SIDE_RESOLVE_NAMES:
                    out[f"{name}_0"] = ""
                    out[f"{name}_1"] = ""
            out.update(tags)
            return out, len(ii)

        n_rows = 0
        net_idx = np.flatnonzero(cols["has_flow"])
        if len(net_idx):
            c, k = base_cols(net_idx)
            c.update({
                "protocol": cols["proto"][net_idx],
                "direction": cols["direction"][net_idx],
            })
            for name in ("packet_tx", "packet_rx", "byte_tx", "byte_rx",
                         "flow_count", "new_flow", "closed_flow",
                         "rtt_sum", "rtt_count", "retrans", "syn_count",
                         "synack_count"):
                c[name] = cols[name][net_idx]
            self.write_columns("flow_metrics.network.1s", c, k)
            n_rows += k
        app_idx = np.flatnonzero(cols["has_app"])
        if len(app_idx):
            c, k = base_cols(app_idx)
            lens = cols["app_service_len"][app_idx]
            c["l7_protocol"] = cols["l7_protocol"][app_idx]
            c["app_service"] = (
                ArenaStrings(arena, cols["app_service_off"][app_idx],
                             lens) if lens.any() else "")
            for name in ("request", "response", "rrt_sum", "rrt_count",
                         "rrt_max", "error_client", "error_server",
                         "timeout"):
                c[name] = cols[name][app_idx]
            self.write_columns("flow_metrics.application.1s", c, k)
            n_rows += k
        return n_rows

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        fast = self._fast_decoder()
        if fast is not None:
            try:
                res = fast.decode(payload)
            except Exception:
                res = None
            # any non-{empty,v4} address (v6) -> pb path for the whole
            # batch: printable v6 formatting stays in one place
            if res is not None and \
                    not (res[1]["ip_flags"] & IP_FALLBACK).any():
                return self._handle_cols(header, res[0], res[1], res[2])
        batch = pb.DocumentBatch.FromString(payload)
        tags = self._agent_tags(header)
        off_s = round(self._clock_offset(header) / 1e9)  # table is 1s-grain
        n = 0

        def base_cols(docs):
            src_s = [_ip_str(d.tag.ip_src) for d in docs]
            dst_s = [_ip_str(d.tag.ip_dst) for d in docs]
            cols = {
                "time": [d.timestamp_s + off_s for d in docs],
                "ip_src": src_s,
                "ip_dst": dst_s,
                "server_port": [d.tag.port for d in docs],
            }
            if self.resources is not None and not self.resources.is_empty():
                # per-side universal tags on metrics rows: this is what
                # makes "group any metric by any resource" work
                res = self.resources.batch_resolver()
                t0 = [res(s) for s in src_s]
                t1 = [res(s) for s in dst_s]
                cols["pod_0"] = [t.pod for t in t0]
                cols["pod_1"] = [t.pod for t in t1]
                for name in SIDE_RESOLVE_NAMES:
                    cols[f"{name}_0"] = [getattr(t, name) for t in t0]
                    cols[f"{name}_1"] = [getattr(t, name) for t in t1]
            elif self.resources is not None:
                # keep the exported row shape stable vs the resolving case
                cols["pod_0"] = ""
                cols["pod_1"] = ""
                for name in SIDE_RESOLVE_NAMES:
                    cols[f"{name}_0"] = ""
                    cols[f"{name}_1"] = ""
            cols.update(tags)  # constant per batch: scalar broadcast
            return cols

        net = [d for d in batch.docs if d.HasField("flow_meter")]
        if net:
            ms = [d.flow_meter for d in net]
            cols = base_cols(net)
            cols.update({
                "protocol": [int(d.tag.proto) for d in net],
                "direction": [d.tag.direction for d in net],
                "packet_tx": [m.packet_tx for m in ms],
                "packet_rx": [m.packet_rx for m in ms],
                "byte_tx": [m.byte_tx for m in ms],
                "byte_rx": [m.byte_rx for m in ms],
                "flow_count": [m.flow_count for m in ms],
                "new_flow": [m.new_flow for m in ms],
                "closed_flow": [m.closed_flow for m in ms],
                "rtt_sum": [m.rtt_sum_us for m in ms],
                "rtt_count": [m.rtt_count for m in ms],
                "retrans": [m.retrans for m in ms],
                "syn_count": [m.syn_count for m in ms],
                "synack_count": [m.synack_count for m in ms],
            })
            self.write_columns("flow_metrics.network.1s", cols, len(net))
            n += len(net)
        app = [d for d in batch.docs if d.HasField("app_meter")]
        if app:
            ms = [d.app_meter for d in app]
            cols = base_cols(app)
            cols.update({
                "l7_protocol": [int(d.tag.l7_protocol) for d in app],
                "app_service": [d.tag.app_service for d in app],
                "request": [m.request for m in ms],
                "response": [m.response for m in ms],
                "rrt_sum": [m.rrt_sum_us for m in ms],
                "rrt_count": [m.rrt_count for m in ms],
                "rrt_max": [m.rrt_max_us for m in ms],
                "error_client": [m.error_client for m in ms],
                "error_server": [m.error_server for m in ms],
                "timeout": [m.timeout for m in ms],
            })
            self.write_columns("flow_metrics.application.1s", cols, len(app))
            n += len(app)
        return n


class StatsDecoder(Decoder):
    """StatsBatch -> deepflow_system (self-telemetry)."""

    MSG_TYPE = MessageType.DFSTATS

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        batch = pb.StatsBatch.FromString(payload)
        tags = self._agent_tags(header)
        off = self._clock_offset(header)
        rows = []
        for m in batch.metrics:
            tag_json = json.dumps(dict(m.tags), sort_keys=True)
            for vname, v in m.values.items():
                rows.append({
                    "time": m.timestamp_ns + off,
                    "metric_name": m.name,
                    "tag_json": tag_json,
                    "value_name": vname,
                    "value": v,
                    **tags,
                })
        self.write("deepflow_system.deepflow_system", rows)
        return len(rows)


class EventDecoder(Decoder):
    """EventBatch -> event.event, plus the file-IO aggregation reducer
    (reference: ingester/event/decoder/file_agg_reducer.go): raw
    file-io-read/write events roll up into per-(pid, path, op) minute
    windows in event.file_agg."""

    MSG_TYPE = MessageType.EVENT

    WINDOW_NS = 60 * 1_000_000_000
    GRACE_NS = 5 * 1_000_000_000

    def __init__(self, *a, **kw) -> None:
        super().__init__(*a, **kw)
        # (window_ns, pid, path, op, tags_json) -> [count, bytes, max, sum]
        # guarded by _agg_lock: this decoder is stateful, so the base
        # class's WORKERS>1 knob must not corrupt the windows
        self._agg: dict[tuple, list] = {}
        self._agg_lock = threading.Lock()
        self._watermark = 0

    def handle(self, header: FrameHeader, payload: bytes) -> int:
        batch = pb.EventBatch.FromString(payload)
        tags = self._agent_tags(header)
        off = self._clock_offset(header)
        rows = [{
            "time": e.timestamp_ns + off,
            "event_type": e.event_type,
            "resource_type": e.resource_type,
            "resource_name": e.resource_name,
            "pid": e.pid,
            "description": e.description,
            "attrs": json.dumps(dict(e.attrs), sort_keys=True),
            **tags,
        } for e in batch.events]
        self.write("event.event", rows)
        # tags are constant per batch: serialize ONCE, not per io event
        tags_json = json.dumps(tags, sort_keys=True)
        for e in batch.events:
            if e.event_type in ("file-io-read", "file-io-write"):
                self._reduce_file_io(e, tags_json)
        self._flush_agg()
        return len(rows)

    def _reduce_file_io(self, e, tags_json: str) -> None:
        op = 0 if e.event_type == "file-io-read" else 1
        window = e.timestamp_ns - e.timestamp_ns % self.WINDOW_NS
        try:
            latency = int(e.attrs.get("latency_ns", "0"))
            nbytes = int(e.attrs.get("bytes", "0"))
        except ValueError:
            latency = nbytes = 0
        key = (window, e.pid, e.resource_name, op, tags_json)
        with self._agg_lock:
            acc = self._agg.get(key)
            if acc is None:
                acc = self._agg[key] = [0, 0, 0, 0]
            acc[0] += 1
            acc[1] += nbytes
            acc[2] = max(acc[2], latency)
            acc[3] += latency
            if e.timestamp_ns > self._watermark:
                self._watermark = e.timestamp_ns

    def _flush_agg(self, force: bool = False) -> None:
        """Emit windows the watermark has passed (late events within the
        grace period still merge; anything later starts a fresh row —
        counts stay correct, the window just splits)."""
        rows = []
        with self._agg_lock:
            limit = self._watermark - self.WINDOW_NS - self.GRACE_NS
            for key in [k for k in self._agg
                        if force or k[0] <= limit]:
                window, pid, path, op, tags_json = key
                count, nbytes, mx, total = self._agg.pop(key)
                rows.append({
                    "time": window, "pid": pid, "path": path, "op": op,
                    "count": count, "bytes": nbytes,
                    "max_latency_ns": mx, "sum_latency_ns": total,
                    **json.loads(tags_json),
                })
        if rows:
            self.write("event.file_agg", rows)

    def flush(self) -> None:
        """Final flush (server shutdown / tests)."""
        self._flush_agg(force=True)


def _aslist(v, n: int) -> list:
    """Scalar column broadcast -> per-row list (store columns may be
    scalars meaning 'this value for every row')."""
    if isinstance(v, ArenaStrings):
        return v.tolist()
    return v if isinstance(v, list) else [v] * n


_IP_CACHE: dict[bytes, tuple[str, int]] = {}
_IP_CACHE_MAX = 1 << 16


def _ip_decode(raw: bytes) -> tuple[str, int]:
    """raw bytes -> (dotted string, u32). Memoized: real traffic repeats a
    bounded host set, so the formatting cost is paid once per address."""
    hit = _IP_CACHE.get(raw)
    if hit is not None:
        return hit
    if len(raw) == 4:
        val = ("%d.%d.%d.%d" % (raw[0], raw[1], raw[2], raw[3]),
               int.from_bytes(raw, "big"))
    elif not raw:
        val = ("", 0)
    else:
        import ipaddress
        try:
            val = (str(ipaddress.ip_address(raw)), 0)
        except ValueError:
            val = (raw.hex(), 0)
    if len(_IP_CACHE) >= _IP_CACHE_MAX:
        _IP_CACHE.clear()  # coarse reset beats per-entry LRU bookkeeping
    _IP_CACHE[bytes(raw)] = val
    return val


def _ip_str(raw: bytes) -> str:
    return _ip_decode(raw)[0]


def _ip4_u32(raw: bytes) -> int:
    return _ip_decode(raw)[1]


def _close_type_idx(name: str) -> int:
    try:
        return CLOSE_TYPES.index(name or "unknown")
    except ValueError:
        return 0


ALL_DECODERS = [ProfileDecoder, TpuSpanDecoder, StepMetricsDecoder,
                FlowLogDecoder, MetricsDecoder, StatsDecoder, EventDecoder]
