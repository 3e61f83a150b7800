"""UniformSender: batched, framed, reconnecting TCP telemetry sender.

Reference analog: agent/src/sender/uniform_sender.rs (Header prepend
:149-210, batching, compression, server failover).

Durable-delivery layer (this port goes beyond the reference, which is
fire-and-forget): every frame carries a monotonically increasing
per-agent ``seq`` (codec v2).  The server periodically writes ACK
frames (highest contiguous seq) back down the same TCP connection; the
sender keeps sent-but-unacked frames in a bounded retransmit window and
replays them after a reconnect, the server's decoders dedup on
``(agent_id, seq)`` — together: at-least-once delivery, exactly-once
rows.  Frames that would previously be dropped (queue overflow with no
lower-priority victim, a failed in-flight write, a dead server) spill
into an on-disk ``Spool`` and replay on reconnect.  Under pressure the
sender sheds by message-type class — DFSTATS/self-mon first,
STEP_METRICS/flow/trace data last — with per-class ``dropped(reason)``
ledger accounting, replacing the old blind drop-newest.

Seq-space discipline (what keeps the server's per-agent state honest):

* The counter is seeded per boot from a ~1ms wall-clock epoch in the
  high bits (``epoch << 22 | counter``), max-ed with the recovered
  spool's highest seq.  A restarted agent therefore always starts
  ABOVE any watermark or dedup floor the server still holds for its
  agent_id — without this, a restart would replay seq 1.. into a
  server whose watermark/dedup floor sits at the old boot's high-water
  mark, and every frame would be silently discarded as a dup.
* A seq is allocated at a frame's FIRST wire or spool write, never at
  ``send()``: a frame shed or dropped before reaching the wire never
  owned a seq, so it cannot leave a permanent gap that stalls the
  server's contiguous watermark (and with it acks, window trim and
  spool trim).
* The few events that DO burn an allocated seq (spool eviction at the
  disk cap, a spool disk error) — and every (re)connect — make the
  sender announce a ``SEQ_BASE`` control frame: "no seq below B will
  ever be sent (again)".  The server fast-forwards its watermark to
  B-1 instead of parking the dead gap until MAX_OOS forces a jump.

Ledger discipline: ``emitted`` is accounted once per ``send()``,
``delivered`` once per frame at its FIRST successful socket write
(retransmits of unacked frames are counted in ``stats`` but not
re-accounted), and every shed/evicted/undeliverable frame is a
``dropped(reason)`` — so ``emitted == delivered + dropped + in_flight``
holds exactly, spool or no spool.
"""

from __future__ import annotations

import logging
import queue
import select
import socket
import struct
import threading
import time

from deepflow_tpu.codec import (
    SEQ_EXT_FMT, FrameDecodeError, FrameHeader, MessageType, StreamDecoder,
    encode_frame, encode_seq_base, priority_of)
from deepflow_tpu.query import qtrace

log = logging.getLogger("df.sender")

_PRIO_NAMES = {0: "high", 1: "mid", 2: "low"}


class _Frame:
    """One frame's transit state. ``needs_account`` flips False at the
    first successful write so retransmits never double-count. ``seq``
    stays None until the frame first reaches the wire or the spool —
    shed/dropped frames never own one. ``trace`` is (trace context, wall
    ns at send) for a frame sent under an open trace, else None."""

    __slots__ = ("msg_type", "payload", "seq", "enq_ns", "needs_account",
                 "trace")

    def __init__(self, msg_type: MessageType, payload: bytes,
                 seq: int | None, enq_ns: int | None,
                 needs_account: bool = True) -> None:
        self.msg_type = msg_type
        self.payload = payload
        self.seq = seq
        self.enq_ns = enq_ns
        self.needs_account = needs_account
        self.trace = None


class UniformSender:
    """One TCP connection shipping frames for all message types.

    Thread-safe send(): enqueue (msg_type, payload); a background thread
    frames and writes, reconnecting with exponential backoff across the
    configured server list (failover, like the reference's sender)."""

    def __init__(self, servers: list[tuple[str, int]], agent_id: int = 0,
                 org_id: int = 0, team_id: int = 0, queue_size: int = 8192,
                 connect_timeout: float = 3.0, telemetry=None,
                 spool=None, ack_window: int = 1024,
                 durable: bool = True, chaos=None, tracer=None) -> None:
        if not servers:
            raise ValueError("need at least one server address")
        from deepflow_tpu.agent.config import _parse_addr
        # a frame sent under an open trace (the probe's capture cycle)
        # becomes a send.frame span of it once written (query/qtrace.py)
        self._tracer = tracer
        self.servers = [_parse_addr(s) if isinstance(s, str) else tuple(s)
                        for s in servers]
        self.agent_id = agent_id
        self.org_id = org_id
        self.team_id = team_id
        self.connect_timeout = connect_timeout
        self._q: queue.Queue = queue.Queue(maxsize=queue_size)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._sock: socket.socket | None = None
        self._server_idx = 0
        # durable=False reverts to the seq-less v1 wire (no ack window,
        # no retransmit) — the bench baseline arm and a compat escape
        # hatch for pre-ACK servers
        self.durable = durable
        self.spool = spool
        self.ack_window = max(1, ack_window)
        if chaos is None:
            from deepflow_tpu.chaos import chaos_from_env
            chaos = chaos_from_env()
        self._chaos = chaos
        self._seq_lock = threading.Lock()
        # per-boot epoch above a 22-bit counter (~1ms units, unmasked so
        # it can never wrap backward; ~2^41 * 2^22 still fits u64): a
        # restarted agent's seqs start above anything the server
        # remembers for this agent_id, even across a fast clean restart
        # whose trimmed-empty spool has no max_seq to recover — the
        # counter outgrowing 22 bits just bleeds into epoch space, which
        # stays monotonic because real send rates are far below the
        # 4M-frames-per-ms that region represents
        self._next_seq = ((time.time_ns() >> 20) << 22) | 1
        if spool is not None:
            self._next_seq = max(self._next_seq, spool.max_seq() + 1)
        self.seq_base = self._next_seq - 1     # seqs are seq_base+1, +2, ...
        self._acked = 0                       # highest contiguous acked
        self._unacked: dict[int, _Frame] = {}  # sent, awaiting ack
        self._pending: list[_Frame] = []       # retransmit/replay, FIFO
        self._inflight: _Frame | None = None
        self._spool_replayed_through = 0
        self._base_dirty = False  # a seq was burned: re-announce SEQ_BASE
        # delivered frames evicted from the retransmit window before
        # their ack: still possibly in a decoder queue, so SEQ_BASE must
        # never advance past them (the dedup floor would drop their rows)
        self._evicted_unacked: set[int] = set()
        self._ackdec = StreamDecoder()
        self.stats = {"sent_frames": 0, "sent_bytes": 0, "dropped": 0,
                      "reconnects": 0, "errors": 0, "retransmits": 0,
                      "spooled": 0, "replayed": 0, "acked_seq": 0,
                      "shed": 0, "unacked_evicted": 0, "seq_bases": 0}
        if telemetry is None:
            from deepflow_tpu.telemetry import Telemetry
            telemetry = Telemetry("agent", enabled=False)
        self._hop = telemetry.hop("sender")
        self._telemetry = telemetry
        if self.spool is not None:
            # a spool recovered from a previous process holds frames
            # that were never emitted on THIS ledger: account them in
            # so replay's delivered keeps the ledger balanced
            self.spool.on_evict = self._on_spool_evict
            recovered = self.spool.pending_records()
            if recovered:
                self._hop.account(emitted=recovered)

    def _on_spool_evict(self, n: int, reason: str) -> None:
        self.stats["dropped"] += n
        self._hop.account(dropped=n, reason=reason)
        # evicted records owned seqs that will never be sent: tell the
        # server so its contiguous watermark doesn't stall on the gap
        self._base_dirty = True

    def start(self) -> "UniformSender":
        self._thread = threading.Thread(
            target=self._run, name="df-uniform-sender", daemon=True)
        self._thread.start()
        return self

    def queue_depth(self) -> int:
        return self._q.qsize()

    def buffered_bytes(self) -> int:
        """Payload bytes held in memory: queued, awaiting an ack or a
        resend (the spool is on disk)."""
        with self._q.mutex:
            held = list(self._q.queue)
        held += list(self._unacked.values()) + list(self._pending)
        return sum(len(f.payload) for f in held)

    def peek(self, n: int = 8) -> list:
        """Non-consuming sample of queued frames (debug queue tap)."""
        with self._q.mutex:
            items = list(self._q.queue)[:n]
        return [{"type": getattr(f.msg_type, "name", str(f.msg_type)),
                 "bytes": len(f.payload)} for f in items]

    def _alloc_seq(self) -> int:
        with self._seq_lock:
            seq = self._next_seq
            self._next_seq += 1
            return seq

    def send(self, msg_type: MessageType, payload: bytes) -> bool:
        self._hop.account(emitted=1)
        # no seq yet: a seq is allocated at the frame's first wire/spool
        # write, so a frame shed or dropped before reaching either never
        # burns one (a burned seq is a permanent gap that stalls the
        # server's contiguous watermark — and with it every ack)
        f = _Frame(msg_type, payload, None, time.monotonic_ns())
        if self._tracer is not None:
            ctx = qtrace.ctx_for_wire()
            if ctx is not None:
                f.trace = (ctx, time.time_ns())
        try:
            self._q.put_nowait(f)
            return True
        except queue.Full:
            pass
        # prioritized backpressure: shed the lowest-priority queued frame
        # strictly below this one's class before giving up room
        mine = priority_of(msg_type)
        victim = self._shed_lower_than(mine)
        if victim is not None:
            self._drop(victim, "priority_shed_"
                       + _PRIO_NAMES[priority_of(victim.msg_type)])
            self.stats["shed"] += 1
            try:
                self._q.put_nowait(f)
                return True
            except queue.Full:
                pass  # raced with other senders: fall through
        if self.spool is not None and mine == 0:
            # high-priority frames survive overflow on disk
            f.seq = self._alloc_seq()
            if self.spool.append(int(msg_type), f.seq, f.payload):
                self.stats["spooled"] += 1
                return True
            self._drop(f, "spool_error")
            self._base_dirty = True  # that seq is now a permanent gap
            return False
        self._drop(f, f"queue_full_{_PRIO_NAMES[mine]}")
        return False

    def _drop(self, f: _Frame, reason: str) -> None:
        self.stats["dropped"] += 1
        self._hop.account(dropped=1, reason=reason)

    def _shed_lower_than(self, prio: int) -> _Frame | None:
        """Remove and return the oldest queued frame with a strictly
        lower priority class than ``prio`` (higher numeric = lower)."""
        with self._q.mutex:
            dq = self._q.queue
            worst_i, worst_p = -1, prio
            for i, f in enumerate(dq):
                p = priority_of(f.msg_type)
                if p > worst_p:
                    worst_i, worst_p = i, p
                    if p == 2:
                        break  # can't get lower
            if worst_i < 0:
                return None
            victim = dq[worst_i]
            del dq[worst_i]
            self._q.not_full.notify()
            return victim

    # -- shutdown ------------------------------------------------------------

    def flush_and_stop(self, timeout: float = 5.0) -> None:
        deadline = time.monotonic() + timeout
        # wait for the queue AND the retransmit/replay backlog AND the
        # in-flight frame — _q.empty() alone used to abandon the frame
        # the worker had already dequeued
        while time.monotonic() < deadline:
            if self._q.empty() and self._inflight is None \
                    and not self._pending and not self._spool_backlog():
                break
            if self._stop.wait(0.02):
                break
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2.0)
        self._close()
        if self.spool is not None:
            self.spool.close()

    def drain_unsent(self) -> list[tuple[MessageType, bytes]]:
        """Stop this sender and hand back every frame NOT yet acked, in
        seq order — the replication rebalance path: when a destination
        loses ownership, its queued/unacked/spooled frames are re-shipped
        to the new owners instead of being dropped with the sender.

        Acked frames are excluded (they are durably at the old owner and
        claimed there or by its replicas); an unacked frame that in fact
        landed may be re-reported once after an ownership change —
        delivery across rebalances is at-least-once, exactly-once within
        a stable ring (docs/CLUSTER.md). Undelivered frames are closed
        out on the ledger as dropped(rebalance); re-sending them through
        a new sender re-emits them on the same hop, so the ledger stays
        balanced end to end."""
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2.0)
            self._thread = None
        frames: dict[int | None, _Frame] = {}
        leftovers: list[_Frame] = []
        while True:
            try:
                f = self._q.get_nowait()
            except queue.Empty:
                break
            if f.seq is None:
                leftovers.append(f)
            else:
                frames.setdefault(f.seq, f)
        self._close()  # moves _unacked into _pending
        for f in self._pending:
            if f.seq is None or f.seq > self._acked:
                if f.seq is None:
                    leftovers.append(f)
                else:
                    frames.setdefault(f.seq, f)
        self._pending = []
        if self.spool is not None:
            for mt, seq, payload in self.spool.replay(self._acked):
                if seq in frames:
                    continue
                try:
                    msg_type = MessageType(mt)
                except ValueError:
                    continue
                frames[seq] = _Frame(msg_type, payload, seq, None)
            self.spool.close()
        out = []
        for f in sorted(frames.values(), key=lambda fr: fr.seq) + leftovers:
            if f.needs_account:
                self._drop(f, "rebalance")
            out.append((f.msg_type, f.payload))
        return out

    def _spool_backlog(self) -> bool:
        """True while the spool holds records not yet handed to replay."""
        return (self.durable and self.spool is not None
                and self.spool.max_seq() > max(self._acked,
                                               self._spool_replayed_through))

    def _close(self) -> None:
        if self._sock:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
        self._ackdec = StreamDecoder()
        # sent-but-unacked frames go back on the retransmit list: the
        # server may or may not have them; dedup makes resending safe.
        # Class-major order (HIGH, then MID, then LOW; seq within a
        # class): after an outage the profiles/spans the operator is
        # debugging with arrive before bulk stats — dedup + per-frame
        # seqs make out-of-seq delivery safe
        if self.durable and self._unacked:
            backlog = list(self._unacked.values())
            self._unacked.clear()
            self._pending = sorted(
                self._pending + backlog,
                key=lambda f: (priority_of(f.msg_type), f.seq))

    def _connect(self) -> bool:
        """Try servers round-robin starting at the current index."""
        for i in range(len(self.servers)):
            host, port = self.servers[(self._server_idx + i)
                                      % len(self.servers)]
            try:
                if self._chaos is not None:
                    self._chaos.on_connect()
                s = socket.create_connection(
                    (host, port), timeout=self.connect_timeout)
                s.settimeout(10.0)
                self._sock = s
                self._server_idx = (self._server_idx + i) % len(self.servers)
                self.stats["reconnects"] += 1
                if self.durable:
                    self._load_replay()
                return True
            except OSError:
                continue
        return False

    def _load_replay(self) -> None:
        """Queue spooled frames (never yet sent) for delivery. Unacked
        retransmits were already moved to _pending by _close()."""
        if self.spool is None:
            return
        start = max(self._acked, self._spool_replayed_through)
        fresh = []
        pending_seqs = {f.seq for f in self._pending}
        for mt, seq, payload in self.spool.replay(start):
            if seq in pending_seqs:
                continue
            try:
                msg_type = MessageType(mt)
            except ValueError:
                continue
            fresh.append(_Frame(msg_type, payload, seq, None))
            self._spool_replayed_through = max(
                self._spool_replayed_through, seq)
        if fresh:
            self.stats["replayed"] += len(fresh)
            # HIGH replays before MID/LOW (see _close): an outage must
            # not make bulk stats queue ahead of profile/span frames
            self._pending = sorted(
                self._pending + fresh,
                key=lambda f: (priority_of(f.msg_type), f.seq))

    # -- seq-base announcements ----------------------------------------------

    def _outstanding_base(self) -> int:
        """Lowest seq this sender may still (re)send. Everything below
        it is either acked or permanently gone (dropped with ledger
        accounting) — safe for the server to declare dead. Conservative
        (too-low) answers are harmless: the server only moves forward."""
        with self._seq_lock:
            cands = [self._next_seq]
        f = self._inflight
        if f is not None and f.seq is not None:
            cands.append(f.seq)
        cands.extend(fr.seq for fr in self._pending if fr.seq is not None)
        if self._unacked:
            cands.append(min(self._unacked))
        if self._evicted_unacked:
            # delivered but unacked and no longer retransmittable: they
            # may still be sitting in a server decode queue, so the base
            # (and with it the dedup floor) must stay below them
            cands.append(min(self._evicted_unacked))
        if self.spool is not None:
            s = self.spool.min_pending_seq()
            if s:
                cands.append(max(s, self._acked + 1))
        return min(cands)

    def _send_base(self) -> None:
        """Announce SEQ_BASE on the live connection (worker thread only).
        Sent after every (re)connect — a restarted agent's fresh epoch
        seq space, or any seqs burned while disconnected, fast-forward
        the server's watermark — and whenever an event burns a seq
        mid-connection (spool evict / spool disk error)."""
        frame = encode_seq_base(self.agent_id, self._outstanding_base())
        try:
            if self._chaos is not None:
                self._chaos.on_send(self._sock, frame)
            else:
                self._sock.sendall(frame)
            self.stats["seq_bases"] += 1
            self.stats["sent_bytes"] += len(frame)
            self._base_dirty = False
        except OSError as e:
            log.warning("seq-base send failed (%s); reconnecting", e)
            self.stats["errors"] += 1
            self._close()
            self._server_idx = (self._server_idx + 1) % len(self.servers)

    # -- ack processing ------------------------------------------------------

    def _read_acks(self) -> None:
        """Drain any ACK frames the server wrote back (non-blocking)."""
        sock = self._sock
        if sock is None or not self.durable:
            return
        try:
            while True:
                r, _, _ = select.select([sock], [], [], 0)
                if not r:
                    return
                data = sock.recv(4096)
                if not data:
                    raise OSError("server closed connection")
                for header, payload in self._ackdec.feed(data):
                    if header.msg_type == MessageType.ACK:
                        self._on_ack(
                            struct.unpack_from(SEQ_EXT_FMT, payload)[0])
        except (OSError, FrameDecodeError, struct.error) as e:
            log.warning("ack channel failed (%s); reconnecting", e)
            self.stats["errors"] += 1
            self._close()
            self._server_idx = (self._server_idx + 1) % len(self.servers)

    def _on_ack(self, seq: int) -> None:
        if seq <= self._acked:
            return
        self._acked = seq
        self.stats["acked_seq"] = seq
        for s in [s for s in self._unacked if s <= seq]:
            del self._unacked[s]
        kept = []
        for f in self._pending:
            if f.seq > seq:
                kept.append(f)
            elif f.needs_account:
                # the server acked a frame we thought undelivered (e.g.
                # a chaos partial write that actually landed whole):
                # it IS delivered; close its ledger entry
                self._hop.account(delivered=1)
                f.needs_account = False
        self._pending = kept
        self._evicted_unacked = {s for s in self._evicted_unacked
                                 if s > seq}
        if self.spool is not None:
            self.spool.trim(seq)
        # the ack may have drained everything below a dead gap (e.g. a
        # recovered spool's old-boot records just finished): announce the
        # jump so the server's watermark doesn't stall at the gap's edge
        if self._outstanding_base() > seq + 1:
            self._base_dirty = True

    # -- send loop -----------------------------------------------------------

    def _next_frame(self) -> _Frame | None:
        if self._pending:
            return self._pending.pop(0)
        try:
            return self._q.get(timeout=0.2)
        except queue.Empty:
            return None

    def _send_frame(self, f: _Frame) -> None:
        self._inflight = f
        is_retransmit = not f.needs_account
        if self.durable and f.seq is None:
            # first wire write: the seq is born here, in write order, so
            # the watermark at the server stays gap-free for frames that
            # actually travel (spooled frames got theirs at spool time)
            f.seq = self._alloc_seq()
        frame = encode_frame(
            FrameHeader(f.msg_type, agent_id=self.agent_id,
                        org_id=self.org_id, team_id=self.team_id,
                        seq=f.seq if self.durable else None),
            f.payload)
        try:
            if self._chaos is not None:
                self._chaos.on_send(self._sock, frame)
            else:
                self._sock.sendall(frame)
            self.stats["sent_frames"] += 1
            self.stats["sent_bytes"] += len(frame)
            if is_retransmit:
                self.stats["retransmits"] += 1
            if f.needs_account:
                if f.enq_ns is not None:
                    self._hop.account(
                        delivered=1,
                        wait_ns=time.monotonic_ns() - f.enq_ns)
                else:
                    self._hop.account(delivered=1)
                f.needs_account = False
                if f.trace is not None:
                    ctx, sent_ns = f.trace
                    self._tracer.record(
                        ctx, "send.frame", sent_ns, time.time_ns(),
                        agent_id=self.agent_id, seq=f.seq,
                        msg_type=f.msg_type.name, bytes=len(frame))
            if self.durable:
                self._unacked[f.seq] = f
                self._cap_unacked()
        except OSError as e:
            # the frame is NOT lost: keep it at the head of the
            # retransmit list (or spool it) before rotating servers
            self.stats["errors"] += 1
            log.warning("send failed (%s); reconnecting", e)
            if f.seq is None:  # non-durable: spool still keys on seq
                f.seq = self._alloc_seq()
            if self.spool is not None and f.needs_account \
                    and f.seq > self._spool_replayed_through:
                if self.spool.append(int(f.msg_type), f.seq, f.payload):
                    self.stats["spooled"] += 1
                else:
                    self._pending.insert(0, f)
            else:
                self._pending.insert(0, f)
            self._close()
            self._server_idx = (self._server_idx + 1) % len(self.servers)
        finally:
            self._inflight = None

    def _cap_unacked(self) -> None:
        """Bound retransmit-window memory. Evicted frames were DELIVERED
        (ledger-wise nothing is lost) — we only give up the ability to
        retransmit them, so delivery degrades to at-most-once beyond the
        window. Sized so a well-acking server never hits it."""
        while len(self._unacked) > self.ack_window:
            oldest = min(self._unacked)
            del self._unacked[oldest]
            self._evicted_unacked.add(oldest)
            self.stats["unacked_evicted"] += 1
        # bound the evicted-seq floor set too; beyond it delivery was
        # already at-most-once, so forgetting the oldest loses nothing
        while len(self._evicted_unacked) > 4 * self.ack_window:
            self._evicted_unacked.discard(min(self._evicted_unacked))

    def _run(self) -> None:
        backoff = 0.1
        hb = self._telemetry.heartbeat("sender")
        while not self._stop.is_set():
            hb.beat(progress=self.stats["sent_frames"])
            if self._sock is None:
                if not self._connect():
                    # interruptible backoff: flush_and_stop used to eat
                    # up to 5s of unkillable time.sleep() here
                    if self._stop.wait(min(backoff, 5.0)):
                        return
                    backoff = min(backoff * 2, 5.0)
                    continue
                backoff = 0.1
                if self.durable:
                    # adopt this boot's seq space / skip dead gaps
                    self._send_base()
                    if self._sock is None:
                        continue
            self._read_acks()
            if self._sock is None:
                continue  # ack channel died; reconnect first
            if self.durable and self._base_dirty:
                self._send_base()
                if self._sock is None:
                    continue
            f = self._next_frame()
            if f is None:
                # idle: frames that overflowed into the spool while the
                # connection was busy drain now, without a reconnect
                if self.durable:
                    self._load_replay()
                continue
            self._send_frame(f)


class ReplicatedSender:
    """Replicated shipping: one independent UniformSender per owner
    destination, HIGH/MID frames fanned to all of them, LOW frames to
    the primary only (sheddable data doesn't earn R copies).

    Each destination gets its OWN seq space, ack window, and spool
    subdirectory — per-server watermarks are already independent on the
    server side, so the existing seq/ack/spool machinery applies per
    destination unchanged: a dead primary's frames sit durably in its
    replica senders' windows/spools and the replicas' copies are what
    the query-time claim filter promotes when the primary dies.

    ``set_destinations`` (driven by the synchronizer's analyzer_addrs
    path on a ring-epoch bump) rebalances without dropping frames: a
    removed destination's un-acked/spooled frames are harvested via
    ``drain_unsent`` and re-shipped to the newly added owners (never to
    retained ones, which already hold their own copies).

    Duck-types the UniformSender surface the agent's components use:
    send / start / flush_and_stop / servers / agent_id / stats /
    queue_depth / peek.
    """

    def __init__(self, servers: list, replication: int = 2,
                 agent_id: int = 0, org_id: int = 0, team_id: int = 0,
                 queue_size: int = 8192, connect_timeout: float = 3.0,
                 telemetry=None, spool_factory=None, ack_window: int = 1024,
                 durable: bool = True, chaos=None, tracer=None) -> None:
        if not servers:
            raise ValueError("need at least one server address")
        from deepflow_tpu.agent.config import _parse_addr
        parsed = [_parse_addr(s) if isinstance(s, str) else tuple(s)
                  for s in servers]
        self.replication = max(1, int(replication))
        self._agent_id = agent_id
        self.org_id = org_id
        self.team_id = team_id
        self._kw = dict(queue_size=queue_size,
                        connect_timeout=connect_timeout,
                        telemetry=telemetry, ack_window=ack_window,
                        durable=durable, chaos=chaos, tracer=tracer)
        # spool_factory(dest_key) -> Spool | None: one spool dir per
        # destination (their seq spaces are unrelated; sharing a spool
        # would interleave them and break trim/replay watermarks)
        self._spool_factory = spool_factory or (lambda key: None)
        self._lock = threading.Lock()
        self._senders: dict[tuple, UniformSender] = {}
        self._order: list[tuple] = []
        self._started = False
        self.stats = {"rebalances": 0, "reshipped": 0}
        for dest in parsed[:self.replication]:
            self._add_dest(dest)

    @staticmethod
    def _dest_key(dest: tuple) -> str:
        return f"{dest[0]}_{dest[1]}".replace(":", "_")

    def _add_dest(self, dest: tuple) -> None:
        s = UniformSender([dest], agent_id=self._agent_id,
                          org_id=self.org_id, team_id=self.team_id,
                          spool=self._spool_factory(self._dest_key(dest)),
                          **self._kw)
        self._senders[dest] = s
        self._order.append(dest)
        if self._started:
            s.start()

    # -- UniformSender surface ----------------------------------------------

    @property
    def agent_id(self) -> int:
        return self._agent_id

    @agent_id.setter
    def agent_id(self, v: int) -> None:
        self._agent_id = v
        with self._lock:
            for s in self._senders.values():
                s.agent_id = v

    @property
    def servers(self) -> list:
        with self._lock:
            return list(self._order)

    @servers.setter
    def servers(self, addrs: list) -> None:
        self.set_destinations(addrs)

    def start(self) -> "ReplicatedSender":
        with self._lock:
            self._started = True
            for s in self._senders.values():
                s.start()
        return self

    def send(self, msg_type: MessageType, payload: bytes) -> bool:
        with self._lock:
            if not self._order:
                return False
            if priority_of(msg_type) >= 2:   # LOW: primary only
                targets = [self._senders[self._order[0]]]
            else:
                targets = [self._senders[d] for d in self._order]
        ok = False
        for s in targets:
            ok = s.send(msg_type, payload) or ok
        return ok

    def queue_depth(self) -> int:
        with self._lock:
            return max((s.queue_depth()
                        for s in self._senders.values()), default=0)

    def buffered_bytes(self) -> int:
        with self._lock:
            return sum(s.buffered_bytes() for s in self._senders.values())

    def peek(self, n: int = 8) -> list:
        with self._lock:
            primary = self._senders.get(self._order[0]) \
                if self._order else None
        return primary.peek(n) if primary is not None else []

    def flush_and_stop(self, timeout: float = 5.0) -> None:
        with self._lock:
            senders = list(self._senders.values())
        threads = [threading.Thread(
            target=s.flush_and_stop, kwargs={"timeout": timeout},
            daemon=True) for s in senders]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout + 2.0)

    # -- rebalance -----------------------------------------------------------

    def set_destinations(self, addrs: list) -> None:
        """Adopt a new owner list (ring order, primary first). Senders
        for retained destinations keep running untouched — their
        windows, spools and seq spaces survive the rebalance — so no
        spooled or un-acked frame is dropped on an epoch bump."""
        from deepflow_tpu.agent.config import _parse_addr
        parsed = [_parse_addr(a) if isinstance(a, str) else tuple(a)
                  for a in addrs][:self.replication]
        with self._lock:
            if parsed == self._order or not parsed:
                return
            removed = [d for d in self._order if d not in parsed]
            added = [d for d in parsed if d not in self._senders]
            harvested: list[tuple] = []
            for dest in removed:
                s = self._senders.pop(dest)
                harvested.extend(s.drain_unsent())
            for dest in added:
                self._add_dest(dest)
            self._order = parsed
            new_targets = [self._senders[d] for d in added]
            self.stats["rebalances"] += 1
        # re-ship a lost owner's outstanding frames to the NEW owners
        # only: retained destinations already hold their own copies, and
        # a second copy there would be a same-shard duplicate row (each
        # boot's seq space is fresh, so the server-side dedup window
        # cannot catch it)
        if harvested and new_targets:
            for mt, payload in harvested:
                for s in new_targets:
                    s.send(mt, payload)
            self.stats["reshipped"] += len(harvested)

    # -- diagnostics ---------------------------------------------------------

    def stat_totals(self) -> dict:
        """Summed per-destination UniformSender stats (diagnostics)."""
        out: dict = {}
        with self._lock:
            senders = list(self._senders.values())
        for s in senders:
            for k, v in s.stats.items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0) + v
        return out

    def per_destination(self) -> dict:
        with self._lock:
            return {f"{h}:{p}": dict(s.stats)
                    for (h, p), s in self._senders.items()}
