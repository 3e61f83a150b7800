"""TpuProbe: the agent component that owns TPU event sources.

Reference analog: agent/src/ebpf_dispatcher.rs (EbpfCollector) — starts the
native tracers, receives callbacks, converts to wire messages, hands them to
the sender.
"""

from __future__ import annotations

import logging
import os
import sys
import threading

from deepflow_tpu.proto import pb
from deepflow_tpu.tpuprobe.events import TpuSpanEvent, batch_to_pb
from deepflow_tpu.tpuprobe.sources import (
    HooksSource, MemorySource, SimMemorySource, SimSource, XPlaneSource)
from deepflow_tpu.tpuprobe.stepmetrics import (
    StepAggregator, encode_step_payload)

log = logging.getLogger("df.tpuprobe")


class TpuProbe:
    def __init__(self, agent) -> None:
        self.agent = agent
        cfg = agent.config.tpuprobe
        self.cfg = cfg
        self.sources: list = []
        self._lock = threading.Lock()
        self.stats = {"spans_sent": 0, "batches": 0}
        telemetry = getattr(agent, "telemetry", None)
        if telemetry is None:
            from deepflow_tpu.telemetry import Telemetry
            telemetry = Telemetry("agent", enabled=False)
        self.telemetry = telemetry
        self._hop = telemetry.hop("tpuprobe")
        # per-step rollups ride the span sink; their frames get their own
        # hop so a steps-path loss never hides inside the span ledger
        self.stepagg: StepAggregator | None = None
        if getattr(cfg, "step_metrics", True):
            self._steps_hop = telemetry.hop("tpuprobe.steps")
            self.stepagg = StepAggregator(
                self._step_sink, topk=getattr(cfg, "step_topk", 5))

    def start(self) -> "TpuProbe":
        mode = self.cfg.source
        if mode in ("auto", "xplane"):
            # deepflow-run attaches before the job imports JAX: the
            # capture loop retries the hooks until they register
            hooks = HooksSource(self._sink).start()
            self.sources.append(XPlaneSource(
                self._sink,
                interval_s=self.cfg.trace_interval_s,
                duration_ms=self.cfg.trace_duration_ms,
                target_coverage=self.cfg.target_coverage,
                steps_per_capture=self.cfg.steps_per_capture,
                calibration=getattr(self.agent, "tpuprobe_calibration",
                                    None),
                telemetry=self.telemetry,
                on_cycle=hooks.start).start())
            self.sources.append(hooks)
            if self.cfg.memory_poll_s > 0:
                self.sources.append(MemorySource(
                    self._mem_sink,
                    poll_interval_s=self.cfg.memory_poll_s,
                    telemetry=self.telemetry).start())
        elif mode == "hooks":
            self.sources.append(HooksSource(self._sink).start())
        elif mode == "sim":
            src = SimSource(self._sink)
            self.sources.append(src)
            src.generate()
            SimMemorySource(self._mem_sink).generate()
            if self.stepagg:
                self.stepagg.flush()  # sim runs end here, not at stop()
        return self

    def stop(self) -> None:
        for s in self.sources:
            stop = getattr(s, "stop", None)
            if stop:
                stop()
            if isinstance(s, XPlaneSource):
                # a probe the guard paused resumes with this cadence
                self.agent.tpuprobe_calibration = s.calibration()
        if self.stepagg:
            self.stepagg.flush()  # ship the last (still-open) step

    def _sink(self, events: list[TpuSpanEvent]) -> None:
        if not events:
            return
        batch = batch_to_pb(
            events, pid=os.getpid(),
            process_name=self.agent.process_name)
        with self._lock:
            self.stats["spans_sent"] += len(events)
            self.stats["batches"] += 1
        self._hop.account(emitted=1, delivered=1)
        self.agent.send_tpu_spans(batch)
        if self.stepagg:
            self.stepagg.feed(events)

    def _step_sink(self, records: list[dict]) -> None:
        if not records:
            return
        payload = encode_step_payload(
            records, pid=os.getpid(),
            process_name=self.agent.process_name)
        with self._lock:
            self.stats["steps_sent"] = \
                self.stats.get("steps_sent", 0) + len(records)
        self._steps_hop.account(emitted=1)
        if self.agent.send_step_metrics(payload):
            self._steps_hop.account(delivered=1)
        else:
            self._steps_hop.account(dropped=1, reason="send_queue_full")

    def _mem_sink(self, samples: list[dict]) -> None:
        if not samples:
            return
        batch = pb.TpuSpanBatch()
        for s in samples:
            m = batch.memory.add(**s)
            m.pid = os.getpid()
            m.process_name = self.agent.process_name
        with self._lock:
            self.stats["mem_samples_sent"] = \
                self.stats.get("mem_samples_sent", 0) + len(samples)
        self._hop.account(emitted=1, delivered=1)
        self.agent.send_tpu_spans(batch)
