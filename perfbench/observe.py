"""What the benchmark records inside the profiled job, around the
program's own calls, without changing what they do:

- every span batch the agent ships (`Agent.send_tpu_spans`), in the
  order and with the time it was handed to the sender: the rows the
  dashboard answers are checked against;
- for each capture while `keep` is set, the XSpace bytes the probe
  parsed and the spans it made of them: the parser's check;
- the probe's per-cycle `stop_ms` / `parse_ms` (each holds the last
  cycle only, so a thread samples them as captures complete);
- the CPU time of the agent's `df-*` threads, from /proc.
"""

from __future__ import annotations

import os
import threading
import time

_CLK_TCK = os.sysconf("SC_CLK_TCK")


def agent_threads_cpu_s(prefix: str = "df-") -> float:
    """CPU seconds used so far by this process's threads named df-*."""
    total = 0
    for t in threading.enumerate():
        if not t.name.startswith(prefix) or not t.native_id:
            continue
        try:
            with open(f"/proc/self/task/{t.native_id}/stat") as f:
                parts = f.read().rsplit(") ", 1)[1].split()
        except OSError:
            continue
        total += int(parts[11]) + int(parts[12])
    return total / _CLK_TCK


class Recorder:
    def __init__(self, train_module: str) -> None:
        from deepflow_tpu.agent import agent as agent_mod
        from deepflow_tpu.tpuprobe import sources
        self.agent = agent_mod.current()
        if self.agent is None or self.agent.tpuprobe is None:
            raise RuntimeError("no in-process agent with a TpuProbe: run "
                               "the job under deepflow-run")
        self.train_module = train_module
        self.batches: list = []          # (ns handed over, TpuSpanBatch)
        self.train_windows = 0           # shipped batches with step ops
        self.captures: list = []         # (xspace bytes, spans, start ns)
        self.keep = False
        self._last_xspace = None
        self._lock = threading.Lock()

        send = self.agent.send_tpu_spans

        # handed over under the lock, so the recorded order is the order
        # in which the sender gets the batches (the span and HBM threads
        # both send)
        def send_tpu_spans(batch):
            with self._lock:
                self.batches.append((time.time_ns(), batch))
                if any(s.hlo_op and s.hlo_module == train_module
                       for s in batch.spans):
                    self.train_windows += 1
                return send(batch)

        self.agent.send_tpu_spans = send_tpu_spans
        parse, extract = sources.parse_xspace, sources.extract_device_spans

        def parse_xspace(data):
            self._last_xspace = data
            return parse(data)

        def extract_device_spans(planes, capture_start_ns=0, stats=None):
            spans = extract(planes, capture_start_ns=capture_start_ns,
                            stats=stats)
            if self.keep:
                self.captures.append((self._last_xspace, list(spans),
                                      capture_start_ns))
            return spans

        sources.parse_xspace = parse_xspace
        sources.extract_device_spans = extract_device_spans
        self._samples: list = []
        self._sampler: threading.Thread | None = None
        self._stop = threading.Event()

    def xplane_source(self):
        from deepflow_tpu.tpuprobe.sources import XPlaneSource
        probe = self.agent.tpuprobe
        if probe is None:
            return None
        return next((s for s in probe.sources
                     if isinstance(s, XPlaneSource)), None)

    def start_sampling(self, every_s: float = 0.05) -> None:
        """Read stop_ms / parse_ms once after each completed capture."""
        def run():
            seen = None
            while not self._stop.wait(every_s):
                src = self.xplane_source()
                if src is None:
                    continue
                n = src.stats["captures"]
                if seen is not None and n != seen:
                    self._samples.append({k: src.stats[k] for k in (
                        "stop_ms", "parse_ms", "sink_ms", "events")})
                seen = n

        self._sampler = threading.Thread(target=run, name="perfbench-sampler",
                                         daemon=True)
        self._sampler.start()

    def stop_sampling(self) -> list:
        self._stop.set()
        if self._sampler is not None:
            self._sampler.join()
        return self._samples

    def shipped_rows(self) -> dict:
        """The shipped spans and HBM samples, as columns."""
        from deepflow_tpu.proto import pb
        kind_name = {v: k.lower().replace("_", "-")
                     for k, v in pb.TpuSpanKind.items()}
        cols = ("time", "duration_ns", "device_id", "kind", "hlo_module",
                "hlo_category", "hlo_op", "run_id", "bytes_accessed",
                "collective")
        spans = {c: [] for c in cols}
        mcols = ("time", "device_id", "bytes_in_use", "peak_bytes_in_use",
                 "bytes_limit", "largest_free_block")
        mem = {c: [] for c in mcols}
        sent = []
        with self._lock:
            batches = list(self.batches)
        for t_ns, batch in batches:
            for s in batch.spans:
                spans["time"].append(s.start_ns)
                spans["duration_ns"].append(s.duration_ns)
                spans["device_id"].append(s.device_id)
                spans["kind"].append(kind_name.get(s.kind, "unknown"))
                spans["hlo_module"].append(s.hlo_module)
                spans["hlo_category"].append(s.hlo_category)
                spans["hlo_op"].append(s.hlo_op)
                spans["run_id"].append(s.run_id)
                spans["bytes_accessed"].append(s.bytes_accessed)
                spans["collective"].append(s.collective)
            for m in batch.memory:
                mem["time"].append(m.timestamp_ns)
                for c in mcols[1:]:
                    mem[c].append(getattr(m, c))
            sent.append([t_ns, len(batch.spans), len(batch.memory)])
        return {"spans": spans, "memory": mem, "sent": sent}
