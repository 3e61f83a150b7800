"""Resource guard: the agent polices its own CPU/memory footprint.

Reference analog: agent/src/utils/guard.rs (controller-set cpu/mem/log
limits; throttle or restart on breach) and the exception bitmap reported in
every Sync. Here: breach pauses the profilers (the compressible load),
recovery resumes them; state surfaces through Sync as DEGRADED.
"""

from __future__ import annotations

import logging
import os
import threading
import time

log = logging.getLogger("df.guard")

_CLK_TCK = os.sysconf("SC_CLK_TCK")
_PAGE = os.sysconf("SC_PAGE_SIZE")

EXC_CPU_LIMIT = 1 << 0
EXC_MEM_LIMIT = 1 << 1


def read_self_usage() -> tuple[float, int]:
    """(cpu_seconds_total, rss_bytes) from /proc/self."""
    with open("/proc/self/stat") as f:
        parts = f.read().rsplit(") ", 1)[1].split()
    utime, stime = int(parts[11]), int(parts[12])
    cpu_s = (utime + stime) / _CLK_TCK
    with open("/proc/self/statm") as f:
        rss_pages = int(f.read().split()[1])
    return cpu_s, rss_pages * _PAGE


def read_agent_threads_cpu_s(prefix: str = "df-") -> float:
    """CPU seconds of this process's agent threads (named df-*): what an
    agent attached INSIDE a job costs, without the job's own threads."""
    import threading
    total = 0
    for t in threading.enumerate():
        if not t.name.startswith(prefix) or not t.native_id:
            continue
        try:
            with open(f"/proc/self/task/{t.native_id}/stat") as f:
                parts = f.read().rsplit(") ", 1)[1].split()
        except OSError:
            continue  # the thread exited since enumerate()
        total += int(parts[11]) + int(parts[12])
    return total / _CLK_TCK


class Guard:
    def __init__(self, agent, max_cpu_pct: float = 50.0,
                 max_mem_mb: float = 2048.0,
                 check_interval_s: float = 10.0,
                 recover_ratio: float = 0.8,
                 in_process: bool = False) -> None:
        self.agent = agent
        # in_process: the agent shares its process with the job it
        # profiles (deepflow-run / attach), so the process's CPU and RSS
        # are the job's. Police the agent's own threads' CPU, and hold
        # max_mem_mb to the bytes the agent buffers (its sender's queue,
        # ack window and resends): no per-thread RSS exists.
        self.in_process = in_process
        self.max_cpu_pct = max_cpu_pct
        self.max_mem_mb = max_mem_mb
        self.check_interval_s = check_interval_s
        self.recover_ratio = recover_ratio
        self.exception_bitmap = 0
        self.degraded = False
        self.cpu_pct = 0.0
        self.rss_mb = 0.0
        self.stats = {"checks": 0, "degrades": 0, "recoveries": 0,
                      "peak_cpu_pct": 0.0}
        self._last: tuple[float, float] | None = None  # (mono, cpu_s)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "Guard":
        self._thread = threading.Thread(
            target=self._run, name="df-guard", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2.0)

    def _run(self) -> None:
        while not self._stop.wait(self.check_interval_s):
            try:
                self.check()
            except Exception:
                log.exception("guard check failed")

    def check(self, now: float | None = None) -> None:
        self.stats["checks"] += 1
        cpu_s, rss = read_self_usage()
        if self.in_process:
            cpu_s = read_agent_threads_cpu_s()
            rss = self.agent.sender.buffered_bytes()
        mono = now if now is not None else time.monotonic()
        if self._last is not None:
            dt = mono - self._last[0]
            if dt > 0:
                # agent threads that exit take their CPU time with them
                self.cpu_pct = max(0.0, 100.0 * (cpu_s - self._last[1]) / dt)
                self.stats["peak_cpu_pct"] = max(
                    self.stats["peak_cpu_pct"], self.cpu_pct)
        self._last = (mono, cpu_s)
        self.rss_mb = rss / (1024 * 1024)
        self._evaluate()

    def _evaluate(self) -> None:
        over_cpu = self.cpu_pct > self.max_cpu_pct
        over_mem = self.rss_mb > self.max_mem_mb
        self.exception_bitmap = ((EXC_CPU_LIMIT if over_cpu else 0)
                                 | (EXC_MEM_LIMIT if over_mem else 0))
        if not self.degraded and (over_cpu or over_mem):
            self.degraded = True
            self.stats["degrades"] += 1
            log.warning("resource limit hit (cpu %.1f%% rss %.0fMB): "
                        "pausing profilers", self.cpu_pct, self.rss_mb)
            self.agent.pause_profilers()
            if over_mem:
                # best-effort reclaim: CPython rarely returns RSS to the OS,
                # so free what we can and judge memory recovery against the
                # hard limit, not the hysteresis bar (see below)
                import gc
                gc.collect()
        elif self.degraded and \
                self.cpu_pct < self.max_cpu_pct * self.recover_ratio and \
                self.rss_mb <= self.max_mem_mb:
            self.degraded = False
            self.stats["recoveries"] += 1
            log.info("resource usage recovered: resuming profilers")
            # degraded is already False: resume_profilers' guard check passes
            self.agent.resume_profilers()
