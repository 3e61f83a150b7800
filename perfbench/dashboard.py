"""The dashboard users keep open on a profiled job: an open loop of panel
queries against the server's HTTP API, and the live-tail poll.

A traffic file names the panels, their rate and the tail's interval.
Each panel is a module perfbench/panels/<name>.py with

    request(t0_ns, t1_ns) -> (path, body)   the query over [t0, t1)
    expected(rows, t0_ns, t1_ns) -> answer  a plain evaluator over the
                                            rows the probe shipped
    answer(response) -> answer              the server's, in that form
    agrees(answer, expected) -> bool        optional; equality otherwise

Every query is timed from when it was due, so a stalled server delays
the queries behind it too; `lateness` says how late the sender was.

Two checks: `check` asks each panel once more after ingestion has ended;
`check_timed` judges a sample of the window's own answers, drawn from
the seed, against what the server can have held when each was asked.
"""

from __future__ import annotations

import importlib.util
import json
import operator
import os
import random
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
TIMEOUT_S = 30.0
SAMPLED = 12        # timed panel answers of a window that are checked


def load_panel(name: str):
    path = os.path.join(HERE, "panels", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"perfbench_panel_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def http(port: int, path: str, body: dict | None = None,
         timeout: float = TIMEOUT_S) -> dict:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class Dashboard:
    """Open-loop panel queries from `start_ns` at `rate_qps`, round robin
    over the panels, and the live tail every `tail_interval_s`, sent by
    `workers` threads. Panel queries stop at `stop_panels()`; the tail
    runs on until `stop()`. Of the panel queries due in the first
    `seconds`, SAMPLED drawn from `seed` keep their responses."""

    def __init__(self, port: int, mix: dict, job_t0_ns: int, seed: int,
                 seconds: float) -> None:
        self.port = port
        self.panels = [(n, load_panel(n)) for n in mix["panels"]]
        self.rate = float(mix["rate_qps"])
        self.tail = load_panel(mix["tail"]["panel"])
        self.tail_every_s = float(mix["tail"]["interval_s"])
        self.workers = int(mix["workers"])
        self.job_t0_ns = job_t0_ns
        n_due = int(self.rate * seconds)
        self.sampled = set(random.Random(seed).sample(
            range(n_due), min(SAMPLED, n_due)))
        self.kept: dict = {}            # sampled index -> record, response
        self.records: list = []         # one per query sent
        self.tail_seen: dict = {}       # key -> ns of the first poll
        self._lock = threading.Lock()
        self._panels_end_ns = None
        self._stop = threading.Event()
        self._threads: list = []
        self._next = 0
        self._next_tail = 0

    def start(self, start_ns: int) -> None:
        self.start_ns = start_ns
        for i in range(self.workers):
            t = threading.Thread(target=self._work, name=f"dashboard-{i}",
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def stop_panels(self, end_ns: int) -> None:
        with self._lock:
            self._panels_end_ns = end_ns

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join()

    def _take(self):
        """The next query due, panel or tail, with its due time and, for
        a panel, its index."""
        with self._lock:
            due_p = self.start_ns + int(self._next * 1e9 / self.rate)
            due_t = self.start_ns + int(self._next_tail
                                        * self.tail_every_s * 1e9)
            end = self._panels_end_ns
            if end is None or due_p < end:
                if due_p <= due_t:
                    i = self._next
                    name, mod = self.panels[i % len(self.panels)]
                    self._next += 1
                    return name, mod, due_p, i
            self._next_tail += 1
            return "live_tail", self.tail, due_t, None

    def _work(self) -> None:
        while not self._stop.is_set():
            name, mod, due, index = self._take()
            wait = (due - time.time_ns()) / 1e9
            if wait > 0 and self._stop.wait(wait):
                return
            path, body = mod.request(self.job_t0_ns, due)
            sent = time.time_ns()
            ok, resp = True, None
            try:
                resp = http(self.port, path, body)
            except Exception as e:     # a failed query counts as failed
                ok, resp = False, repr(e)
            done = time.time_ns()
            if ok and name == "live_tail":
                with self._lock:
                    for key in mod.keys(resp):
                        self.tail_seen.setdefault(key, done)
            rec = {"panel": name, "due": due, "sent": sent, "done": done,
                   "ok": ok, "error": None if ok else resp}
            with self._lock:
                self.records.append(rec)
                if index in self.sampled:
                    self.kept[index] = (rec, mod, resp if ok else None)

    def check(self, rows: dict, t1_ns: int) -> dict:
        """Each panel's and the tail's answer over the job's whole range,
        once ingestion has ended, against the plain evaluator: {panel:
        None where they agree, else both, cut short}."""
        off = {}
        for name, mod in self.panels + [("live_tail", self.tail)]:
            path, body = mod.request(self.job_t0_ns, t1_ns)
            got = mod.answer(http(self.port, path, body))
            want = mod.expected(rows, self.job_t0_ns, t1_ns)
            agrees = getattr(mod, "agrees", operator.eq)
            off[name] = None if agrees(got, want) else {
                "server": repr(got)[:1500], "evaluator": repr(want)[:1500]}
        return off

    def check_timed(self, rows: dict) -> list:
        """The sampled answers of the window, each against the plain
        evaluator over every state the server can have held while it
        answered. The agent hands the server its batches one at a time,
        and the server writes each whole, in that order (one connection,
        one decoder), so what it holds is the first k batches: at least
        up to the last batch whose train-step launch a tail poll had
        returned before the query was sent, at most the batches handed
        over before the answer came. A batch whose rows alone evaluate as
        no rows do changes no answer (each evaluator filters rows, then
        aggregates), so only the states it ends are tried. Returns one
        entry per sampled answer that agrees with none of them or never
        came."""
        ends = batch_ends(rows)
        seen = self._batches_seen(rows, ends)
        off = []
        for index in sorted(self.sampled):
            rec, mod, resp = self.kept.get(index, (None, None, None))
            if resp is None:
                off.append({"index": index, "answer": "none",
                            "error": rec and rec["error"]})
                continue
            got = mod.answer(resp)
            agrees = getattr(mod, "agrees", operator.eq)
            lo = max((b + 1 for b, t in enumerate(seen)
                      if t is not None and t < rec["sent"]), default=0)
            hi = sum(t < rec["done"] for t, _, _ in ends)

            def evaluate(part):
                return mod.expected(part, self.job_t0_ns, rec["due"])

            silent = evaluate(batches(rows, ends, 0, 0))
            states = [k for k in range(hi, lo, -1)
                      if evaluate(batches(rows, ends, k - 1, k)) != silent]
            if not any(agrees(got, evaluate(batches(rows, ends, 0, k)))
                       for k in states + [lo]):
                off.append({"index": index, "panel": rec["panel"],
                            "batches": [lo, hi], "server": repr(got)[:1500]})
        return off

    def _batches_seen(self, rows: dict, ends: list) -> list:
        """For each shipped batch, when a tail poll first returned one of
        its train-step launches (None where none did)."""
        seen = []
        for b in range(len(ends)):
            times = [self.tail_seen[e[0]] for e in self.tail.expected(
                batches(rows, ends, b, b + 1), 0, 2**63)
                if e[0] in self.tail_seen]
            seen.append(min(times, default=None))
        return seen


def batch_ends(rows: dict) -> list:
    """(ns handed over, span rows, memory rows) after each shipped batch."""
    out, n_spans, n_mem = [], 0, 0
    for t_ns, spans, mem in rows["sent"]:
        n_spans, n_mem = n_spans + spans, n_mem + mem
        out.append((t_ns, n_spans, n_mem))
    return out


def batches(rows: dict, ends: list, i: int, j: int) -> dict:
    """The rows of shipped batches i to j - 1."""
    s0, m0 = ends[i - 1][1:] if i else (0, 0)
    s1, m1 = ends[j - 1][1:] if j else (0, 0)
    return {"spans": {c: v[s0:s1] for c, v in rows["spans"].items()},
            "memory": {c: v[m0:m1] for c, v in rows["memory"].items()}}
