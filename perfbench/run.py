#!/usr/bin/env python3
"""One benchmark run of one cell of BENCHMARK.json:

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

It starts the server (python -m deepflow_tpu.server.server) in a process
of its own, pinned to CPUs apart from the job's, then the cell's
training job under deepflow-run (perfbench/job.py), which alone holds the
chips. Once the job is warm, the dashboard of the cell's traffic file
queries the server for --seconds while the job trains. After the window
it waits for the server to hold every row the probe shipped, checks the
answers, the parser and the training step, and prints one JSON line.

Cells, configurations, traffic mixes, panels and metrics are found by
name: perfbench/configs/<config>.json, perfbench/traffic/<traffic>.json,
perfbench/panels/<panel>.py, perfbench/metrics/<metric>.py and the
limits of the correctness check in perfbench/limits/<config>.json.

The parent never imports JAX. With no TPU, or fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import queue
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench import model  # noqa: E402
from perfbench.dashboard import Dashboard, http  # noqa: E402

READY_TIMEOUT_S = 1100      # the first run in a checkout compiles
INGEST_TIMEOUT_S = 60
END_TIMEOUT_S = 240


class RunError(Exception):
    pass


def load_metric(name: str):
    import importlib.util
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def split_cpus() -> dict:
    """The job's CPUs, the server's (a quarter), and one for the
    dashboard client (this process)."""
    cpus = sorted(os.sched_getaffinity(0))
    if len(cpus) < 4:
        return {"job": cpus, "server": cpus, "client": cpus}
    n_srv = math.ceil(len(cpus) / 4)
    return {"job": cpus[:-1 - n_srv], "server": cpus[-1 - n_srv:-1],
            "client": cpus[-1:]}


def popen_pinned(cmd, cpus, **kw) -> subprocess.Popen:
    return subprocess.Popen(
        cmd, cwd=ROOT, preexec_fn=lambda: os.sched_setaffinity(0, cpus),
        **kw)


def build_native() -> None:
    """The program's native libraries, built once per checkout."""
    r = subprocess.run(["make", "-s", "-C", "deepflow_tpu/native"], cwd=ROOT,
                       stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                       text=True)
    if r.returncode:
        raise RunError(f"native build failed: {r.stderr[-2000:]}")


def start_server(run_dir: str, cpus) -> tuple:
    ports = {k: free_port() for k in ("ingest", "query", "sync")}
    log = open(os.path.join(run_dir, "server.log"), "w")
    proc = popen_pinned(
        [sys.executable, "-m", "deepflow_tpu.server.server",
         "--host", "127.0.0.1", "--ingest-port", str(ports["ingest"]),
         "--query-port", str(ports["query"]),
         "--sync-port", str(ports["sync"]),
         "--data-dir", os.path.join(run_dir, "data")],
        cpus, stdout=log, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 60
    while True:
        if proc.poll() is not None:
            raise RunError(f"server exited with {proc.returncode}")
        try:
            http(ports["query"], "/v1/health", timeout=5)
            return proc, ports
        except OSError:
            if time.monotonic() > deadline:
                raise RunError("server not up in 60 s")
            time.sleep(0.2)


def stop(proc) -> None:
    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=20)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


class Job:
    """The job process and its stream of PERFBENCH events."""

    def __init__(self, proc) -> None:
        self.proc = proc
        self.events: queue.Queue = queue.Queue()
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            if line.startswith("PERFBENCH "):
                self.events.put(json.loads(line[len("PERFBENCH "):]))
        self.events.put({"event": "exit"})

    def wait(self, name: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                ev = self.events.get(timeout=max(
                    0.01, deadline - time.monotonic()))
            except queue.Empty:
                raise RunError(f"job: no {name!r} in {timeout:.0f} s")
            if ev["event"] == name:
                return ev
            if ev["event"] == "error":
                raise RunError(f"job: {ev['error']}")
            if ev["event"] == "exit":
                raise RunError(f"job exited before {name!r}")


def wait_ingested(port: int, rows: dict) -> float:
    """Seconds until the server holds every shipped span and sample."""
    want = (len(rows["spans"]["time"]), len(rows["memory"]["time"]))
    t0 = time.monotonic()
    while True:
        got = []
        for table in ("tpu_hlo_span", "tpu_memory"):
            res = http(port, "/v1/query/", {
                "db": "profile",
                "sql": f"SELECT Count(*) AS n FROM {table}"})["result"]
            got.append(int(res["values"][0][0]) if res["values"] else 0)
        if tuple(got) == want:
            return time.monotonic() - t0
        if time.monotonic() - t0 > INGEST_TIMEOUT_S:
            raise RunError(f"server holds {got} rows of the shipped {want}")
        time.sleep(0.2)


def run(bench: dict, workload: str, seed: int, seconds: int, trace: bool,
        allow_cpu: bool = False, limits: dict | None = None) -> dict:
    """One run; allow_cpu and limits are for the tests' rehearsal at a
    test size on the CPU."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    mix = model.load_json(os.path.join(HERE, "traffic",
                                       f"{cell['traffic']}.json"))
    if limits is None:
        limits = model.load_json(os.path.join(HERE, "limits",
                                              f"{cell['config']}.json"))
    if not os.path.isdir(os.path.join(ROOT, "deepflow_tpu")):
        raise RunError("no deepflow_tpu package in this checkout")
    build_native()
    cpus = split_cpus()
    os.sched_setaffinity(0, cpus["client"])
    run_dir = tempfile.mkdtemp(prefix="perfbench-")
    server = job = dash = err = None
    try:
        server, ports = start_server(run_dir, cpus["server"])
        spec = {"config_file": conf_entry["file"], "seed": seed,
                "seconds": seconds, "trace": trace, "run_dir": run_dir,
                "chips": cell["chips"], "allow_cpu": allow_cpu}
        with open(os.path.join(run_dir, "spec.json"), "w") as f:
            json.dump(spec, f)
        cache = os.path.join(HERE, ".cache", "jax")
        os.makedirs(cache, exist_ok=True)
        # the cache lives in the checkout; no size cap evicts the
        # job's programs between runs
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache,
                   JAX_COMPILATION_CACHE_MAX_SIZE="-1",
                   PYTHONPATH=os.pathsep.join(
                       p for p in (ROOT, os.environ.get("PYTHONPATH"))
                       if p))
        err = open(os.path.join(run_dir, "job.stderr"), "w")
        job = Job(popen_pinned(
            [sys.executable, "-m", "deepflow_tpu.cli.runner",
             "--service", f"perfbench-{workload}",
             "--server", f"127.0.0.1:{ports['ingest']}",
             "--controller", f"127.0.0.1:{ports['sync']}",
             "perfbench/job.py", "--spec", os.path.join(run_dir, "spec.json")],
            cpus["job"], env=env, stdout=subprocess.PIPE, stderr=err,
            text=True))
        try:
            ready = job.wait("ready", READY_TIMEOUT_S)
            setup_s = time.monotonic() - T_START
            dash = Dashboard(ports["query"], mix["dashboard"],
                             ready["job_t0_ns"], seed, seconds)
            dash.start(ready["w0_ns"])
            end = job.wait("window_end", seconds + 120)
            dash.stop_panels(end["w1_ns"])
            job.wait("shipped", 180)
            with open(os.path.join(run_dir, "shipped.json")) as f:
                rows = json.load(f)
            ingest_s = wait_ingested(ports["query"], rows)
            time.sleep(2 * dash.tail_every_s)     # a last poll of the tail
            dash.stop()
            far = end["w1_ns"] + 3600 * 10**9
            panels_off = dash.check(rows, far)
            timed_off = dash.check_timed(rows)
            job.wait("done", END_TIMEOUT_S)
            job.proc.wait(timeout=60)
        except RunError as e:
            err.flush()
            with open(os.path.join(run_dir, "job.stderr")) as f:
                tail = f.read()[-4000:]
            raise RunError(f"{e}\n--- job stderr ---\n{tail}")
        with open(os.path.join(run_dir, "job.json")) as f:
            jr = json.load(f)
        ctx = {"job": jr, "setup_s": setup_s, "chips": cell["chips"],
               "queries": [q for q in dash.records
                           if q["due"] < end["w1_ns"]],
               "steps_stored": stored_steps(dash, rows, end),
               "peaks": peak_row(jr["device"]["kind"], allow_cpu),
               "ingest_s": ingest_s}
        checks = correctness(jr, panels_off, timed_off,
                             ctx["steps_stored"], limits)
        for name, diff in panels_off.items():
            if diff is not None:
                print(f"panel {name} disagrees: {json.dumps(diff)}",
                      file=sys.stderr)
        for diff in timed_off:
            print(f"timed answer disagrees: {json.dumps(diff)}",
                  file=sys.stderr)
        return result(bench, cell, trace, ctx, checks)
    finally:
        if dash is not None:
            dash.stop()
        if job is not None:
            stop(job.proc)
        stop(server)
        if err is not None:
            err.close()
        shutil.rmtree(run_dir, ignore_errors=True)


def stored_steps(dash, rows: dict, end: dict) -> list:
    """Device 0's train-step launches whose device end lies in the
    window, with the time the live tail first returned each."""
    tail = dash.tail
    out = []
    for run_id, t, dur in tail.expected(rows, 0, 2**63):
        if end["w0_ns"] <= t + dur < end["w1_ns"]:
            out.append({"run_id": run_id, "end_ns": t + dur,
                        "first_seen_ns": dash.tail_seen.get(run_id)})
    return out


def peak_row(kind: str, allow_cpu: bool = False) -> dict | None:
    """The chip's peaks; an unknown kind is an error (the CPU of a
    rehearsal has none)."""
    peaks = model.load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in peaks and not allow_cpu:
        raise RunError(f"no peak for device kind {kind!r} in peaks.json")
    return peaks.get(kind)


def correctness(jr: dict, panels_off: dict, timed_off: list, steps: list,
                limits: dict) -> dict:
    """Each compared number beside its limit (perfbench/limits)."""
    tr, pa = jr["training"], jr["parser"]
    values = {
        "loss_gap": tr["loss_gap"], "grad_gap": tr["grad_gap"],
        "update_gap": tr["update_gap"],
        "span_events_off": pa["span_events_off"],
        "span_ns_gap": pa["span_ns_gap"],
        "captures_unchecked": 2 - pa["captures_checked"],
        "panels_off": sum(v is not None for v in panels_off.values()),
        "timed_panels_off": len(timed_off),
        "tail_missed": sum(s["first_seen_ns"] is None for s in steps),
    }
    if "stitching" in jr:
        values["coll_groups_off"] = jr["stitching"]["groups_off"]
    return judge(values, limits)


def judge(values: dict, limits: dict) -> dict:
    """The numbers that have a limit, each beside it."""
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()
            if k in limits}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def result(bench: dict, cell: dict, trace: bool, ctx: dict,
           checks: dict) -> dict:
    jr = ctx["job"]
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench[group]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    q = ctx["queries"]
    device = dict(jr["device"], memory_peak_bytes=jr["memory_peak_bytes"])
    out = {"correct": is_correct(checks),
           "attempted": len(q), "failed": sum(not r["ok"] for r in q),
           "metrics": metrics, "device": device}
    if trace and jr.get("trace"):
        device["busy_s"] = jr["trace"]["busy_s"]
        device["window_s"] = jr["trace"]["window_s"]
        out["breakdown"] = {"device_ops": jr["trace"]["device_ops"],
                            "idle_gaps": jr["trace"]["idle_gaps"]}
    late = sorted((r["sent"] - r["due"]) / 1e6 for r in q)
    out["dashboard"] = {"queries": len(q), "late_ms_p95": (
        late[math.ceil(0.95 * len(late)) - 1] if late else None),
        "late_ms_max": late[-1] if late else None,
        "ingest_wait_s": ctx["ingest_s"]}
    if "stitching" in jr:
        out["stitching"] = jr["stitching"]
    out["setup_phases_s"] = jr["setup_phases_s"]
    out["probe"] = {k: jr["probe"].get(k) for k in (
        "window_captures", "captures", "span_windows", "est_step_ms",
        "dead_ms", "stop_ms", "parse_ms", "contended", "errors")}
    # read, not compared: no control or fault reads 3x (10x) its readings
    out["not_compared"] = {"loss_gap": jr["training"]["loss_gap"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=int, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        out = run(bench, args.workload, args.seed, args.seconds,
                  bool(args.trace))
    except (RunError, OSError, KeyError, ValueError) as e:
        print(f"perfbench: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
