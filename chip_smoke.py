#!/usr/bin/env python3
"""Chip smoke: the profiled-job path end to end on a TPU, through the
entry points a user calls.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded job on a 2x2 v5e host

1. build the native libraries from the tracked sources (make native:
   build, then --verify-abi);
2. start the server (python -m deepflow_tpu.server.server) on free ports
   with a throwaway data dir; it must never map libtpu;
3. run examples/train_with_observability.py under deepflow-run at the
   LlamaConfig.llama7b() widths, cut to 4 layers, batch 2 x seq 1024; the
   job trains until the agent's TpuProbe has shipped device spans from 2
   capture windows and prints one JSON report;
4. ask the server what it ingested: DF-SQL on profile.tpu_hlo_span,
   /v1/profile/TpuFlame, /v1/profile/TpuMemory, the controller's platform
   info (and /v1/profile/TpuCollectives with --chips 4);
5. one chip: the job held the xplane parser to jax.profiler.ProfileData
   on a trace of its own. Four chips: the sharded first loss against the
   same step on one device.

Any phase that does not hold ends the run with a non-zero exit and no
result line. The parent never imports JAX: the job is the only process
that holds the chip. The last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "smoke")
JOB_ARGS = ["--llama7b", "--layers", "4", "--batch", "2", "--seq", "1024",
            "--steps", "20"]
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port: int, path: str, body: dict | None = None) -> dict:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def sql(port: int, text: str) -> list[dict]:
    res = http(port, "/v1/query/", {"db": "profile", "sql": text})["result"]
    return [dict(zip(res["columns"], row)) for row in res["values"]]


def maps_libtpu(pid: int) -> bool:
    with open(f"/proc/{pid}/maps") as f:
        return "libtpu" in f.read()


def build_native() -> None:
    t0 = time.monotonic()
    subprocess.run(["make", "-B", "-j8", "native",
                    f"PYTHON={sys.executable}"],
                   cwd=REPO, check=True, stdout=subprocess.DEVNULL)
    say(f"native: built and ABI-verified ({time.monotonic() - t0:.1f} s)")


def start_server(data_dir: str) -> tuple[subprocess.Popen, dict]:
    ports = {"ingest": free_port(), "query": free_port(),
             "sync": free_port()}
    log = open(os.path.join(OUT, "server.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "deepflow_tpu.server.server",
         "--host", "127.0.0.1", "--ingest-port", str(ports["ingest"]),
         "--query-port", str(ports["query"]),
         "--sync-port", str(ports["sync"]), "--data-dir", data_dir],
        cwd=REPO, stdout=log, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 60
    while True:
        check(proc.poll() is None, f"server exited with {proc.returncode}")
        try:
            http(ports["query"], "/v1/health")
            break
        except OSError:
            check(time.monotonic() < deadline, "server not up in 60 s")
            time.sleep(0.5)
    check(not maps_libtpu(proc.pid), "the server mapped libtpu")
    say(f"server: up on {ports}, libtpu not mapped")
    return proc, ports


def cache_entries(path: str) -> int:
    return sum(len(files) for _, _, files in os.walk(path))


def run_job(ports: dict, shard: bool) -> dict:
    from deepflow_tpu.utils.jaxcache import DEFAULT_DIR, cache_dir
    cdir = cache_dir()
    before = cache_entries(cdir)
    in_checkout = cache_entries(DEFAULT_DIR)
    cmd = [sys.executable, "-m", "deepflow_tpu.cli.runner",
           "--service", "chip-smoke",
           "--server", f"127.0.0.1:{ports['ingest']}",
           "--controller", f"127.0.0.1:{ports['sync']}",
           "examples/train_with_observability.py", *JOB_ARGS,
           "--smoke-dir", OUT] + (["--shard"] if shard else [])
    t0 = time.monotonic()
    with open(os.path.join(OUT, "job.stderr"), "w") as err:
        proc = subprocess.run(cmd, cwd=REPO, stdout=subprocess.PIPE,
                              stderr=err, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith('{"job_report"')]
    check(bool(lines), f"job exited {proc.returncode} without a report; "
                       f"see {OUT}/job.stderr")
    report = json.loads(lines[-1])["job_report"]
    say(f"job: exit {proc.returncode} after {time.monotonic() - t0:.1f} s")
    say("job device: " + json.dumps(report["device"]))
    for key in ("config", "mesh", "collective_ops_in_hlo", "compile_s",
                "steps", "median_step_ms",
                "first_loss", "last_loss", "ln_vocab",
                "state_bytes_per_device", "probe", "parser_check",
                "sharded_vs_single"):
        if key in report:
            say(f"job {key}: {json.dumps(report[key])}")
    say(f"compile cache: {cdir}, {cache_entries(cdir) - before} new "
        f"entries ({before} before)")
    if cdir != DEFAULT_DIR:
        say(f"compile cache: {cache_entries(DEFAULT_DIR) - in_checkout} new "
            f"entries in the unused {DEFAULT_DIR}")
    check(proc.returncode == 0 and not report["failures"],
          f"job failed: {report['failures']}")
    return report


def check_server(ports: dict, report: dict, shard: bool) -> None:
    qp = ports["query"]
    n_dev = report["device"]["count"] if shard else 1
    ops = "hlo_op != ''"
    (totals,) = sql(qp, "SELECT Count(*) AS n, Count(DISTINCT hlo_op) AS "
                        f"ops FROM tpu_hlo_span WHERE {ops}")
    say(f"DF-SQL tpu_hlo_span: {totals['n']} op rows, "
        f"{totals['ops']} distinct hlo_op")
    check(totals["ops"] > 1, "fewer than 2 distinct hlo_op")
    top = sql(qp, "SELECT hlo_op, Sum(duration_ns) AS d FROM tpu_hlo_span "
                  f"WHERE {ops} GROUP BY hlo_op ORDER BY d DESC LIMIT 10")
    say("DF-SQL top 10 ops by Sum(duration_ns): " + json.dumps(
        [[r["hlo_op"], r["d"]] for r in top]))
    per_dev = sql(qp, "SELECT device_id, Count(*) AS n FROM tpu_hlo_span "
                      f"WHERE {ops} GROUP BY device_id")
    devs = {int(r["device_id"]): r["n"] for r in per_dev}
    say(f"DF-SQL op rows per device_id: {json.dumps(devs)}")
    check(set(devs) == set(range(n_dev)),
          f"device_id {sorted(devs)} != {list(range(n_dev))}")

    flame = http(qp, "/v1/profile/TpuFlame", {})["result"]
    say(f"TpuFlame: root total_value {flame['total_value']} ns, "
        f"{len(flame['children'])} modules")
    check(flame["total_value"] > 0 and bool(flame["children"]),
          "TpuFlame tree is empty")

    mem = http(qp, "/v1/profile/TpuMemory", {})["result"]["devices"]
    say("TpuMemory: " + json.dumps(
        [{k: d[k] for k in ("device_id", "peak_bytes_in_use",
                            "bytes_limit")} for d in mem]))
    check(bool(mem) and all(d["bytes_limit"] > 0 for d in mem),
          "no HBM samples with bytes_limit > 0")
    peak = max(d["peak_bytes_in_use"] for d in mem)
    check(peak > report["state_bytes_per_device"],
          f"HBM peak {peak} not above the training state "
          f"{report['state_bytes_per_device']}")

    agents = http(qp, "/v1/agents")["agents"]
    kinds = sorted({d["device_kind"] for a in agents
                    for d in a["devices"]})
    say(f"controller platform device_kind: {kinds}")
    check(kinds == [report["device"]["kind"]],
          f"controller device_kind {kinds} != {report['device']['kind']}")

    if shard:
        check_collectives(qp, report, n_dev)


def check_collectives(qp: int, report: dict, n_dev: int) -> None:
    """Groups of n_dev participants; per step, as many groups as the
    compiled step has collective ops. Windows open and close mid-step,
    so edge groups and edge steps may come up short: the bulk decides,
    and nothing may exceed what the compiled step holds."""
    groups = http(qp, "/v1/profile/TpuCollectives", {})["result"]
    expected = len(report["collective_ops_in_hlo"])
    parts = collections.Counter(g["n_participants"] for g in groups)
    whole = collections.defaultdict(list)
    for g in groups:
        whole[g["run_id"]].append(g)
    per_run = collections.Counter(
        sum(g["collective"] in COLLECTIVES for g in gs)
        for gs in whole.values()
        if all(g["n_participants"] == n_dev for g in gs))
    say(f"TpuCollectives: {len(groups)} groups, participants "
        f"{dict(parts)}; groups per step {dict(per_run)}; compiled step "
        f"has {expected} collective ops")
    check(bool(groups) and parts.most_common(1)[0][0] == n_dev
          and max(parts) == n_dev,
          f"collective groups are not of {n_dev} participants")
    check(bool(per_run) and per_run.most_common(1)[0][0] == expected
          and max(per_run) == expected,
          f"groups per step {dict(per_run)} != {expected}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()
    if not os.path.isdir(os.path.join(REPO, "deepflow_tpu")):
        print("chip_smoke: no deepflow_tpu package next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    os.makedirs(OUT, exist_ok=True)
    shard = args.chips == 4
    data_dir = tempfile.mkdtemp(prefix="chip-smoke-")
    server = None
    try:
        build_native()
        server, ports = start_server(data_dir)
        report = run_job(ports, shard)
        check(report["device"]["count"] == args.chips,
              f"{report['device']['count']} devices, expected {args.chips}")
        check_server(ports, report, shard)
        check(not maps_libtpu(server.pid), "the server mapped libtpu")
        say("server: never mapped libtpu")
    except (SmokeFailure, subprocess.SubprocessError, OSError,
            KeyError, ValueError) as e:
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    finally:
        if server is not None:
            server.terminate()
            try:
                server.wait(timeout=20)
            except subprocess.TimeoutExpired:
                server.kill()
                server.wait()
        shutil.rmtree(data_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
