"""The profiled training job of one benchmark run. run.py starts it as a
user would, under deepflow-run, so the in-process agent's TpuProbe
watches it with its defaults:

    python -m deepflow_tpu.cli.runner ... perfbench/job.py --spec RUN.json

Set-up: the weights and the token feed from the seed, on the device; the
program's train step compiled once; its first three steps read for the
correctness check; then steps until the probe has shipped three windows
that hold train-step spans (its first windows calibrate its cadence). The
window: steps for --seconds, each ending in block_until_ready. After it:
the peak HBM, the device trace of a few steps (traced runs), the agent
detached (its sender flushed), the shipped rows written out, the parser
held to ProfileData on captures drawn from the seed, and, with the
training state freed, the plain reference.

It talks to run.py in lines "PERFBENCH {json}" on stdout and leaves its
results in RUN_DIR/job.json.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import random
import sys
import time

TRAIN_MODULE = "jit_train_step"
WARM_DEADLINE_S = 240
# the probe calibrates its cadence from its first windows; after one
# train-step window coverage came out bimodal between runs, after three
# it is steady
WARM_TRAIN_WINDOWS = 3
TRACE_STEPS = 5


def emit(event: str, **kw) -> None:
    print("PERFBENCH " + json.dumps({"event": event, **kw}), flush=True)


def parser_check(captures: list, seed: int, n: int = 2) -> dict:
    """The spans the probe made of a capture against ProfileData on the
    same XSpace bytes: per device and op, equal event counts, and the
    summed device time per event within the 1 ns storage quantum."""
    import re

    from jax.profiler import ProfileData
    picked = random.Random(seed).sample(range(len(captures)),
                                        min(n, len(captures)))
    events_off, ns_gap, ops, events = 0, 0.0, 0, 0
    for i in sorted(picked):
        xspace, spans, _ = captures[i]
        ref: dict = {}
        for plane in ProfileData.from_serialized_xspace(xspace).planes:
            m = re.match(r"^/device:TPU:(\d+)$", plane.name)
            if not m:
                continue
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    op = re.match(r"%?([^\s=]+)", ev.name).group(1)
                    ps = dict(ev.stats).get("device_duration_ps")
                    c = ref.setdefault((dev, op), [0, 0.0])
                    c[0] += 1
                    c[1] += ps / 1000 if ps is not None else ev.duration_ns
        ours: dict = {}
        for s in spans:
            if s.hlo_op:
                c = ours.setdefault((s.device_id, s.hlo_op), [0, 0])
                c[0] += 1
                c[1] += s.duration_ns
        for key in set(ref) | set(ours):
            n_ref, t_ref = ref.get(key, (0, 0.0))
            n_our, t_our = ours.get(key, (0, 0))
            events_off += n_ref != n_our
            ns_gap = max(ns_gap, abs(t_our - t_ref) / max(n_ref, 1))
        ops += len(ref)
        events += sum(c[0] for c in ref.values())
    return {"captures_checked": len(picked), "ops": ops, "events": events,
            "span_events_off": events_off, "span_ns_gap": ns_gap}


def device_trace(trainer, out_dir: str, n_devices: int) -> dict:
    """A few steps under the benchmark's own profiler trace, started
    while the probe is between windows (the two exclude each other)."""
    import jax

    from perfbench.trace_reduce import reduce_file
    deadline = time.monotonic() + 60
    while True:
        try:
            jax.profiler.start_trace(out_dir)
            break
        except RuntimeError:
            if time.monotonic() > deadline:
                raise
            trainer.step().block_until_ready()
    try:
        for _ in range(TRACE_STEPS):
            with jax.profiler.TraceAnnotation("perfbench.step"):
                loss = trainer.step()
            with jax.profiler.TraceAnnotation("perfbench.block"):
                loss.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(out_dir, "plugins/profile/*/*.xplane.pb")),
               key=os.path.getmtime)
    return reduce_file(path, n_devices)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--spec", required=True)
    with open(parser.parse_args().spec) as f:
        spec = json.load(f)
    from perfbench import model
    from perfbench.observe import Recorder, agent_threads_cpu_s
    rec = Recorder(TRAIN_MODULE)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf = model.load_json(spec["config_file"])
    chips = spec["chips"]
    devices = jax.devices()
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": min(len(devices), chips)}
    if dev0.platform != "tpu" and not spec.get("allow_cpu"):
        emit("error", error=f"no TPU: JAX found {dev0.platform!r}",
             device=device)
        return 3
    if len(devices) < chips:
        emit("error", error=f"{len(devices)} devices, the cell asks for "
             f"{chips}", device=device)
        return 3
    devices = devices[:chips]
    from perfbench.training import Trainer
    job_t0_ns = time.time_ns()
    phases = [("backend", time.monotonic())]
    trainer = Trainer(conf, spec["seed"], devices)
    phases.append(("weights_and_compile", time.monotonic()))
    program = trainer.first_steps()
    phases.append(("first_steps", time.monotonic()))
    deadline = time.monotonic() + WARM_DEADLINE_S
    src = rec.xplane_source()
    # the CPU rehearsal ships no device spans: it does not wait for them
    while not spec.get("allow_cpu") and (
            rec.train_windows < WARM_TRAIN_WINDOWS
            or not (src and src.stats["est_step_ms"])):
        trainer.step().block_until_ready()
        src = rec.xplane_source()
        if time.monotonic() > deadline:
            emit("error", error="the probe shipped no train-step window "
                 f"in {WARM_DEADLINE_S} s", device=device)
            return 4
    warm_steps = trainer.steps
    phases.append(("probe_calibration", time.monotonic()))

    rec.keep = True
    rec.start_sampling()
    cpu0 = agent_threads_cpu_s()
    w0_ns, w0 = time.time_ns(), time.perf_counter()
    emit("ready", job_t0_ns=job_t0_ns, w0_ns=w0_ns)
    n = 0
    while True:
        loss = trainer.step()
        loss.block_until_ready()
        n += 1
        if time.perf_counter() - w0 >= spec["seconds"]:
            break
    w1, w1_ns = time.perf_counter(), time.time_ns()
    cpu1 = agent_threads_cpu_s()
    rec.keep = False
    samples = rec.stop_sampling()
    emit("window_end", w0_ns=w0_ns, w1_ns=w1_ns, steps=n)
    last_loss = float(loss)
    mem_peak = max((d.memory_stats()["peak_bytes_in_use"] for d in devices
                    if d.memory_stats()), default=0)
    result = {
        "device": device, "memory_peak_bytes": mem_peak,
        "w0_ns": w0_ns, "w1_ns": w1_ns, "window_s": w1 - w0,
        "steps": n, "warm_steps": warm_steps, "last_loss": last_loss,
        "setup_phases_s": {name: t1 - t0 for (_, t0), (name, t1)
                           in zip(phases, phases[1:])},
        "agent_cpu_pct": 100.0 * (cpu1 - cpu0) / (w1 - w0),
        "probe_samples": samples,
        "flops_per_step": model.flops_per_step(model.widths(conf))}
    if spec["trace"]:
        result["trace"] = device_trace(
            trainer, os.path.join(spec["run_dir"], "trace"), chips)
    src = rec.xplane_source()
    result["probe"] = dict(src.stats) if src else {}
    result["probe"]["window_captures"] = len(samples)
    guard = rec.agent.guard
    result["guard"] = dict(guard.stats) if guard else {}
    from deepflow_tpu.agent import agent as agent_mod
    agent_mod.detach()
    with open(os.path.join(spec["run_dir"], "shipped.json"), "w") as f:
        json.dump(rec.shipped_rows(), f)
    emit("shipped")

    result["parser"] = parser_check(rec.captures, spec["seed"])
    if chips > 1:
        from perfbench import stitching
        result["stitching"] = stitching.check(rec.captures, TRAIN_MODULE)
    rec.captures.clear()
    trainer.free()
    del trainer
    gc.collect()
    from perfbench import reference
    t = time.monotonic()
    ref = reference.readings_on(conf, spec["seed"], devices)
    result["reference_s"] = time.monotonic() - t
    result["training"] = reference.gaps(ref, program)
    for k in ("loss", "update", "moved_share"):
        result["training"][k] = {"program": program[k], "reference": ref[k]}
    with open(os.path.join(spec["run_dir"], "job.json"), "w") as f:
        json.dump(result, f)
    emit("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
