"""End-to-end demo: train a Llama-style model on TPU with deepflow-tpu
attached (BASELINE config 3 in miniature).

    # terminal 1
    python -m deepflow_tpu.server.server

    # terminal 2 — zero-code:
    python -m deepflow_tpu.cli.runner --service llama-train \
        examples/train_with_observability.py
    #   ...at the published Llama-2 7B widths, cut to what one v5e holds:
    python -m deepflow_tpu.cli.runner --service llama-train \
        examples/train_with_observability.py --llama7b --layers 4 \
        --batch 2 --seq 1024
    #   ...or run directly (this file attaches itself when asked):
    python examples/train_with_observability.py --attach

    # then
    python -m deepflow_tpu.cli.dfctl tpu-flame
    python -m deepflow_tpu.cli.dfctl flame --service llama-train
    python -m deepflow_tpu.cli.dfctl query \
        "SELECT hlo_op, Sum(duration_ns) AS d, Sum(flops) AS f \
         FROM tpu_hlo_span GROUP BY hlo_op ORDER BY d DESC LIMIT 10" \
        --db profile

--smoke-dir DIR is the mode chip_smoke.py drives under deepflow-run: it
requires a TPU, trains until the agent's probe has shipped device spans
from 2 capture windows, prints one JSON report line, then
checks either the xplane parser against jax.profiler.ProfileData on a
trace of its own (one chip) or the sharded loss against the same step on
one device (--shard).
"""

import argparse
import dataclasses
import glob
import json
import math
import os
import statistics
import time

import jax


def build_config(args):
    from deepflow_tpu.models.llama import LlamaConfig
    if args.llama7b:
        # published widths; only depth (and the token window) are cut
        return dataclasses.replace(
            LlamaConfig.llama7b(), n_layers=args.layers,
            max_seq=max(LlamaConfig.llama7b().max_seq, args.seq))
    return LlamaConfig(
        vocab=8192, d_model=args.d_model, n_layers=args.layers,
        n_heads=8, n_kv_heads=4, d_ff=int(args.d_model * 2.75),
        max_seq=args.seq * 2)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--attach", action="store_true",
                        help="attach the in-process agent directly")
    parser.add_argument("--server", default="127.0.0.1:20033")
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--llama7b", action="store_true",
                        help="LlamaConfig.llama7b() widths (d_model 4096, "
                             "d_ff 11008, 32/8 heads, vocab 32000)")
    parser.add_argument("--d-model", type=int, default=512)
    parser.add_argument("--layers", type=int, default=6)
    parser.add_argument("--seq", type=int, default=512)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--shard", action="store_true",
                        help="shard params over make_mesh(jax.devices())")
    parser.add_argument("--smoke-dir", default="",
                        help="chip-smoke mode; keeps its trace here")
    args = parser.parse_args()

    if args.attach:
        from deepflow_tpu.agent.agent import attach
        from deepflow_tpu.agent.config import TpuProbeConfig
        attach(app_service="llama-train", servers=[args.server],
               tpuprobe=TpuProbeConfig(enabled=True, source="xplane",
                                       trace_interval_s=5.0,
                                       trace_duration_ms=1000))

    from deepflow_tpu.utils.jaxcache import enable_compile_cache
    enable_compile_cache()
    cfg = build_config(args)
    if args.smoke_dir:
        return smoke(args, cfg)

    from deepflow_tpu.models.llama import init_params, make_train_step

    params = init_params(cfg, jax.random.key(0))
    train_step, init_opt = make_train_step(cfg)
    opt_state = init_opt(params)
    step = jax.jit(train_step, donate_argnums=(0, 1))
    tokens = jax.random.randint(
        jax.random.key(1), (args.batch, args.seq + 1), 0,
        cfg.vocab)

    print(f"training: d={cfg.d_model} L={cfg.n_layers} seq={args.seq} "
          f"batch={args.batch} on {jax.devices()[0].device_kind}")
    t0 = time.time()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
        if i % 10 == 0:
            print(f"step {i:4d} loss {float(jax.device_get(loss)):.4f}")
    loss = float(jax.device_get(loss))
    dt = time.time() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s), final loss {loss:.4f}")
    return 0


# -- chip-smoke mode ---------------------------------------------------------

SMOKE_MIN_WINDOWS = 2    # capture windows that must ship device spans
SMOKE_DEADLINE_S = 180   # of training, to get there


def _state_bytes_on(device, *trees) -> int:
    """Bytes of the training state that live on one device."""
    return sum(shard.data.nbytes
               for leaf in jax.tree.leaves(trees)
               for shard in leaf.addressable_shards
               if shard.device == device)


def smoke(args, cfg) -> int:
    from deepflow_tpu.agent import agent as agent_mod
    from deepflow_tpu.models.llama import (
        init_params, make_train_step, param_specs)
    from deepflow_tpu.tpuprobe.sources import HooksSource, XPlaneSource

    devices = jax.devices()
    dev0 = devices[0]
    report: dict = {"device": {"platform": dev0.platform,
                               "kind": dev0.device_kind,
                               "count": len(devices)}}
    failures: list[str] = []

    def done() -> int:
        report["failures"] = failures
        print(json.dumps({"job_report": report}), flush=True)
        return 1 if failures else 0

    if dev0.platform != "tpu":
        failures.append(f"platform is {dev0.platform!r}, not 'tpu'")
        return done()
    agent = agent_mod.current()
    if agent is None or agent.tpuprobe is None:
        failures.append("no in-process agent with a TpuProbe: run under "
                        "deepflow-run")
        return done()
    probe = agent.tpuprobe
    xsrc = next(s for s in probe.sources if isinstance(s, XPlaneSource))
    hooks = next(s for s in probe.sources if isinstance(s, HooksSource))

    report["config"] = {
        "widths": "llama7b" if args.llama7b else "demo",
        "d_model": cfg.d_model, "d_ff": cfg.d_ff, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads, "vocab": cfg.vocab,
        "n_layers": cfg.n_layers, "batch": args.batch, "seq": args.seq,
        "optimizer": "sgd momentum 0.9", "dtype": "bfloat16"}
    train_step, init_opt = make_train_step(cfg)
    key, tok_key = jax.random.key(0), jax.random.key(1)
    tokens = jax.random.randint(tok_key, (args.batch, args.seq + 1), 0,
                                cfg.vocab)
    params = init_params(cfg, key)
    if args.shard:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepflow_tpu.parallel.mesh import make_mesh, shard_params
        mesh = make_mesh(devices)
        report["mesh"] = dict(mesh.shape)
        params = shard_params(params, param_specs(cfg), mesh)
        tokens = jax.device_put(tokens, NamedSharding(mesh, P()))
    opt_state = init_opt(params)
    report["state_bytes_per_device"] = _state_bytes_on(
        dev0, params, opt_state)

    t0 = time.perf_counter()
    step = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens).compile()
    report["compile_s"] = time.perf_counter() - t0
    if args.shard:
        from examples.smoke_checks import hlo_collective_ops
        report["collective_ops_in_hlo"] = hlo_collective_ops(step.as_text())

    losses, times = [], []
    deadline = time.monotonic() + SMOKE_DEADLINE_S
    while True:
        t = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens)
        loss.block_until_ready()
        times.append(time.perf_counter() - t)
        losses.append(loss)
        if (len(times) >= args.steps
                and xsrc.stats["span_windows"] >= SMOKE_MIN_WINDOWS
                and probe.stats.get("mem_samples_sent", 0) > 0):
            break
        if time.monotonic() > deadline:
            failures.append(
                f"deadline {SMOKE_DEADLINE_S}s: {len(times)} steps, "
                f"{xsrc.stats['span_windows']} windows with spans")
            break
    first, last = float(losses[0]), float(losses[-1])
    report.update({
        "steps": len(times),
        # the first timed step is warm-up; windows open during the rest
        "median_step_ms": statistics.median(times[1:] or times) * 1e3,
        "first_loss": first, "last_loss": last,
        "ln_vocab": math.log(cfg.vocab)})
    agent_mod.detach()  # stops the probe and flushes the sender
    report["probe"] = {
        k: xsrc.stats[k] for k in (
            "captures", "events", "errors", "contended", "span_windows",
            "planes_without_ops", "unknown_device_planes", "dead_ms",
            "stop_ms", "parse_ms", "sink_ms", "est_step_ms",
            "coverage_pct")}
    report["probe"].update(
        spans_sent=probe.stats["spans_sent"],
        mem_samples_sent=probe.stats.get("mem_samples_sent", 0),
        hook_events=hooks.stats["events"])
    if agent.guard:
        report["probe"].update(
            guard_degrades=agent.guard.stats["degrades"],
            guard_peak_cpu_pct=agent.guard.stats["peak_cpu_pct"])

    if not (math.isfinite(first) and math.isfinite(last)):
        failures.append(f"loss not finite: first {first}, last {last}")
    if abs(first - math.log(cfg.vocab)) > 0.5:
        failures.append(f"first loss {first} is not near ln(vocab)")
    pr = report["probe"]
    if pr["errors"] or pr["planes_without_ops"]:
        failures.append(f"capture errors {pr['errors']}, device planes "
                        f"without 'XLA Ops' {pr['planes_without_ops']}")
    if pr.get("guard_degrades"):
        failures.append(f"the agent's resource guard paused the probe "
                        f"{pr['guard_degrades']} times")
    if pr["span_windows"] < SMOKE_MIN_WINDOWS:
        failures.append(f"only {pr['span_windows']} capture windows "
                        "yielded device spans")
    if not pr["hook_events"]:
        failures.append("jax.monitoring hooks saw no event")

    if args.shard:
        del params, opt_state, step
        report["sharded_vs_single"] = _single_device_loss(
            cfg, train_step, init_opt, key, tok_key, args, first, failures)
    else:
        report["parser_check"] = _parser_check(
            step, params, opt_state, tokens, args.smoke_dir, failures)
    return done()


def _single_device_loss(cfg, train_step, init_opt, key, tok_key, args,
                        sharded_first: float, failures: list) -> dict:
    """The sharded step's first loss against the same params and tokens
    stepped on jax.devices()[0] alone."""
    from deepflow_tpu.models.llama import init_params
    params = init_params(cfg, key)
    tokens = jax.random.randint(tok_key, (args.batch, args.seq + 1), 0,
                                cfg.vocab)
    step = jax.jit(train_step, donate_argnums=(0, 1))
    _, _, loss = step(params, init_opt(params), tokens)
    single = float(loss)
    rel = abs(sharded_first - single) / abs(single)
    if rel > 1e-2:  # bf16 matmuls summed in a different order
        failures.append(f"sharded first loss {sharded_first} vs one "
                        f"device {single}: rel diff {rel:.4g}")
    return {"sharded": sharded_first, "single": single, "rel_diff": rel}


def _parser_check(step, params, opt_state, tokens, out_dir: str,
                  failures: list) -> dict:
    """Trace 3 steps into out_dir and hold the agent's xplane parser to
    jax.profiler.ProfileData on that file."""
    from examples.smoke_checks import check_against_profile_data
    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
        loss.block_until_ready()
    jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.xplane.pb")), key=os.path.getmtime)
    try:
        check = check_against_profile_data(path, device_id=0)
    except ValueError as e:  # no TPU plane in the trace
        failures.append(f"parser vs ProfileData: {e}")
        return {}
    if check["mismatched"] or not check["ops"]:
        failures.append(f"parser vs ProfileData: {check['mismatched'][:5]}"
                        f" ({check['ops']} ops)")
    return check


if __name__ == "__main__":
    raise SystemExit(main())
