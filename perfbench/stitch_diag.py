"""The stitching check of a several-chip configuration over many seeds
in one job: for each seed, the weights and feed from the seed, steps
until the probe has shipped a train-step window, then `--seconds` of
steps whose captures are all checked (perfbench/stitching.py), with each
device's live bytes once the training state is made and its peak. The
benchmark's own runs do not run this.

    python perfbench/stitch_diag.py --config mistral7b-l16-tp4 \
        --seeds 1,2,3 --seconds 15 --out stitch.jsonl \
        [--layers 4]

It starts the server as run.py does and the job under deepflow-run.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

MODULE = "jit_train_step"


def job(args) -> int:
    from perfbench import model, stitching
    from perfbench.observe import Recorder
    from perfbench.training import Trainer
    rec = Recorder(MODULE)
    import jax
    conf = model.load_json(f"perfbench/configs/{args.config}.json")
    if args.layers:
        conf["num_hidden_layers"] = args.layers
    devices = jax.devices()[:conf["training"]["chips"]]
    with open(args.out, "a") as out:
        for seed in (int(s) for s in args.seeds.split(",")):
            trainer = Trainer(conf, seed, devices)
            live = [d.memory_stats()["bytes_in_use"] for d in devices]
            seen, deadline = rec.train_windows, time.monotonic() + 120
            while rec.train_windows <= seen:
                trainer.step().block_until_ready()
                if time.monotonic() > deadline:
                    raise SystemExit("the probe shipped no train-step "
                                     "window in 120 s")
            rec.captures.clear()
            rec.keep = True
            t0 = time.monotonic()
            while time.monotonic() - t0 < args.seconds:
                trainer.step().block_until_ready()
            rec.keep = False
            res = stitching.check(list(rec.captures), MODULE)
            res.update(seed=seed, steps=trainer.steps,
                       device_kind=devices[0].device_kind,
                       devices=len(devices),
                       layers=conf["num_hidden_layers"],
                       bytes_in_use_after_init=live,
                       peak_bytes_in_use=[
                           d.memory_stats()["peak_bytes_in_use"]
                           for d in devices])
            out.write(json.dumps(res) + "\n")
            out.flush()
            print(json.dumps(res), flush=True)
            rec.captures.clear()
            trainer.free()
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=15)
    parser.add_argument("--out", required=True)
    parser.add_argument("--layers", type=int, default=0,
                        help="run the configuration at this depth")
    parser.add_argument("--job", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    args.out = os.path.abspath(args.out)
    if args.job:
        return job(args)
    from perfbench import run
    run.build_native()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    run_dir = tempfile.mkdtemp(prefix="perfbench-diag-")
    server, ports = run.start_server(run_dir, run.split_cpus()["server"])
    try:
        return subprocess.run(
            [sys.executable, "-m", "deepflow_tpu.cli.runner",
             "--server", f"127.0.0.1:{ports['ingest']}",
             "--controller", f"127.0.0.1:{ports['sync']}",
             "perfbench/stitch_diag.py", "--job", "--config", args.config,
             "--seeds", args.seeds, "--seconds", str(args.seconds),
             "--layers", str(args.layers), "--out", args.out], cwd=run.ROOT,
            env=dict(os.environ, PYTHONPATH=run.ROOT)).returncode
    finally:
        run.stop(server)


if __name__ == "__main__":
    sys.exit(main())
