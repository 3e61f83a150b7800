"""Readings that set the training check's limits, for a configuration:
on each seed, the program's first three steps (as the job drives them)
against the plain float32 reference, beside the control (the reference
with float8 matmuls in the program's place) and a planted fault (the
reference that averages over half the batch). The benchmark's own runs
do not run this.

    python perfbench/control.py --config mistral7b-l4 --seeds 1,2,3

prints one JSON line per seed and a summary line: the largest reading of
the program (the lower end of each limit) and the smallest of the control
and of the fault (the upper end). Each seed's line also judges each side
as a run does (run.judge against perfbench/limits/<config>.json): the
program has to come out correct, the control and the fault not. On the
chip it runs at the cell's own size; perfbench/tests/test_control.py runs
it at a test size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

NUMBERS = ("loss_gap", "grad_gap", "update_gap")


SIDES = ("program", "control_fp8", "fault_half_batch")


def seed_readings(conf: dict, seed: int, devices: list,
                  limits: dict | None = None) -> dict:
    import gc

    from perfbench import model, reference
    from perfbench.training import Trainer
    t = [time.monotonic()]
    trainer = Trainer(conf, seed, devices)
    prog = trainer.first_steps()
    trainer.free()
    del trainer
    gc.collect()
    t.append(time.monotonic())
    w = model.widths(conf)
    ref = reference.readings_on(conf, seed, devices)
    t.append(time.monotonic())
    ctrl = reference.readings_on(conf, seed, devices, precision="fp8")
    t.append(time.monotonic())
    half = reference.readings_on(conf, seed, devices,
                                 keep_rows=w["batch"] // 2)
    t.append(time.monotonic())
    out = {"seed": seed, "program": reference.gaps(ref, prog),
           "control_fp8": reference.gaps(ref, ctrl),
           "fault_half_batch": reference.gaps(ref, half),
           "moved_share": {"program": prog["moved_share"],
                           "reference": ref["moved_share"]},
           "update_norms": {"program": prog["update"],
                            "reference": ref["update"]},
           "ref_loss": ref["loss"], "program_loss": prog["loss"],
           "seconds": dict(zip(("program", "reference", "control",
                                "half_batch"),
                               (b - a for a, b in zip(t, t[1:]))))}
    if limits is not None:
        out["judged"] = {side: score(out[side], limits) for side in SIDES}
    return out


def score(gaps: dict, limits: dict) -> dict:
    """One side's readings as the harness judges a run's."""
    from perfbench import run
    checks = run.judge(gaps, limits)
    return {"correct": run.is_correct(checks), "checks": checks}


def summarize(rows: list[dict]) -> dict:
    out = {k: {"program_max": max(r["program"][k] for r in rows),
               "control_min": min(r["control_fp8"][k] for r in rows),
               "fault_half_batch_min": min(r["fault_half_batch"][k]
                                           for r in rows)}
           for k in NUMBERS}
    if all("judged" in r for r in rows):
        out["seeds_correct"] = {side: sum(r["judged"][side]["correct"]
                                          for r in rows) for side in SIDES}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True,
                        help="a configuration name or a .json path")
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)
    import jax

    from perfbench import model
    path = (args.config if args.config.endswith(".json")
            else f"perfbench/configs/{args.config}.json")
    conf = model.load_json(path)
    limits = model.load_json(f"perfbench/limits/{conf['name']}.json")
    chips = conf["training"]["chips"]
    devices = jax.devices()[:chips]
    print(json.dumps({"device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}), flush=True)
    rows = []
    for s in args.seeds.split(","):
        rows.append(seed_readings(conf, int(s), devices, limits))
        print(json.dumps(rows[-1]), flush=True)
    summary = {"summary": summarize(rows), "seeds": len(rows)}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows + [summary]:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
