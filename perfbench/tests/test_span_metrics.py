"""The per-layer readers of the probe's cycle phases: each reads the
samples a run records, and gives a number, or None where a CPU run has
no capture in its window."""

import os

from perfbench import model, run
from perfbench.tests.test_faults import _bench

NEW = ("probe_sink_ms",)


def test_probe_sink_ms_is_the_median_of_the_window_cycles():
    reader = run.load_metric("probe_sink_ms")
    samples = [{"stop_ms": 400.0, "parse_ms": 600.0, "sink_ms": v,
                "events": 1} for v in (12.5, 9.0, 30.0)]
    assert reader.read({"job": {"probe_samples": samples}}) == 12.5
    assert reader.read({"job": {"probe_samples": []}}) is None


def test_new_readers_read_a_traced_cpu_run():
    limits = model.load_json("perfbench/limits/mistral7b-l4.json")
    limits["captures_unchecked"] = 2      # the CPU has no device planes
    affinity = os.sched_getaffinity(0)
    try:
        out = run.run(_bench(), "tiny.train", 2**31 + 11, 4, True,
                      allow_cpu=True, limits=limits)
    finally:
        os.sched_setaffinity(0, affinity)
    assert out["correct"], out["checks"]
    for name in NEW:
        got = out["metrics"].get(name)
        assert got is None or (got["unit"] == "ms" and got["value"] >= 0), got
