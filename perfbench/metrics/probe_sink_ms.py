"""Median over the window's capture cycles of the probe's hand-over of a
window's spans to the sender (XPlaneSource.stats["sink_ms"], sampled
each cycle; the probe.sink span of the cycle's trace)."""

from perfbench.metrics._stats import median


def read(ctx: dict):
    return median([s["sink_ms"] for s in ctx["job"]["probe_samples"]])
