"""Median over the window's capture cycles of the probe's xplane parse
(XPlaneSource.stats["parse_ms"], sampled each cycle)."""

from perfbench.metrics._stats import median


def read(ctx: dict):
    return median([s["parse_ms"] for s in ctx["job"]["probe_samples"]])
