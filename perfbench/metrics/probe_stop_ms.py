"""Median over the window's capture cycles of the probe's profiler
session stop (XPlaneSource.stats["stop_ms"], sampled each cycle)."""

from perfbench.metrics._stats import median


def read(ctx: dict):
    return median([s["stop_ms"] for s in ctx["job"]["probe_samples"]])
