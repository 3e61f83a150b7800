"""p95 of the window's DF-SQL panel queries (not the live tail), timed
from when due."""

from perfbench.metrics._stats import p95
from perfbench.metrics.query_p95_ms import latencies


def read(ctx: dict):
    return p95(latencies(ctx, {"top_ops", "device_time_per_s"}))
