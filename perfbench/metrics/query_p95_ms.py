"""p95 over every dashboard query due in the window (panels and the
live tail), timed from when it was due; a failed query counts at the
client's timeout."""

from perfbench.dashboard import TIMEOUT_S
from perfbench.metrics._stats import p95


def latencies(ctx: dict, panels=None) -> list:
    return [(q["done"] - q["due"]) / 1e6 if q["ok"] else TIMEOUT_S * 1e3
            for q in ctx["queries"] if panels is None or q["panel"] in panels]


def read(ctx: dict):
    return p95(latencies(ctx))
