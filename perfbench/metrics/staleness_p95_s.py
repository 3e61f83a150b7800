"""p95 over the window's stored steps of the time from the step's
device end, as stored, to the first live-tail poll that returned it."""

from perfbench.metrics._stats import p95


def read(ctx: dict):
    return p95([(s["first_seen_ns"] - s["end_ns"]) / 1e9
                for s in ctx["steps_stored"]
                if s["first_seen_ns"] is not None])
