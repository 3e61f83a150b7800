"""Idle share of the benchmark's own device trace of a few steps: one
minus the union of the op intervals over the traced span, averaged over
the chips (perfbench/trace_reduce.py)."""


def read(ctx: dict):
    trace = ctx["job"].get("trace") or {}
    return trace.get("idle_pct")
