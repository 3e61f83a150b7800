"""Collective time per step during which no compute op runs on that
device, from the benchmark's own device trace; nothing where the cell
has no collectives."""


def read(ctx: dict):
    trace = ctx["job"].get("trace") or {}
    return trace.get("coll_exposed_ms")
