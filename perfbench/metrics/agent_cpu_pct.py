"""CPU time of the agent's df-* threads over the window, from /proc, as
a share of one core."""


def read(ctx: dict):
    return ctx["job"]["agent_cpu_pct"]
