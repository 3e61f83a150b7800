"""The job's wall time over the window divided by the steps it
completed there; each step ends in block_until_ready (host clock)."""


def read(ctx: dict):
    job = ctx["job"]
    return 1000.0 * job["window_s"] / job["steps"]
