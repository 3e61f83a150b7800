"""From the run's start to the window's: native build check, server
start, the job's imports, weights, compile, first steps, and the
probe's calibration windows (host clock)."""


def read(ctx: dict):
    return ctx["setup_s"]
