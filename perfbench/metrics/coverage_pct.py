"""Share of the window's steps whose device spans the store holds:
device 0's train-step launches that end in the window, over the steps
the job completed there."""


def read(ctx: dict):
    return 100.0 * len(ctx["steps_stored"]) / ctx["job"]["steps"]
