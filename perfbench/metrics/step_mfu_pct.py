"""The whole step's share of the chips' bf16 peak: the benchmark's FLOPs
per step (perfbench/model.py) times the window's steps, over the window
and chips times the peak of peaks.json."""


def read(ctx: dict):
    job = ctx["job"]
    if ctx["peaks"] is None:
        return None
    done = job["flops_per_step"] * job["steps"] / job["window_s"]
    return 100.0 * done / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
