"""TpuMemory: each device's latest HBM sample, and the ops that moved
the most HBM bytes."""

from perfbench.panels._rows import spans
from perfbench.panels.top_ops import top_agrees

TOP = 15


def request(t0_ns: int, t1_ns: int):
    return "/v1/profile/TpuMemory", {"time_start": t0_ns,
                                     "time_end": t1_ns}


def expected(rows: dict, t0_ns: int, t1_ns: int) -> dict:
    mem = rows["memory"]
    latest: dict = {}
    order = sorted(range(len(mem["time"])), key=lambda i: mem["time"][i])
    for i in order:
        if t0_ns <= mem["time"][i] < t1_ns and mem["bytes_limit"][i] > 0:
            latest[mem["device_id"][i]] = (
                mem["bytes_in_use"][i], mem["peak_bytes_in_use"][i],
                mem["bytes_limit"][i], mem["largest_free_block"][i])
    moved: dict = {}
    for r in spans(rows, t0_ns, t1_ns):
        if r["bytes_accessed"] > 0:
            k = (r["hlo_op"], r["hlo_module"])
            moved[k] = moved.get(k, 0) + r["bytes_accessed"]
    return {"devices": latest, "top_bytes": moved}


def answer(response: dict) -> dict:
    res = response["result"]
    latest = {d["device_id"]: (d["bytes_in_use"], d["peak_bytes_in_use"],
                               d["bytes_limit"], d["largest_free_block"])
              for d in res["devices"]}
    return {"devices": latest,
            "top_bytes": [((o["hlo_op"], o["hlo_module"]),
                           o["bytes_accessed"]) for o in res["top_ops"]]}


def agrees(got: dict, want: dict) -> bool:
    return (got["devices"] == want["devices"]
            and top_agrees(got["top_bytes"], want["top_bytes"], TOP))
