"""TpuFlame: device time as a module -> category -> op tree."""

from perfbench.panels._rows import DEVICE_KINDS, spans


def request(t0_ns: int, t1_ns: int):
    return "/v1/profile/TpuFlame", {"time_start": t0_ns, "time_end": t1_ns}


def expected(rows: dict, t0_ns: int, t1_ns: int) -> dict:
    out: dict = {}
    for r in spans(rows, t0_ns, t1_ns):
        if r["duration_ns"] > 0 and r["kind"] in DEVICE_KINDS:
            path = tuple(x for x in (r["hlo_module"],
                                     r["hlo_category"] or "other",
                                     r["hlo_op"]) if x)
            out[path] = out.get(path, 0) + r["duration_ns"]
    return out


def answer(response: dict) -> dict:
    """{path: self value} of every node that ends a stack."""
    out: dict = {}

    def walk(node, path):
        if node["self_value"]:
            out[path] = node["self_value"]
        for c in node["children"]:
            walk(c, path + (c["name"],))

    for c in response["result"]["children"]:
        walk(c, (c["name"],))
    return out
