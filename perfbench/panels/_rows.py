"""Row helpers shared by the panels' plain evaluators."""

DEVICE_KINDS = ("device-compute", "device-collective", "device-transfer")


def spans(rows: dict, t0_ns: int, t1_ns: int):
    """Shipped span rows (dicts) with t0 <= time < t1."""
    cols = rows["spans"]
    names = list(cols)
    for vals in zip(*(cols[c] for c in names)):
        r = dict(zip(names, vals))
        if t0_ns <= r["time"] < t1_ns:
            yield r


def sql_rows(response: dict) -> list:
    res = response["result"]
    return [dict(zip(res["columns"], row)) for row in res["values"]]
