"""DF-SQL: device 0's op time in each second of the job."""

from perfbench.panels._rows import spans, sql_rows


def request(t0_ns: int, t1_ns: int):
    return "/v1/query/", {"db": "profile", "sql": (
        "SELECT time(time, 1) AS t, Sum(duration_ns) AS d "
        "FROM tpu_hlo_span WHERE hlo_op != '' AND device_id = 0 "
        f"AND time >= {t0_ns} AND time < {t1_ns} GROUP BY t ORDER BY t")}


def expected(rows: dict, t0_ns: int, t1_ns: int) -> dict:
    out: dict = {}
    for r in spans(rows, t0_ns, t1_ns):
        if r["hlo_op"] and r["device_id"] == 0:
            t = r["time"] // 1_000_000_000
            out[t] = out.get(t, 0) + r["duration_ns"]
    return out


def answer(response: dict) -> dict:
    return {int(r["t"]): int(r["d"]) for r in sql_rows(response)}
