"""DF-SQL: the ten HLO ops with the most summed device time."""

from perfbench.panels._rows import spans, sql_rows

N = 10


def request(t0_ns: int, t1_ns: int):
    return "/v1/query/", {"db": "profile", "sql": (
        "SELECT hlo_op, Sum(duration_ns) AS d FROM tpu_hlo_span "
        f"WHERE hlo_op != '' AND time >= {t0_ns} AND time < {t1_ns} "
        f"GROUP BY hlo_op ORDER BY d DESC LIMIT {N}")}


def expected(rows: dict, t0_ns: int, t1_ns: int) -> dict:
    """Every op's summed time; the server returns the top N of them."""
    sums: dict = {}
    for r in spans(rows, t0_ns, t1_ns):
        if r["hlo_op"]:
            sums[r["hlo_op"]] = sums.get(r["hlo_op"], 0) + r["duration_ns"]
    return sums


def answer(response: dict) -> list:
    return [(r["hlo_op"], int(r["d"])) for r in sql_rows(response)]


def agrees(got: list, sums: dict) -> bool:
    return top_agrees(got, sums, N)


def top_agrees(got: list, sums: dict, n: int) -> bool:
    """A top-n answer with its ties cut anywhere: n rows (or all there
    are), each the key's exact sum, in order, whose values are the n
    largest."""
    top = sorted(sums.values(), reverse=True)[:n]
    return ([v for _, v in got] == top
            and all(sums.get(k) == v for k, v in got)
            and len({k for k, _ in got}) == len(got))
