"""The live tail: device 0's train-step launches as they land. Each
launch's key is its run_id; the first poll that returns it dates its
arrival."""

from perfbench.panels._rows import spans, sql_rows

MODULE = "jit_train_step"


def request(t0_ns: int, t1_ns: int):
    return "/v1/query/", {"db": "profile", "sql": (
        "SELECT run_id, time, duration_ns FROM tpu_hlo_span "
        f"WHERE device_id = 0 AND hlo_op = '' AND hlo_module = '{MODULE}' "
        f"AND time >= {t0_ns} AND time < {t1_ns}")}


def keys(response: dict) -> list:
    return [int(r["run_id"]) for r in sql_rows(response)]


def expected(rows: dict, t0_ns: int, t1_ns: int) -> list:
    return sorted((r["run_id"], r["time"], r["duration_ns"])
                  for r in spans(rows, t0_ns, t1_ns)
                  if r["device_id"] == 0 and not r["hlo_op"]
                  and r["hlo_module"] == MODULE)


def answer(response: dict) -> list:
    return sorted((int(r["run_id"]), int(r["time"]), int(r["duration_ns"]))
                  for r in sql_rows(response))
