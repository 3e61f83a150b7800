"""The check of the window's own answers (Dashboard.check_timed): an
answer passes where it is the plain evaluator's over a state the server
can have held while it answered, and fails where it is older than what a
tail poll had already shown, or holds a batch handed over after it came."""

import pytest

from perfbench.dashboard import Dashboard

MIX = {"rate_qps": 8, "panels": ["top_ops"], "workers": 1,
       "tail": {"panel": "live_tail", "interval_s": 0.5}}
COLS = ("time", "duration_ns", "device_id", "kind", "hlo_module",
        "hlo_category", "hlo_op", "run_id", "bytes_accessed", "collective")


def _rows():
    """Three batches, handed over at 100, 200 and 300 ns: each one train
    launch on device 0 and two ops; then one batch of HBM samples."""
    spans = {c: [] for c in COLS}
    sent = []
    for b, t_sent in enumerate((100, 200, 300)):
        for op, dur in (("", 50), ("fusion.1", 10 + b), ("fusion.2", 5)):
            row = {"time": 10 * b, "duration_ns": dur, "device_id": 0,
                   "kind": "device-compute", "hlo_module": "jit_train_step",
                   "hlo_category": "", "hlo_op": op, "run_id": b + 1,
                   "bytes_accessed": 0, "collective": ""}
            for c in COLS:
                spans[c].append(row[c])
        sent.append([t_sent, 3, 0])
    mem = {c: [] for c in ("time", "device_id", "bytes_in_use",
                           "peak_bytes_in_use", "bytes_limit",
                           "largest_free_block")}
    sent.append([310, 0, 0])
    return {"spans": spans, "memory": mem, "sent": sent}


def _sums(n_batches: int) -> list:
    """top_ops' answer over the first batches, as the server sends it."""
    sums = {"fusion.1": sum(10 + b for b in range(n_batches)),
            "fusion.2": 5 * n_batches}
    vals = sorted(([k, v] for k, v in sums.items() if v), key=lambda r: -r[1])
    return {"result": {"columns": ["hlo_op", "d"], "values": vals}}


@pytest.mark.parametrize("held,agrees", [
    (0, False),     # older than the tail had shown when the query was sent
    (1, True),      # what the tail had shown
    (2, True),      # and the next batch, handed over before the answer
    (3, False),     # a batch handed over after the answer came
])
def test_an_answer_is_judged_by_what_the_server_can_have_held(held, agrees):
    dash = Dashboard(0, MIX, 0, seed=1, seconds=1)
    dash.sampled = {0}
    panel = dash.panels[0][1]
    rec = {"panel": "top_ops", "due": 1000, "sent": 160, "done": 250,
           "ok": True, "error": None}
    dash.kept = {0: (rec, panel, _sums(held))}
    dash.tail_seen = {1: 150, 2: 260}
    off = dash.check_timed(_rows())
    assert (off == []) is agrees, off


def test_an_answer_that_never_came_fails():
    dash = Dashboard(0, MIX, 0, seed=1, seconds=1)
    dash.sampled = {0, 3}
    rec = {"panel": "top_ops", "due": 1000, "sent": 160, "done": 250,
           "ok": True, "error": None}
    dash.kept = {0: (rec, dash.panels[0][1], _sums(1))}
    dash.tail_seen = {1: 150}
    assert [o["index"] for o in dash.check_timed(_rows())] == [3]
