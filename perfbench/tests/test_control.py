"""The control and the planted fault against the program's readings, at
a test size (perfbench/tests/tiny.json). On the chip the same script
runs at the cell's own size: python perfbench/control.py --config NAME."""

import json
import os

import pytest

from perfbench import control, model

TINY = os.path.join(os.path.dirname(__file__), "tiny.json")


@pytest.fixture(scope="module")
def rows():
    import jax
    conf = model.load_json(TINY)
    limits = model.load_json("perfbench/limits/mistral7b-l4.json")
    return [control.seed_readings(conf, s, jax.devices()[:1], limits)
            for s in (3, 2**31 + 5)]


def test_control_fails_the_gradient(rows):
    summary = control.summarize(rows)["grad_gap"]
    assert summary["control_min"] > 2 * summary["program_max"], summary


@pytest.mark.parametrize("side,correct", [
    ("program", True), ("control_fp8", False), ("fault_half_batch", False)])
def test_the_cells_limits_judge_each_side(rows, side, correct):
    # the harness's own comparison, with the cell's limits, on every seed
    for r in rows:
        assert r["judged"][side]["correct"] is correct, r["judged"][side]


@pytest.mark.parametrize("number", ["grad_gap", "update_gap"])
def test_half_batch_reads_ten_times_the_program(rows, number):
    summary = control.summarize(rows)[number]
    assert summary["fault_half_batch_min"] > 10 * summary["program_max"]


def test_every_leaf_moves_alike_on_both_sides(rows):
    # Lion moves each weight by one step of lr where that is over half a
    # bfloat16 ulp of the weight; the norms (ones) and the largest weights
    # stay, in the program and in the reference alike
    for r in rows:
        moved = r["moved_share"]["program"]
        assert min(v for k, v in moved.items() if "norm" not in k) > 0.5
        assert moved == pytest.approx(r["moved_share"]["reference"],
                                      abs=0.01)
        assert r["program"]["update_gap"] < 0.01, json.dumps(r["program"])
