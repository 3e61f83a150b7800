"""The trace reducer on two recorded traces: a v5e capture of a matmul
loop and a synthetic 8-device SPMD step with collectives.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

import os

import pytest

from perfbench import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(__file__), "..", "..", "tests",
                        "fixtures")


def _ops(path, device):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    plane = pd.find_plane_with_name(f"/device:TPU:{device}")
    return [(float(e.start_ns), float(e.start_ns) + tr._duration_ns(e),
             tr._op_name(e.name))
            for line in plane.lines if line.name == "XLA Ops"
            for e in line.events]


def _grid_busy(ops, t0, t1, n=200_000):
    """Busy time by sampling the window on a fine grid (a brute force)."""
    step = (t1 - t0) / n
    hits = 0
    for i in range(n):
        t = t0 + (i + 0.5) * step
        hits += any(s <= t < e for s, e, _ in ops)
    return hits * step


@pytest.mark.parametrize("name,devices", [("matmul_v5e", 1),
                                          ("spmd8_synth", 8)])
def test_busy_is_the_union_of_op_intervals(name, devices):
    path = os.path.join(FIXTURES, f"{name}.xplane.pb")
    assert tr.reduce_file(path)["devices"] == devices
    out = tr.reduce_file(path, n_devices=1)        # device 0 alone
    ops = _ops(path, 0)
    t0, t1 = min(s for s, _, _ in ops), max(e for _, e, _ in ops)
    if devices == 1:
        want = _grid_busy(ops, t0, t1)
        assert out["busy_s"] * 1e9 == pytest.approx(want, rel=2e-3)
    # nothing counts twice: the union is at most the summed durations
    assert out["busy_s"] * 1e9 <= sum(e - s for s, e, _ in ops) + 1e-6
    assert 0.0 <= out["idle_pct"] < 100.0
    assert out["busy_s"] <= out["window_s"]


def test_self_times_leave_nested_time_out():
    events = [(0, 100, "while"), (10, 40, "a"), (50, 90, "b"),
              (60, 70, "c")]
    self_ns = dict(tr._self_times(events))
    assert self_ns == {"while": 30, "a": 30, "b": 30, "c": 10}
    assert sorted(op for _, _, op in tr._leaves(events)) == ["a", "c"]


def test_exposed_collective_time_on_the_spmd_step():
    out = tr.reduce_file(os.path.join(FIXTURES, "spmd8_synth.xplane.pb"),
                         n_devices=1)
    # per step on device 0: all-reduce 1200 ns and all-gather 800 ns,
    # both after the compute fusion has ended, so all of it is exposed
    assert out["coll_ms"] == pytest.approx(2000 / 1e6)
    assert out["coll_exposed_ms"] == pytest.approx(2000 / 1e6)


def test_idle_gaps_are_named_by_the_host():
    out = tr.reduce_file(os.path.join(FIXTURES, "matmul_v5e.xplane.pb"))
    assert out["idle_gaps"], "a 3-launch trace has gaps between launches"
    longest = out["idle_gaps"][0]
    assert longest[1] > 0 and longest[0] != "no host event"
    assert out["device_ops"][0][0].startswith("convolution")


def test_subtract_counts_what_is_left_uncovered():
    a = tr.union([(0, 10), (20, 30)])
    b = tr.union([(5, 25)])
    assert tr._subtract(a, b) == 10
    assert tr._subtract(a, []) == 20
