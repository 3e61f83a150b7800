"""The stitching check on a synthetic 8-device SPMD capture."""

import os

from perfbench import stitching

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "..", "tests",
                       "fixtures", "spmd8_synth.xplane.pb")


def _capture():
    from deepflow_tpu.tpuprobe.xplane import parse_xplane_file
    with open(FIXTURE, "rb") as f:
        return f.read(), parse_xplane_file(FIXTURE)


def test_whole_launches_stitch_as_the_plain_grouping():
    xspace, spans = _capture()
    out = stitching.check([(xspace, spans, 0)], "jit_train_step")
    assert out["whole_launches"] == 2
    assert out["groups_checked"] == 4       # all-reduce.3, all-gather.7
    assert out["groups_off"] == 0 and out["short_groups"] == 0


def test_a_participant_left_out_is_caught():
    xspace, spans = _capture()
    first = min(s.run_id for s in spans if s.hlo_op)
    spans = [s for s in spans if not (s.device_id == 3 and s.run_id == first
                                     and s.hlo_op == "all-reduce.3")]
    out = stitching.check([(xspace, spans, 0)], "jit_train_step")
    assert out["groups_off"] == 1
    assert out["short_inside_whole"] == 1
