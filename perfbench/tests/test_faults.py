"""Whole runs on the CPU at a test size with the timed path broken
underneath: each must come out not correct. The harness's look for a
chip is skipped (allow_cpu); a sitecustomize on PYTHONPATH plants the
fault in every process the run starts (the job and the server)."""

import json
import os
import textwrap

import pytest

from perfbench import model, run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny.json")

FAULTS = {
    # the program's step hands back the state it was given
    "state_unchanged": """
        import deepflow_tpu.models.llama as L
        _orig = L.make_train_step
        def make_train_step(cfg, optimizer=None, mesh=None, sp_axis=None):
            step, init = _orig(cfg, optimizer, mesh, sp_axis)
            def train_step(params, opt_state, tokens):
                return (params, opt_state) + step(params, opt_state,
                                                  tokens)[2:]
            return train_step, init
        L.make_train_step = make_train_step
    """,
    # half of the batch left out, the mean taken over the rest
    "half_batch": """
        import deepflow_tpu.models.llama as L
        _orig = L.make_train_step
        def make_train_step(cfg, optimizer=None, mesh=None, sp_axis=None):
            step, init = _orig(cfg, optimizer, mesh, sp_axis)
            def train_step(params, opt_state, tokens):
                return step(params, opt_state, tokens[:tokens.shape[0] // 2])
            return train_step, init
        L.make_train_step = make_train_step
    """,
    # the control: the reference's float8 step in the program's place
    "fp8_control": """
        import deepflow_tpu.models.llama as L
        _orig = L.make_train_step
        def make_train_step(cfg, optimizer=None, mesh=None, sp_axis=None):
            _, init = _orig(cfg, optimizer, mesh, sp_axis)
            from perfbench import model, reference
            fp8 = reference.step_fn(model.load_json(TINY), "fp8")
            def train_step(params, opt_state, tokens):
                lion = opt_state[0]
                params, mu, loss, _ = fp8(params, lion.mu, tokens)
                lion = lion._replace(count=lion.count + 1, mu=mu)
                return params, (lion,) + tuple(opt_state[1:]), loss
            return train_step, init
        L.make_train_step = make_train_step
    """,
    # an answer altered where the server produces it
    "answer_altered": """
        import deepflow_tpu.server.querier as Q
        _orig = Q.QuerierAPI.query
        def query(self, body):
            out = _orig(self, body)
            if "GROUP BY t" in body.get("sql", ""):
                out["result"]["values"].append([1, 1])
            return out
        Q.QuerierAPI.query = query
    """,
    # the same, only while the window runs: a flame over a range that ends
    # within a minute of now gains a node; the check after ingestion,
    # over a range that ends an hour on, is answered right
    "answer_altered_in_window": """
        import time
        import deepflow_tpu.server.querier as Q
        _orig = Q.QuerierAPI.tpu_flame
        def tpu_flame(self, body):
            out = _orig(self, body)
            if body.get("time_end", 0) < time.time_ns() + 60 * 10**9:
                out["result"]["children"].append(
                    {"name": "altered", "self_value": 1, "children": []})
            return out
        Q.QuerierAPI.tpu_flame = tpu_flame
    """,
}


def _bench():
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny",
                             "file": "perfbench/tests/tiny.json"})
    bench["workloads"].append({"name": "tiny.train", "config": "tiny",
                               "traffic": "train", "chips": 1})
    return bench


def _run(tmp_path, monkeypatch, fault=None):
    if fault:
        (tmp_path / "sitecustomize.py").write_text(
            textwrap.dedent(FAULTS[fault]).replace("TINY", repr(TINY)))
        monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
            (str(tmp_path), run.ROOT)))
    limits = model.load_json("perfbench/limits/mistral7b-l4.json")
    limits["captures_unchecked"] = 2      # the CPU has no device planes
    affinity = os.sched_getaffinity(0)
    try:
        return run.run(_bench(), "tiny.train", 2**31 + 9, 4, False,
                       allow_cpu=True, limits=limits)
    finally:
        os.sched_setaffinity(0, affinity)


def test_a_sound_run_is_correct(tmp_path, monkeypatch):
    out = _run(tmp_path, monkeypatch)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_run_is_not_correct(tmp_path, monkeypatch, fault):
    out = _run(tmp_path, monkeypatch, fault)
    assert not out["correct"], out["checks"]
