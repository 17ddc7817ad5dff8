import os

import pytest

from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "small.xplane.pb")


def test_union_busy_and_gaps():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("a", 3.0, 1.0)]
    assert tr.union([(0.0, 1.0), (0.5, 1.5), (3.0, 4.0)]) == [(0.0, 1.5),
                                                              (3.0, 4.0)]
    assert tr.busy_seconds(ops) == pytest.approx(2.5)
    assert tr.sum_by_name(ops, "a") == (2.0, 2)
    assert tr.idle_gaps(ops, -1.0, 5.0) == [(-1.0, 0.0), (1.5, 3.0),
                                            (4.0, 5.0)]


def test_gap_attribution_and_breakdown():
    events = {"devices": {"/device:TPU:0": {
        "ops": [("fusion.1", 1.0, 1.0), ("paged_mixed_attention_ragged", 2.0,
                                          0.5), ("fusion.1", 4.0, 1.0)],
        "modules": [("jit_step(1)", 1.0, 1.5), ("jit_step(1)", 4.0, 1.0)]}},
        "annotations": [("arks_step[r1=abc]", 0.5, 2.9),
                        ("arks_step", 3.9, 1.6)]}
    phases = [{"name": "phase.mixed", "start": 102.4, "end": 103.45}]
    out = tr.reduce(events, phases, clock_offset_s=-100.0)
    assert out["window_s"] == pytest.approx(5.0)
    assert out["busy_s"] == pytest.approx(2.5)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 0.5..1.0 inside the first step; 2.5..4.0 has its midpoint 3.25 inside
    # the first step and inside phase.mixed laid on the trace's clock;
    # 5.0..5.5 inside the step that had no request live
    assert gaps["inside a step"] == pytest.approx(0.5)
    assert gaps["inside a step: phase.mixed"] == pytest.approx(1.5)
    assert gaps["step with no request live"] == pytest.approx(0.5)
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", 2.0]


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in benchmarks/tests/data")
def test_recorded_trace():
    ev = tr.read_events(RECORDED)
    out = tr.reduce(ev)
    assert out["chips"] == 1
    assert 0 < out["busy_s"] <= out["window_s"]
    kernel_s, n = tr.sum_by_name(out["ops"], "paged_mixed_attention")
    assert n > 0 and 0 < kernel_s < out["busy_s"]
    assert out["annotations"] and out["modules"]
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_the_step_readers_tell_the_slowest_program_from_the_rest():
    from benchmarks import manifest
    seq = [("jit__unknown(1)", float(i), 0.30 + 0.01 * i) for i in range(5)]
    pipe = [("jit__unknown(2)", 10.0 + i, 0.03) for i in range(9)]
    rare = [("jit_gather(3)", 30.0, 0.2)]         # ran once: not a step
    read = {n: manifest.load_reader(n)
            for n in ("seq_step_ms_p50", "pipe_step_ms_p50")}
    both = {"device": {"modules": seq + pipe + rare}}
    assert read["seq_step_ms_p50"](both) == pytest.approx(320.0)
    assert read["pipe_step_ms_p50"](both) == pytest.approx(30.0)
    only = {"device": {"modules": seq + rare}}
    assert read["seq_step_ms_p50"](only) == pytest.approx(320.0)
    assert read["pipe_step_ms_p50"](only) is None
    assert read["seq_step_ms_p50"]({"device": None}) is None
