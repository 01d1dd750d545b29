"""The reduction from a profiler trace to busy time, ops, top ops and labelled gaps."""

from pathlib import Path

import pytest

from bench import trace
from bench.harness import SPANS

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


def test_union_and_clip():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert trace.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


def test_reduce_events_by_hand():
    host = [(0.0, 10.0, "window"), (0.0, 5.0, "iteration"), (0.0, 1.0, "front_end_call"),
            (1.0, 5.0, "block"), (5.0, 10.0, "iteration"), (5.0, 7.0, "front_end_call"),
            (7.0, 10.0, "block"), (-1.0, 11.0, "outside")]
    ops = {"/device:TPU:0": [(1.0, 3.0, "fusion"), (2.0, 4.0, "dot"), (7.5, 9.0, "dot"),
                             (12.0, 13.0, "late")]}
    s = trace.reduce_events(ops, host)
    assert s.window_s == 10.0 and s.n_devices == 1 and s.n_ops == 3
    assert s.busy_s == pytest.approx(3.0 + 1.5)
    assert s.top_ops == [("dot", 3.5), ("fusion", 2.0)]
    # gaps: [0,1] front_end_call, [4,7.5] iteration -> innermost at 5.75 is front_end_call,
    # [9,10] block
    assert s.gaps == [("front_end_call", 3.5), ("front_end_call", 1.0), ("block", 1.0)]
    assert s.spans["front_end_call"] == [1.0, 2.0]


def test_reduce_needs_a_window():
    with pytest.raises(ValueError):
        trace.reduce_events({}, [(0.0, 1.0, "iteration")])


def test_short_name():
    name = ("%fusion.434 = f32[32,32,512,512]{2,3,0,1:T(8,128)} fusion(f32[32,32,512,512,1]"
            "{2,3,1,0,4:T(8,128)} %bitcast.3059), kind=kOutput, calls=%fused_computation.1226")
    assert trace.short_name(name) == "%fusion.434 = f32[32,32,512,512] fusion"
    assert trace.short_name("not an instruction") == "not an instruction"


def test_recorded_chip_trace():
    # three iterations of a 2048^3 matmul program on one TPU v5e, each with a
    # 20 ms host sleep inside front_end_call (tests/bench/record_trace.py)
    s = trace.summarize(str(SMALL), SPANS)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.0627, abs=1e-3)
    # the device clock runs about 0.8 ms behind the host's here, so the first
    # iteration's four ops carry times before the window opens
    assert s.n_ops == 8
    assert 0.0002 < s.busy_s < 0.0005
    assert [n.split(" = ")[0] for n, _ in s.top_ops[:2]] == ["%fusion", "%convolution_tanh_fusion"]
    assert len(s.spans["front_end_call"]) == 3
    assert all(d >= 0.02 for d in s.spans["front_end_call"])
    assert {name for name, _ in s.gaps} <= set(SPANS) | {"none"}
    assert [n for n, _ in s.gaps[:3]] == ["front_end_call"] * 3
    assert all(g > 0.019 for _, g in s.gaps[:3])
