"""The reduction from a profiler trace to busy, idle, op and collective
time, on interval sets worked by hand and on a small trace recorded on
a TPU v5e."""
from pathlib import Path

import pytest

from chipbench import trace

TESTDATA = Path(__file__).parent / "testdata"


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 50)]
    assert trace.union_ns(iv, 0, 100) == 15 + 11 + 10
    assert trace.union_ns(iv, 8, 45) == 7 + 11 + 5
    assert trace.union_ns([], 0, 10) == 0


def test_gaps_are_the_complement_of_the_union():
    iv = [(5, 15), (0, 10), (20, 30)]
    gaps = trace.gaps_ns(iv, 0, 40)
    assert gaps == [(15, 20), (30, 40)]
    assert sum(b - a for a, b in gaps) + trace.union_ns(iv, 0, 40) == 40


def test_idle_gap_goes_to_the_span_covering_most_of_it():
    spans = sorted([(0, 4, "bench.submit"), (4, 20, "bench.poll")])
    assert trace._cover(spans, 3, 10) == "bench.poll"
    assert trace._cover(spans, 30, 40) == "no bench span"


def test_recorded_v5e_trace():
    """Three runs of resnet50's first stage (the 7x7 convolution) at
    batch 8 on one v5e, each followed by a copy of its output to the host
    in a ``bench.step`` span and a 10 ms sleep in ``bench.wait``; traced
    without a ``bench.traced`` span, so the window is the device's
    first to last op."""
    s = trace.summarize(str(TESTDATA / "v5e_conv1_three_steps.xplane.pb"))
    assert s.devices == ["/device:TPU:0"]
    assert s.window_ns == 31_948_081
    assert s.busy_ns["/device:TPU:0"] == 1_159_542
    assert s.busy_ns["/device:TPU:0"] <= sum(s.op_ns.values())
    (top, secs), *_ = s.top_ops()
    assert top.endswith("/fusion") and top.startswith("jit__lambda")
    assert secs == pytest.approx(3 * 257.66e-6, rel=0.01)
    assert all(v == 0 for c in s.collective_ns.values() for v in c.values())
    assert s.host_span_counts == {"bench.step": 2, "bench.wait": 2}
    idle = s.window_ns - s.busy_ns["/device:TPU:0"]
    assert sum(s.idle_by_host.values()) == pytest.approx(idle)
    assert max(s.idle_by_host, key=s.idle_by_host.get) == "bench.wait"


def test_op_names_and_collectives():
    name = trace.op_name("%collective-permute-start.2 = (bf16[2,512]) "
                         "collective-permute-start(%fusion.1)")
    assert name == "collective-permute-start.2"
    assert trace.is_collective(name, "collective-permute")
    assert not trace.is_collective(trace.op_name(
        "%fusion.4 = f32[8] fusion(%all-reduce.1)"), "all-reduce")
