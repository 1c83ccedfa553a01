"""The trace reduction on a small recorded trace, and its interval arithmetic on made-up events.

``data/cdist-4calls.xplane.pb`` is PR 22's chip trace (TPU v5 lite) of four calls of
``ht.spatial.cdist`` on 40 000 x 18 rows, each inside a ``chipbench.call`` annotation: four
``jit__sqrt_quadratic_expand`` programs, 38.390 ms of device time together.
"""
import os

import pytest

from harness import xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cdist-4calls.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce(RECORDED, annotation="chipbench.call")


def test_recorded_trace_busy_window_and_idle(trace):
    assert len(trace.calls) == 4 and [d["name"] for d in trace.devices] == ["/device:TPU:0"]
    assert trace.busy_s == pytest.approx(38.390148e-3, rel=1e-9)
    assert trace.window_s == pytest.approx(42.292594e-3, rel=1e-9)
    assert trace.device_s_per_call() == pytest.approx(38.390148e-3 / 4, rel=1e-9)
    assert trace.span_s_per_call() == pytest.approx(10.55225875e-3, rel=1e-9)
    idle = sum(s for _, s in trace.idle_gaps(n=1000, least_s=0.0)) * len(trace.calls)
    assert idle == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)


def test_recorded_trace_per_name_times(trace):
    assert trace.module_s_per_call() == {"jit__sqrt_quadratic_expand": pytest.approx(9.597537e-3, rel=1e-9)}
    label, seconds = trace.top_ops()[0]
    assert label == "fusion.2 f32[40000,40000]" and seconds == pytest.approx(9.58183875e-3, rel=1e-6)
    assert trace.op_s_per_call(["fusion"]) == pytest.approx(9.58183875e-3, rel=1e-6)  # fusion.2 alone: not multiply_reduce_fusion
    assert trace.op_s_per_call([]) == 0.0 and trace.op_s_per_call(["no_such_kernel"]) == 0.0
    assert all(name and seconds > 0 for name, seconds in trace.idle_gaps())


def test_a_trace_without_the_annotation_reduces_to_nothing():
    assert xplane.reduce(RECORDED, annotation="an annotation nobody wrote") is None


def test_union_and_clipped_length():
    merged = xplane.union([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert merged == [[0, 4], [5, 6]]
    assert xplane.clipped_length(merged, 1, 5.5) == pytest.approx(3.5)


def test_self_times_take_the_children_out():
    events = [("%while.1 = () while()", 0.0, 10.0), ("%k.1 = f32[2]{0} custom-call()", 1.0, 4.0),
              ("%k.1 = f32[2]{0} custom-call()", 5.0, 9.0), ("%after = f32[] add()", 10.0, 11.0)]
    got = {}
    for text, secs, _ in xplane.self_times(events):
        got[xplane.op_name(text)] = got.get(xplane.op_name(text), 0.0) + secs
    assert got == {"while.1": pytest.approx(3.0), "k.1": pytest.approx(7.0), "after": pytest.approx(1.0)}
    assert xplane.op_label("%k.1 = (f32[2]{0:T(128)}, s32[]{:S(2)}) custom-call(f32[2]{0} %x)") == "k.1 (f32[2], s32[])"


def test_a_device_clock_that_runs_early_or_late_is_shifted_into_the_annotated_calls():
    calls = [(10.0, 20.0), (20.5, 30.0)]
    device = lambda s0: [{"busy": [[s0, s0 + 8.0], [s0 + 10.5, s0 + 18.0]]}]
    assert xplane.clock_shift(calls, device(11.0)) == 0.0  # inside: left alone
    assert xplane.clock_shift(calls, device(9.0)) == pytest.approx(1.0)  # starts before its call: moved to its start
    assert xplane.clock_shift(calls, device(12.5)) == pytest.approx(-0.5)  # ends after the last fence: moved back
