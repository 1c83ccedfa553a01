"""The span readers: on a recorded chip trace of the program with its spans, and on made-up events.

``data/kmeans-fit30-3calls.xplane.pb`` is PR 24's chip trace (TPU v5 lite) of three calls of ``KMeans.fit`` on
(2^25, 32) f32, k = 8, 30 iterations, each inside a ``bench.call`` annotation: the program's own
``ht.call:KMeans.fit`` span in each, with ``ht.fetch:kmeans.inertia`` and ``ht.fetch:kmeans.n_iter`` inside it.
"""
import os

import pytest
from conftest import CHIP, ROOT

from harness import manifest, report, spans, xplane

RECORDED = os.path.join(CHIP, "tests", "data", "kmeans-fit30-3calls.xplane.pb")
NEW = ["call_self_ms.call", "fetch_ms.call", "idle_in_call_ms.call", "idle_outside_call_ms.call",
       "exchanges.call", "exchange_ms.call", "clock_slack_ms"]


def run_of(trace, calls=0):
    return report.TracedRun(config={}, chips=1, work={}, peaks=None, calls=calls, counters={}, trace=trace)


def read(name, trace):
    return manifest.load_module("layer_metrics", name).read(run_of(trace, len(trace.calls) if trace else 0))


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce(RECORDED)


# ---- the recorded trace
def test_recorded_span_tree(trace):
    mine = sorted((s, e, name) for name, s, e in trace.host if name.startswith("ht."))
    assert [name for _, _, name in mine] == ["ht.call:KMeans.fit", "ht.fetch:kmeans.inertia", "ht.fetch:kmeans.n_iter"] * 3
    for (bs, be), (cs, ce, _), *fetches in zip(trace.calls, mine[0::3], mine[1::3], mine[2::3]):
        assert bs <= cs and ce <= be  # the program's span inside the harness's annotation
        assert all(cs <= s and e <= ce for s, e, _ in fetches)  # and its fetches inside it
    found = spans.of(trace)
    assert found.calls == 3 and found.exchanges == 0 and found.exchange_s == 0.0
    assert 0 < found.self_s < found.fetch_s < found.call_s < trace.window_s
    assert found.self_s == pytest.approx(found.call_s - found.fetch_s, abs=1e-12)  # every fetch lies in a call


def test_recorded_idle_split_sums_to_the_windows_idle(trace):
    found = spans.of(trace)
    assert found.idle_in_call_s + found.idle_outside_call_s == pytest.approx(trace.window_s - trace.busy_s, abs=1e-9)
    per_call = (read("idle_in_call_ms.call", trace) + read("idle_outside_call_ms.call", trace)) * 1e-3
    assert per_call == pytest.approx((trace.window_s - trace.busy_s) / 3, abs=1e-9)
    assert found.idle_in_call_s > found.idle_outside_call_s > 0  # the fetches' round trips, then the client's turn-around


def test_recorded_clock_slack_is_reproduced(trace):
    launches = sorted(s for name, s, _ in trace.host if name == spans.LAUNCH)
    notices = sorted(s for name, s, _ in trace.host if name == spans.COMPLETIONS[0])
    programs = sorted((s, e) for _, s, e in trace.devices[0]["modules"])
    assert len(launches) == len(notices) == len(programs) > 3
    lo = max([a - s for a, (s, _) in zip(launches, programs)] + [trace.window[0] - programs[0][0]])
    hi = min([n - e for n, (_, e) in zip(notices, programs)] + [trace.window[1] - programs[-1][1]])
    shift, slack = spans.placement(trace)
    assert lo < hi and slack == pytest.approx(hi - lo, abs=1e-12) and shift == pytest.approx(0.5 * (lo + hi), abs=1e-12)
    assert read("clock_slack_ms", trace) == pytest.approx((hi - lo) * 1e3, abs=1e-9)
    assert 0 < slack < 2e-3  # the profiler sets the clocks to a millisecond or so


def test_recorded_per_call_metrics(trace):
    found = spans.of(trace)
    assert read("call_self_ms.call", trace) == pytest.approx(found.self_s / 3 * 1e3)
    assert read("fetch_ms.call", trace) == pytest.approx(found.fetch_s / 3 * 1e3)
    assert read("exchanges.call", trace) == 0 and read("exchange_ms.call", trace) == 0.0


# ---- made-up events
def made_up(host, busy, calls=((0.0, 10.0), (10.0, 20.0)), modules=None):
    modules = modules if modules is not None else [("jit_f", s, e) for s, e in busy]
    return xplane.Trace(calls=list(calls), host=host,
                        devices=[{"name": "/device:TPU:0", "modules": modules, "ops": [], "busy": [list(b) for b in busy]}])


def launched(busy, early=0.5, late=0.5):
    return [(spans.LAUNCH, s - early, s - early + 0.1) for s, _ in busy] + [(spans.COMPLETIONS[0], e + late, e + late + 0.1) for _, e in busy]


def test_interval_arithmetic():
    assert spans.complement([[1, 2], [3, 5]], 0, 4) == [(0, 1), (2, 3)]
    assert spans.complement([], 0, 4) == [(0, 4)] and spans.complement([[0, 4]], 0, 4) == []
    assert spans.overlap([(0, 2), (3, 6)], [(1, 4), (5, 9)]) == pytest.approx(3.0)
    assert spans.overlap([(0, 1)], []) == 0.0


def test_made_up_tree_self_time_and_idle_split():
    busy = [(2.0, 6.0), (12.0, 18.0)]
    host = launched(busy) + [
        ("ht.call:f", 1.0, 8.0), ("ht.fetch:a", 3.0, 7.0), ("ht.exchange:bucket_move", 1.5, 2.0), ("ht.call:nested", 1.2, 1.4),
        ("ht.call:f", 11.0, 19.0), ("ht.fetch:a", 12.0, 18.5), ("ht.fetch:inner", 13.0, 14.0), ("unrelated", 0.0, 20.0)]
    found = spans.of(made_up(host, busy))
    assert (found.calls, found.exchanges) == (2, 1)
    assert found.call_s == pytest.approx(15.0) and found.fetch_s == pytest.approx(10.5) and found.exchange_s == pytest.approx(0.5)
    assert found.self_s == pytest.approx(15.0 - 10.5 - 0.5)  # a nested call and a nested fetch are not taken out twice
    assert found.slack_s == pytest.approx(1.0)  # early 0.5 + late 0.5, and the device stood in the middle already
    # idle 0-2, 6-12, 18-20; the calls cover 1-8 and 11-19
    assert found.idle_in_call_s == pytest.approx(1.0 + 2.0 + 1.0 + 1.0)
    assert found.idle_outside_call_s == pytest.approx(1.0 + 3.0 + 1.0)


def test_the_device_is_placed_at_the_middle_of_what_the_host_allows():
    busy = [(2.0, 6.0), (12.0, 18.0)]
    host = launched(busy, early=0.2, late=1.0) + [("ht.call:f", 1.0, 6.5), ("ht.call:f", 11.0, 18.5)]
    trace = made_up(host, busy)
    shift, slack = spans.placement(trace)
    assert shift == pytest.approx(0.4) and slack == pytest.approx(1.2)  # allowed: -0.2 .. +1.0
    found = spans.of(trace)
    # busy stands at 2.4-6.4 and 12.4-18.4: idle 0-2.4, 6.4-12.4, 18.4-20 against calls 1-6.5 and 11-18.5
    assert found.idle_in_call_s == pytest.approx(1.4 + 0.1 + 1.4 + 0.1)
    assert found.idle_in_call_s + found.idle_outside_call_s == pytest.approx(20.0 - 10.0)


def test_no_pairing_no_placement_and_no_idle_split():
    busy = [(2.0, 6.0), (12.0, 18.0)]
    calls = [("ht.call:f", 1.0, 8.0), ("ht.call:f", 11.0, 19.0)]
    one_launch_short = made_up(launched(busy)[1:] + calls, busy)
    assert spans.placement(one_launch_short) is None
    found = spans.of(one_launch_short)
    assert found.slack_s is None and found.idle_in_call_s is None and found.idle_outside_call_s is None
    assert found.call_s == pytest.approx(15.0)  # what needs no device clock is still read
    assert read("clock_slack_ms", one_launch_short) is None and read("idle_in_call_ms.call", one_launch_short) is None
    assert read("call_self_ms.call", one_launch_short) == pytest.approx(7.5e3)


def test_a_program_that_writes_no_span_reads_as_nothing():
    busy = [(2.0, 6.0), (12.0, 18.0)]
    parent = made_up(launched(busy), busy)
    assert spans.of(parent) is None
    assert [read(name, parent) for name in NEW[:-1]] == [None] * 6
    assert read("clock_slack_ms", parent) == pytest.approx(1e3)  # the trace's own: needs no span


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_returns_none_without_a_trace(name):
    assert read(name, None) is None  # the --rehearse path


def test_the_manifest_lists_the_seven_last_and_as_program_spans():
    entries = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]
    assert [m["name"] for m in entries[-7:]] == NEW
    assert all(m["source"] == "program_span" and m["moves"] == "call_ms.p50" and m["better"] == "lower" for m in entries[-7:])
