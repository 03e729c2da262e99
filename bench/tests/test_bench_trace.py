"""Trace reduction: interval arithmetic, gap attribution, and the numbers
a small trace recorded on one v5e (``record_trace.py``) reduces to."""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.xplane.pb")


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]
    assert tr.union([]) == []


def test_clip_cuts_to_the_window():
    assert tr.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


def test_gap_goes_to_the_innermost_span_or_to_compile():
    spans = [(0.0, 10.0, "dispatch"), (2.0, 5.0, "generate")]
    assert tr._label(3.0, 4.0, spans, []) == "generate"
    assert tr._label(6.0, 7.0, spans, []) == "dispatch"
    assert tr._label(11.0, 12.0, spans, []) == "host"
    assert tr._label(3.0, 4.0, spans, [(2.9, 3.8)]) == "compile"


def test_gap_totals_average_over_devices():
    red = tr.Reduced(window=(0.0, 1.0), n_devices=2, busy_s=0.5, ops={},
                     kernels={},
                     gaps=[(0.4, "wait"), (0.2, "wait"), (0.3, "score")])
    assert tr.gap_totals(red) == [("wait", pytest.approx(0.3)),
                                  ("score", pytest.approx(0.15))]


@pytest.fixture(scope="module")
def small():
    if not os.path.exists(DATA):
        pytest.fail(f"missing recorded trace {DATA}")
    return tr.reduce(DATA)


def test_recorded_trace_window_and_busy_time(small):
    assert small.n_devices == 1
    # The window holds three matmul steps, three kernel calls and a
    # 0.2 s sleep.
    assert 0.2 < small.window_s < 5.0
    assert 0.0 < small.busy_s < small.window_s - 0.19
    assert sum(small.ops.values()) >= small.busy_s * 0.999


def test_recorded_trace_ops_and_kernel(small):
    # The device's clock leads the host's by about a millisecond here, so
    # the first step can start before the window span does.
    whole = tr.reduce(DATA, window_span=None)
    durations = whole.kernel_durations("_router_xattn_pool_jit")
    assert len(durations) == 3
    assert all(0 < d < 1e-3 for d in durations)
    assert small.kernel_durations("_router_xattn_pool_jit")
    assert whole.busy_s >= small.busy_s > 0
    top = dict(tr.top_ops(whole, n=3))
    assert len(top) == 3 and all(v > 0 for v in top.values())


def test_recorded_trace_idle_gaps_named_by_the_host_span(small):
    totals = dict(tr.gap_totals(small))
    assert max(totals, key=totals.get) == "wait"
    assert totals["wait"] == pytest.approx(0.2, abs=0.05)
    assert sum(totals.values()) == pytest.approx(
        small.window_s - small.busy_s, rel=1e-6)


def test_window_can_be_cut_to_its_first_seconds(small):
    cut = tr.reduce(DATA, window_s=small.window_s / 2)
    assert cut.window_s == pytest.approx(small.window_s / 2)
    assert cut.busy_s <= small.busy_s
