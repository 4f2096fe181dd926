"""The end-to-end arithmetic and the per-layer readers, on made-up runs."""
import os
import sys

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
from chipbench import drive, spec  # noqa: E402


def run_with(chunks, t_close=10.0, **kw):
    return drive.Run(seconds=t_close, t_open=0.0,
                     t_close=t_close, chunks=chunks, **kw)


def test_a_stall_in_the_window_lowers_the_rate():
    steady = run_with([(i, i + 1.0, 512) for i in range(10)])
    stalled = run_with([(i, i + 1.0 + (3.0 if i >= 4 else 0.0), 512)
                        for i in range(10)])
    assert drive.events_per_s(steady) == pytest.approx(10 * 512 / 10.0)
    # a 3 s stall from the fifth chunk on: seven chunks by the first
    # completion at or after the close, at 10 s
    assert drive.events_per_s(stalled) == pytest.approx(7 * 512 / 10.0)
    assert drive.events_per_s(stalled) < drive.events_per_s(steady)


def test_a_stall_at_the_end_of_the_window_lowers_the_rate():
    """Chunks complete each second up to 7 s, then nothing until 11 s: the
    span runs to that completion past the close, not to the last one
    before it."""
    steady = run_with([(i, i + 1.0, 512) for i in range(12)])
    stalled = run_with([(i, i + 1.0, 512) for i in range(7)]
                       + [(7.0, 11.0, 512), (11.0, 12.0, 512)])
    assert drive.events_per_s(stalled) == pytest.approx(8 * 512 / 11.0)
    assert drive.events_per_s(steady) == pytest.approx(512.0)
    assert drive.events_per_s(stalled) < 0.75 * drive.events_per_s(steady)
    # nothing completes after the close: the idle end counts up to it
    idle_end = run_with([(i, i + 1.0, 512) for i in range(7)])
    assert drive.events_per_s(idle_end) == pytest.approx(7 * 512 / 10.0)


def test_the_rate_counts_from_the_window_opening():
    """A long first step is not left out: the time runs from the opening,
    not from the first completion; a step longer than the window is
    counted whole, over its own length."""
    late = run_with([(0.0, 6.0, 512), (6.0, 10.5, 512), (10.5, 15.0, 512)])
    assert drive.events_per_s(late) == pytest.approx(1024 / 10.5)
    assert drive.events_per_s(run_with([(0.0, 31.0, 512)])) == \
        pytest.approx(512 / 31.0)
    assert drive.events_per_s(run_with([])) is None


def test_tails_are_taken_over_all_hits_not_chunk_medians():
    # ten chunks of hits: nine fast chunks, one slow one with many hits
    lat = [0.010] * 90 + [0.100] * 20
    run = run_with([], hit_latency_s=lat)
    v = drive.end_to_end_values(run, setup_s=1.0)
    assert v["detect_p95_ms"] == pytest.approx(
        1e3 * np.percentile(lat, 95))
    assert v["detect_p95_ms"] == pytest.approx(100.0)
    # the median of per-chunk medians would have said 10 ms
    assert v["detect_p50_ms"] == pytest.approx(10.0)
    assert v["setup_s"] == 1.0


def test_percentile_interpolates_like_numpy():
    rng = np.random.default_rng(0)
    x = rng.exponential(size=333).tolist()
    for q in (0, 50, 95, 99, 100):
        assert drive.percentile(x, q) == pytest.approx(np.percentile(x, q))


def reader(name):
    return spec.load_reader(name)


def test_host_cpu_per_thousand_events():
    run = run_with([(0, 1, 512), (1, 2, 512), (2, 11, 512)], cpu_s=2.048)
    # 1,024 events completed in the window: 2.048 s / 1.024 kev
    assert reader("host.cpu_ms_per_kev")(run) == pytest.approx(2000.0)
    assert reader("host.cpu_ms_per_kev")(run_with([])) is None


def test_enumeration_time_per_complex_event():
    run = run_with([], enum_calls=[(1.0, 0.002, 100), (2.0, 0.001, 50),
                                   (12.0, 5.0, 1)])
    assert reader("enum.us_per_ce")(run) == pytest.approx(20.0)
    assert reader("enum.us_per_ce")(run_with([])) is None


def test_chunk_latency_p95_of_completed_chunks():
    chunks = [(i, i + 0.01 * (i + 1), 512) for i in range(9)] + \
        [(9.5, 30.0, 512)]
    got = reader("service.chunk_ms")(run_with(chunks))
    want = 1e3 * np.percentile([0.01 * (i + 1) for i in range(9)], 95)
    assert got == pytest.approx(want)


def test_trace_readers_read_nothing_without_a_trace():
    run = run_with([(0, 1, 512)])
    for name in ("device.idle_share", "step.device_ms",
                 "cer_fused_scan_roofline"):
        assert reader(name)(run) is None


def test_roofline_cost_counts_the_counting_work():
    cost = spec.load_roofline("cer_fused_scan").cost(
        {"T": 512, "B": 1, "A": 1, "W": 104, "S": 11, "NC": 6, "V": 32,
         "NQ": 1, "timed": 0, "trace": 0})
    assert cost["flops"] == 512 * (2 * 104 * 11 * 11 + 2 * 104 * 11)
    assert cost["bytes"] == 4 * (512 + 512 + 2 * 104 * 11 + 32 * 6
                                 + 6 * 121 + 11 + 11)
