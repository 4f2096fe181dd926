"""The reduction from a profiler trace to idle share, step and kernel time.

``data/trace_synth_seq5.json`` is a trace of six 512-event chunks of the
``synth_seq5`` cell's served path, recorded on one TPU v5e and trimmed to
the window (``tracing.Trace`` form).  The made-up trace below checks the
arithmetic by hand.
"""
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
from chipbench import drive, spec, tracing  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_synth_seq5.json")
MS = 1_000_000


def made_up():
    """A 100 ms window: two steps of 20 ms, a kernel inside each, overlapping
    ops, and a host submit span over the longest gap."""
    return tracing.Trace(
        ops={0: [("fusion.1", 5 * MS, 15 * MS), ("cer_fused_scan", 10 * MS,
                                                  25 * MS),
                 ("fusion.2", 60 * MS, 70 * MS),
                 ("cer_fused_scan", 65 * MS, 80 * MS),
                 ("copy", 150 * MS, 160 * MS)]},       # after the window
        modules={0: [("jit__step_impl(3)", 5 * MS, 25 * MS),
                     ("jit__step_impl(3)", 60 * MS, 80 * MS),
                     ("jit_other", 90 * MS, 95 * MS),
                     # started after the window: not the window's step
                     ("jit__step_impl(3)", 150 * MS, 170 * MS)]},
        host=[(tracing.WINDOW_SPAN, "python", 0, 100 * MS),
              ("bench.submit", "python", 26 * MS, 58 * MS),
              ("bench.sink", "svc-device", 81 * MS, 82 * MS)])


def test_union_merges_overlaps_and_nesting():
    spans = [("a", 0, 10), ("b", 5, 8), ("c", 9, 20), ("d", 30, 40)]
    assert tracing.union(spans) == [(0, 20), (30, 40)]


def test_made_up_trace_by_hand():
    tr = made_up()
    assert tracing.window_seconds(tr) == pytest.approx(0.100)
    # busy: 5–25 and 60–80 ms
    assert tracing.busy_seconds(tr) == pytest.approx(0.040)
    assert tracing.idle_share(tr) == pytest.approx(60.0)
    assert tracing.module_time(tr, r"_step_impl") == (pytest.approx(0.040), 2)
    # a step that starts in the window and ends after it counts whole
    tr.modules[0].append(("jit__step_impl(3)", 90 * MS, 130 * MS))
    assert tracing.module_time(tr, r"_step_impl") == (pytest.approx(0.080), 3)
    assert tracing.op_time(tr, "cer_fused_scan") == (pytest.approx(0.030), 2)
    # self time: fusion.1 runs 5–15 ms, the kernel 10–25 ms over it
    assert tracing.top_ops(tr)[0] == ("cer_fused_scan", pytest.approx(0.030))
    gaps = tracing.idle_gaps(tr)
    assert gaps[0] == ("bench.submit [python]", pytest.approx(0.035))
    assert [g[1] for g in gaps] == pytest.approx([0.035, 0.020, 0.005])


def test_trace_readers_on_made_up_trace():
    run = drive.Run(seconds=0.1, trace=made_up(),
                    peaks=spec.load_peaks("TPU v5 lite"),
                    kernel_shapes={"cer_fused_scan": {
                        "T": 512, "B": 1, "A": 1, "W": 104, "S": 11,
                        "NC": 6, "V": 32, "NQ": 1}})
    assert spec.load_reader("device.idle_share")(run) == pytest.approx(60.0)
    assert spec.load_reader("step.device_ms")(run) == pytest.approx(20.0)
    share = spec.load_reader("cer_fused_scan_roofline")(run)
    cost = spec.load_roofline("cer_fused_scan").cost(
        run.kernel_shapes["cer_fused_scan"])
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert share == pytest.approx(100 * 2 * least / 0.030)


def test_self_time_leaves_out_nested_operations():
    spans = [("%while.1 = (s32[]) while(...)", 0, 100),
             ("%cond.2 = s32[] conditional(...)", 10, 60),
             ("%fusion.3 = f32[8] fusion(...)", 20, 50),
             ("%copy.4 = f32[8] copy(...)", 70, 80)]
    own = dict(tracing.self_times(spans))
    assert own == {spans[0][0]: 40, spans[1][0]: 20, spans[2][0]: 30,
                   spans[3][0]: 10}
    assert tracing.short_name(spans[2][0]) == "fusion.3"


def test_a_trace_without_the_window_span_is_refused():
    tr = made_up()
    tr.host = tr.host[1:]
    with pytest.raises(ValueError):
        tracing.window_seconds(tr)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    tr = tracing.load_json(RECORDED)
    assert tracing.devices(tr) == [0]
    window = tracing.window_seconds(tr)
    busy = tracing.busy_seconds(tr)
    assert 0 < busy < window
    assert tracing.idle_share(tr) == pytest.approx(100 * (1 - busy / window))
    secs, steps = tracing.module_time(tr, r"_step_impl")
    assert steps == 6 and 0 < secs <= busy
    k_secs, calls = tracing.op_time(tr, "cer_fused_scan")
    assert calls == 6 and 0 < k_secs <= secs
    gaps = tracing.idle_gaps(tr)
    assert gaps and sum(g[1] for g in gaps) <= window - busy + 1e-9
