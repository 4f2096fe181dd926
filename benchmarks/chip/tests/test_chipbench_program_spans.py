"""The readers of the program's own host spans (``service.*``, ``host.gc``).

The program records them in the profiler's trace, on the device's clock,
and the harness's loader keeps them among the host spans.
``data/trace_r512.json`` holds two steps of the ``stock_q3_r512`` cell with
a checkpoint between them, recorded on one TPU v5e with the program's
spans; trimmed to those steps (the window span is put round them), with
the operations nested in another one and shorter than 50 µs left out
(their time falls to the operation holding them), host spans kept where
they are the program's, the benchmark's or 1 ms or longer, and operation
names cut to their instruction names.  The made-up trace below checks the
arithmetic by hand.
"""
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
from chipbench import drive, spec, tracing  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
READERS = ("service.starved_share", "service.encode_ms",
           "service.durable_ms", "host.gc_share")


def made_up():
    """Two chunks of a served engine in a 100 ms window (times in tenths of
    a millisecond): the encoder thread's encodes, the device thread's wait,
    step (holding its readback, log and checkpoint) and delivery, and a
    full collection."""
    def span(name, line, s, e):
        return (name, line, s * MS // 10, e * MS // 10)
    dev, enc = "svc-device", "svc-encode"
    return tracing.Trace(
        ops={0: [("fusion.1", 0, 30 * MS), ("fusion.2", 50 * MS, 80 * MS)]},
        modules={0: [("jit__part_step_impl(7)", 0, 30 * MS),
                     ("jit__part_step_impl(7)", 50 * MS, 80 * MS)]},
        host=[(tracing.WINDOW_SPAN, "python", 0, 100 * MS),
              span("service.encode", enc, -80, -20),
              span("service.encode", enc, 350, 410),
              span("service.encode", enc, 700, 740),
              span("service.step", dev, 0, 450),
              span("engine.readback", dev, 280, 380),
              span("service.log", dev, 380, 390),
              span("service.checkpoint", dev, 390, 450),
              span("service.deliver", dev, 450, 460),
              span("service.wait_input", dev, 460, 500),
              span("service.step", dev, 500, 810),
              span("engine.readback", dev, 780, 800),
              span("service.log", dev, 800, 805),
              span("service.deliver", dev, 810, 820),
              span("service.wait_input", dev, 820, 1100),
              span("host.gc", "python", 850, 950)])


def read(name, trace):
    return spec.load_reader(name)(drive.Run(seconds=0.1, trace=trace))


def test_readers_by_hand():
    tr = made_up()
    # waits 46–50 and 82–100 ms (clipped at the window's end)
    assert read("service.starved_share", tr) == pytest.approx(22.0)
    # the encodes that started in the window: 6 and 4 ms
    assert read("service.encode_ms", tr) == pytest.approx(5.0)
    # log 1 + checkpoint 6 + log 0.5 ms over the two steps that ended
    assert read("service.durable_ms", tr) == pytest.approx(3.75)
    assert read("host.gc_share", tr) == pytest.approx(10.0)


def test_a_window_without_a_collection_reads_zero():
    tr = made_up()
    tr.host = [h for h in tr.host if h[0] != "host.gc"]
    assert read("host.gc_share", tr) == 0.0


def test_nothing_to_read_without_the_programs_spans():
    """A program that records no span of its own (the one before them)
    leaves every reader silent; the existing readers are unaffected."""
    tr = tracing.load_json(os.path.join(DATA, "trace_synth_seq5.json"))
    assert all(read(m, tr) is None for m in READERS)
    assert read("device.idle_share", tr) == pytest.approx(76.422656516226)
    bare = made_up()
    bare.host = bare.host[:1]
    assert all(read(m, bare) is None for m in READERS)


def test_recorded_chip_trace_with_the_programs_spans():
    """Two ``stock_q3_r512`` steps on the chip, a 21 MB checkpoint copied to
    the host between them: the durable path's time is the checkpoint's."""
    tr = tracing.load_json(os.path.join(DATA, "trace_r512.json"))
    assert tracing.module_time(tr, r"_step_impl")[1] == 2
    assert read("step.device_ms", tr) == pytest.approx(429.1636875)
    (ckpt,) = [h for h in tr.host if h[0] == "service.checkpoint"]
    durable = sum(e - s for n, _, s, e in tr.host
                  if n in ("service.log", "service.checkpoint"))
    assert ckpt[3] - ckpt[2] > 0.95 * durable
    assert read("service.durable_ms", tr) == pytest.approx(durable / 2e6)
    assert read("service.durable_ms", tr) == pytest.approx(9.0433455)
    assert read("service.encode_ms", tr) == pytest.approx(5.957414)
    # the device thread waits for input for almost none of the window
    assert read("service.starved_share", tr) == pytest.approx(0.010510124)
    assert read("host.gc_share", tr) == pytest.approx(0.029644329)
    # the gap the checkpoint leaves lies in the step that takes it
    lo, hi = ckpt[2], ckpt[3]
    gap, width = tracing.idle_gaps(tr)[0]
    assert gap.startswith("service.step") and width * 1e9 >= hi - lo
