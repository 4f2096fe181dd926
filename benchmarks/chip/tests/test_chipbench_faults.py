"""Runs of both cells on the CPU, with the chip check skipped: sound, they
come out correct; with the timed path broken underneath, not correct.

The faults a cell of one chip can have: a step that returns its state
unchanged, half of each chunk left out, and an answer altered where it is
produced.  The cells run here at small sizes (chunks of 128 events, a 512
slot ring for Q3 at a hundredth of the published rate), which the Pallas
interpreter gets through in seconds.  ``stock_q3_r512`` keeps its own rate
and ring, and its run outlasts a 30 s window of stock time, so expiry is
compared here too.
"""
import json
import os
import shutil
import sys
import time

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
from chipbench import drive, spec  # noqa: E402

CELLS = ("stock_q3.replay", "stock_q3_r512.replay", "synth_seq5.replay",
         "synth_seq5.steady")
REPLAY = ("stock_q3.replay", "stock_q3_r512.replay", "synth_seq5.replay")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dst = tmp_path_factory.mktemp("chip") / "chip"
    shutil.copytree(CHIP, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    small = {"stock_q3": dict(chunk_len=128, lane_cap=64, ring=512,
                              arena_capacity=8192),
             "stock_q3_r512": dict(chunk_len=128, lane_cap=64,
                                   arena_capacity=8192),
             "synth_seq5": dict(chunk_len=128)}
    for name, engine in small.items():
        cfg = spec.load_config(name, str(dst))
        cfg["engine"].update(engine)
        if name == "stock_q3":
            cfg["generator"]["events_per_sec"] = 48.03
        with open(dst / "configs" / f"{name}.json", "w") as f:
            json.dump(cfg, f)
    mix = spec.load_mix("steady", str(dst))
    mix["rate"] = 400.0
    with open(dst / "traffic" / "steady.json", "w") as f:
        json.dump(mix, f)
    return str(dst)


def alter_answer(engine):
    """Add one to the first count of every chunk the engine returns."""
    name = "feed_keyed" if hasattr(engine, "key_attrs") else "feed_attrs"
    feed = getattr(engine, name)

    def altered(*a, **kw):
        counts, hits = feed(*a, **kw)
        counts = counts.copy()
        counts.reshape(-1)[0] += 1
        return counts, hits
    setattr(engine, name, altered)


def keep_state(engine):
    """The compiled step computes its counts but hands back the state it
    was given.  The tECS arena alone still advances: the host's walk over
    a node store that never grows does not end, so an unchanged arena
    shows as a run that times out, not as a wrong answer."""
    import jax

    def kept(old, new):
        if isinstance(new, dict) and "arena" in new:
            return dict(old, arena=new["arena"])
        return old
    if hasattr(engine, "key_attrs"):
        impl = engine._part_step_impl
        engine._step = jax.jit(lambda *a: (lambda r: (
            r[0], kept(a[2], r[1]), r[2]))(impl(*a)))
    else:
        impl = engine._step_impl
        engine._step = jax.jit(lambda *a: (lambda r: (
            r[0], kept(a[1], r[1])))(impl(*a)))


def drop_half(svc):
    """Every other event of each chunk never reaches the engine."""
    from repro.core import Event
    encode = svc.adapter.encode
    svc.adapter.encode = lambda events: encode(
        [e if i % 2 == 0 else Event("__pad__", {})
         for i, e in enumerate(events)])


FAULTS = {"answer_altered": {"plant": alter_answer},
          "state_unchanged": {"plant": keep_state},
          "half_left_out": {"plant": lambda e: None, "plant_service": drop_half}}


def run_cell(root, cell, faults=None, trace=False):
    bench = spec.load_benchmark()
    # stock_q3_r512's window has to outlast several 30 s windows of stock
    # time (282 events each at 9.4 events/s)
    seconds = 3.0 if cell.startswith("stock_q3_r512") else 1.5
    out, code = drive.execute(bench, spec.find_cell(bench, cell), seed=2 ** 31 + 99,
                              seconds=seconds, trace=trace,
                              t_start=time.perf_counter(), root=root,
                              faults=faults)
    return out, code


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell):
    out, code = run_cell(root, cell)
    assert code == 0
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]
    if cell.startswith("stock_q3_r512"):
        assert out["attempted"] * 1000.0 / 9.380859375 > 4 * 30000.0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", REPLAY)
def test_broken_path_is_not_correct(root, cell, fault):
    out, _ = run_cell(root, cell, faults=FAULTS[fault])
    assert not out["correct"], out["checks"]


def test_traced_run_reports_per_layer_metrics(root):
    out, code = run_cell(root, "synth_seq5.replay", trace=True)
    assert code == 0 and out["correct"]
    assert "host.cpu_ms_per_kev" in out["metrics"]
    assert set(out["device"]) >= {"busy_s", "window_s", "memory_peak_bytes"}
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
