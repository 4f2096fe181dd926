"""The control comes out not correct, at a size a test run holds.

Each configuration names a control: the plain reference in the program's
place with one stated guarantee broken (``chipbench/control.py``).  The
same comparison that judges a run has to fail it, and has to pass the
reference itself.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import control, spec  # noqa: E402

SEEDS = (3, 2 ** 31 + 1, 987654321)
# stock_q3_r512: 16,384 events are 29 min of stock time, 58 windows; the
# lower precision shows at the window's edge, so it needs many of them
EVENTS = {"stock_q3": 1536, "stock_q3_r512": 16384, "synth_seq5": 20480}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config", sorted(EVENTS))
def test_control_fails_and_reference_passes(config, seed):
    cfg = spec.load_config(config)
    sound = control.readings(cfg, seed, EVENTS[config], control=False)
    assert all(v == 0 for v, _ in sound.values()), sound
    broken = control.readings(cfg, seed, EVENTS[config], control=True)
    failing = {k: v for k, (v, lim) in broken.items() if v > lim}
    assert failing, broken
    # the number each control is there to move
    key = {"lower_precision": "count_diff",
           "enumerate_at_most": "ce_diff"}[cfg["control"]["kind"]]
    assert key in failing
