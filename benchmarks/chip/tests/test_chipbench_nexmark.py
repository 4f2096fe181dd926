"""The NEXmark generator and the ``nexmark_newuser`` configuration: Beam's
proportions and hot keys, the integer-ms clock, the plain reference
against the program's host engine, and the control failing."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import control, reference, spec, streams  # noqa: E402

CFG = spec.load_config("nexmark_newuser")
BIG_SEED = 2 ** 31 + 12345


def stream(n, seed=BIG_SEED):
    s = streams.Stream(CFG["generator"], seed)
    s.grow(n)
    return s


def test_proportions_are_one_person_three_auctions_46_bids_in_50():
    s = stream(50_000)
    kinds = s.columns["type"][:50_000].reshape(-1, 50)
    assert s.type_names == ["PERSON", "AUCTION", "BID"]
    assert (kinds[:, :1] == 0).all() and (kinds[:, 1:4] == 1).all()
    assert (kinds[:, 4:] == 2).all()


def test_the_hot_bidder_has_69_percent_of_the_events():
    """3 of 4 bids come from the hot bidder, the second person of the
    newest batch of 100: 46/50 x 3/4 = 0.69 of all events."""
    n = 200_000
    s = stream(n)
    eid = np.arange(n)
    newest = eid // 50                      # one person per 50 events
    hot = newest // 100 * 100 + 1 + 1000
    c = s.columns
    share = np.mean((c["type"][:n] == 2) & (c["person"][:n] == hot))
    assert abs(share - 0.69) < 0.01, share
    # the hot seller (first of the batch) opens 3 of 4 auctions
    sells = c["type"][:n] == 1
    seller_share = np.mean(c["person"][:n][sells] == (hot - 1)[sells])
    assert abs(seller_share - 0.75) < 0.01, seller_share
    # every event has a key: a person id
    assert (c["person"][:n] >= 1000).all()


def test_the_clock_is_integer_ms_with_ten_events_a_tick():
    n = 100_000
    s = stream(n)
    t = s.columns["date_time"][:n]
    assert np.issubdtype(t.dtype, np.integer) and t[0] == 0
    assert (np.diff(t) >= 0).all()
    _, ties = np.unique(t, return_counts=True)
    assert (ties == 10).all()
    raw = s.raw(12_345)
    assert raw["date_time"] == 1234 and isinstance(raw["date_time"], int)


def test_reference_equals_the_host_engine_on_a_prefix():
    from repro.core import Event, compile_query
    from repro.core.engine import Engine
    from repro.core.partition import PartitionedEngine
    n = 20_000
    s = stream(n, seed=7)
    cq = compile_query(CFG["query"])
    eng = PartitionedEngine(lambda: Engine(cq.cea, window=cq.query.window),
                            cq.query.partition_by)
    host = np.zeros(n, np.int64)
    for i in range(n):
        r = s.raw(i)
        for ce in eng.process(Event(r["type"], {k: v for k, v in r.items()
                                                if k != "type"})):
            host[ce.end] += 1
    want, _ = reference.evaluate(CFG["reference"], s.select(np.arange(n)),
                                 s.type_names, n)
    assert want.sum() > 0 and np.array_equal(want, host)


@pytest.mark.parametrize("seed", [3, 987654321])
def test_control_fails_and_reference_passes(seed):
    """bfloat16 prices and timestamps: bids just under $100,000 round up
    over the threshold (date_time alone moves no count: a person's chains
    end well inside the 10 s window).  About one wrong count a 200,000
    events; seed 2**31 + 1 reads none until 800,000 events, the size of a
    chip run."""
    n = 400_000
    sound = control.readings(CFG, seed, n, control=False)
    assert all(v == 0 for v, _ in sound.values()), sound
    broken = control.readings(CFG, seed, n, control=True)
    assert broken["count_diff"][0] > 0, broken
