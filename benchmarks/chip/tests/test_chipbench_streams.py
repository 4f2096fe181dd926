"""The generators: seeded, block-independent, with the source's shapes."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import spec, streams  # noqa: E402

BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("config", ["stock_q3", "synth_seq5"])
def test_same_seed_same_stream_other_seed_other(config):
    gen = spec.load_config(config)["generator"]
    a, b, c = (streams.Stream(gen, s) for s in (BIG_SEED, BIG_SEED, 7))
    for s in (a, b, c):
        s.grow(5000)
    assert all(np.array_equal(a.columns[k], b.columns[k]) for k in a.columns)
    assert not np.array_equal(a.columns["type"], c.columns["type"])
    assert a.raw(4999) == b.raw(4999)


def test_growth_in_blocks_does_not_change_the_stream():
    gen = spec.load_config("stock_q3")["generator"]
    one = streams.Stream(gen, 3)
    one.grow(3 * streams.BLOCK)
    lazy = streams.Stream(gen, 3)
    assert lazy.raw(2 * streams.BLOCK + 17) == one.raw(2 * streams.BLOCK + 17)


def test_negative_and_huge_seeds_are_seeds():
    gen = spec.load_config("synth_seq5")["generator"]
    for seed in (-1, 2 ** 70):
        streams.Stream(gen, seed).grow(10)


def test_stock_stream_shape():
    """stock_stream: 8 names, BUY/SELL, 4 volumes and price U(5, 50) to the
    cent, each uniform; the clock advances 1000/4803 ms an event."""
    gen = spec.load_config("stock_q3")["generator"]
    s = streams.Stream(gen, 11)
    n = 4 * streams.BLOCK
    s.grow(n)
    c = s.columns
    assert s.type_names == ["BUY", "SELL"]
    for col, k in (("type", 2), ("name", 8), ("volume", 4)):
        _, counts = np.unique(c[col], return_counts=True)
        assert len(counts) == k
        # each value within 5 binomial sd of n/k
        sd = np.sqrt(n * (1 / k) * (1 - 1 / k))
        assert np.all(np.abs(counts - n / k) < 5 * sd), (col, counts)
    assert set(np.unique(c["volume"])) == {100.0, 200.0, 500.0, 1000.0}
    assert c["price"].min() >= 5.0 and c["price"].max() <= 50.0
    assert np.allclose(c["price"], np.round(c["price"], 2))
    step = np.diff(c["stock_time"])
    assert np.allclose(step, 1000.0 / 4803.0)
    assert c["stock_time"][0] == pytest.approx(1000.0 / 4803.0)
    raw = s.raw(0)
    assert set(raw) == {"type", "name", "volume", "price", "stock_time"}
    assert isinstance(raw["name"], str) and isinstance(raw["volume"], float)


def test_random_stream_shape():
    """RandomStream: A1..A5 and B1..B6, each equally likely."""
    gen = spec.load_config("synth_seq5")["generator"]
    s = streams.Stream(gen, 5)
    n = 4 * streams.BLOCK
    s.grow(n)
    assert s.type_names == [f"A{i}" for i in range(1, 6)] + \
        [f"B{i}" for i in range(1, 7)]
    counts = np.bincount(s.columns["type"], minlength=11)
    sd = np.sqrt(n * (1 / 11) * (10 / 11))
    assert np.all(np.abs(counts - n / 11) < 5 * sd), counts
    assert s.raw(3) == {"type": s.type_names[s.columns["type"][3]]}
