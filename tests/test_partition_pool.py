"""The key pool (``KeyPoolEngine``, DESIGN.md §6) against the host engine.

PARTITION BY person over the NEXmark stream at a small size: a 30 ms window,
20 active people, chunks of 64 events, a pool of 64 narrow slots with
16-start rings and 4 wide slots with 256-start rings.  Every count at every
position must equal ``core.partition.PartitionedEngine``'s (and the chip
benchmark's plain reference), whatever the pool does: reclaim, promote,
run a hot key's events in further waves.
"""
import os
import sys

import numpy as np
import pytest

from repro.core import Event, compile_query
from repro.core.engine import Engine
from repro.core.partition import PartitionedEngine
from repro.runtime import StreamService, cumulative_matches
from repro.vector import (KeyPoolEngine, PartitionedStreamingEngine,
                          PoolOverflowError, VectorEngine)

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
sys.path.insert(0, CHIP)
from chipbench import reference, spec, streams  # noqa: E402

Q = ("SELECT * FROM S WHERE PERSON AS p ; AUCTION AS a ; BID AS b "
     "FILTER b[price >= 100000] PARTITION BY [person] WITHIN 30 [date_time]")
REF = {"atoms": [{"type": "PERSON"}, {"type": "AUCTION"},
                 {"type": "BID", "where": [["price", ">=", 100000.0]]}],
       "partition_by": "person",
       "window": {"kind": "time", "size": 30.0, "attr": "date_time"},
       "consume": False}
CHUNK = 64


def pool(slots=64, wide_slots=4, strict=True, use_pallas=False):
    ve = VectorEngine(compile_query(Q), use_pallas=use_pallas,
                      max_window_events=256)
    return KeyPoolEngine(ve, ("person",), CHUNK, slots=slots,
                         wide_slots=wide_slots, narrow_ring=16,
                         strict_overflow=strict)


def nexmark(n, seed=11):
    gen = dict(spec.load_config("nexmark_newuser")["generator"],
               num_active_people=20)
    s = streams.Stream(gen, seed)
    s.grow(n)
    return s


def as_events(raws):
    return [Event(r["type"], {k: v for k, v in r.items() if k != "type"})
            for r in raws]


def host_counts(events):
    cq = compile_query(Q)
    eng = PartitionedEngine(lambda: Engine(cq.cea, window=cq.query.window),
                            cq.query.partition_by)
    out = np.zeros(len(events), np.int64)
    for i, ev in enumerate(events):
        for ce in eng.process(ev):
            out[ce.end] += 1
    return out


def feed_all(eng, events):
    got = []
    for c in range(0, len(events), CHUNK):
        counts, _ = eng.feed(events[c:c + CHUNK])
        got.append(counts)
    return np.concatenate(got)


def ev(kind, person, t, price=0):
    return {"type": kind, "person": person, "auction": 0, "price": price,
            "date_time": t}


def filler(t, n, person0=5000):
    """``n`` cheap bids of other people at ``t``: they move the clock and
    match nothing."""
    return [ev("BID", person0 + i, t, 1) for i in range(n)]


def test_more_keys_than_the_pool_holds_counts_exactly():
    n = 64 * CHUNK
    s = nexmark(n)
    events = as_events(s.raw(i) for i in range(n))
    eng = pool()
    got = feed_all(eng, events)
    want = host_counts(events)
    ref, _ = reference.evaluate(REF, s.select(np.arange(n)), s.type_names, n)
    assert len(np.unique(s.columns["person"][:n])) > eng.slots + eng.wide_slots
    assert want.sum() > 0 and np.array_equal(want, ref)
    assert np.array_equal(got, want)
    st = eng.stats
    assert st.reclaimed > 0 and st.pool_overflow == 0
    assert st.spilled_table == 0 and st.evicted_lanes == 0
    assert 0 < st.keys_live <= st.keys_peak <= eng.slots + eng.wide_slots
    assert not eng.window_overflow.any() and eng.compile_count == 1
    # the scan follows the chunk: far fewer grid slots than a dense grid
    # of every slot, and at most 16 per event
    assert st.slots_scanned <= 16 * n


def test_hot_key_outgrows_the_narrow_ring_in_one_chunk_and_is_promoted():
    raws = [ev("PERSON", 7, 0), ev("AUCTION", 7, 1)] + filler(2, 62)
    # key 7's next chunk: 40 bids, more than its 16-start narrow ring
    raws += [ev("AUCTION", 7, 20)] + [ev("BID", 7, 21, 10 ** 6)
                                      for _ in range(40)] + filler(22, 23)
    events = as_events(raws)
    eng = pool()
    got = feed_all(eng, events)
    want = host_counts(events)
    assert want[65:105].tolist() == [2] * 40
    assert np.array_equal(got, want)
    assert eng.stats.promoted == 1
    assert eng.stats.spilled_table == 0 and eng.stats.pool_overflow == 0
    assert not eng.window_overflow.any()


def test_more_keys_promoted_in_one_chunk_than_a_wide_wave_holds():
    """Ten keys outgrow their 16-start narrow rings in the same chunk: each
    takes a free wide slot, whatever a wide wave's lane count (8)."""
    keys = range(200, 210)
    raws = [ev(k, p, 0, 10 ** 6) for p in keys
            for k in ("PERSON", "AUCTION", "BID", "BID", "BID", "BID")]
    raws += filler(0, 4)
    raws += [ev("BID", p, 1, 10 ** 6) for p in keys for _ in range(5)]
    raws += filler(1, 14)
    raws += [ev("BID", p, 2, 10 ** 6) for p in keys for _ in range(6)]
    raws += filler(2, 4)
    events = as_events(raws)
    eng = pool(wide_slots=12)
    got = feed_all(eng, events)
    want = host_counts(events)
    assert want[128:].sum() > 0
    assert np.array_equal(got, want)
    assert eng.stats.promoted == 10 and eng.stats.pool_overflow == 0
    assert not eng.window_overflow.any()


def test_key_back_after_its_window_gets_a_fresh_partition():
    raws = [ev("PERSON", 7, 0), ev("AUCTION", 7, 1)] + filler(2, 62)
    raws += filler(40, 64, person0=6000)            # key 7's window passes
    raws += [ev("BID", 7, 120, 10 ** 6), ev("PERSON", 7, 121),
             ev("AUCTION", 7, 122), ev("BID", 7, 123, 10 ** 6)] + \
        filler(124, 60, person0=7000)
    events = as_events(raws)
    eng = pool()
    got = feed_all(eng, events)
    want = host_counts(events)
    assert want[128] == 0 and want[131] == 1
    assert np.array_equal(got, want)
    assert eng.stats.reclaimed >= 1


@pytest.mark.parametrize("t_bid", [25, 31, 32])
def test_key_back_inside_its_window_keeps_its_state(t_bid):
    """A bid in a later chunk, up to 30 ms after the key's newest event
    (the window's edge; 32 is past it): the slot is reclaimed only once no
    start is live."""
    raws = [ev("PERSON", 7, 1), ev("AUCTION", 7, 1)] + filler(1, 62)
    raws += [ev("BID", 7, t_bid, 10 ** 6)] + filler(t_bid, 63)
    events = as_events(raws)
    eng = pool()
    got = feed_all(eng, events)
    want = host_counts(events)
    assert want[64] == (1 if t_bid <= 31 else 0)
    assert np.array_equal(got, want)


def test_pool_exhaustion_is_counted_and_raises_under_strict_overflow():
    raws = [ev("PERSON", 100 + i, i // 8) for i in range(CHUNK)]
    lenient = pool(slots=16, strict=False)
    lenient.feed(as_events(raws))
    assert lenient.stats.pool_overflow == CHUNK - 16
    assert lenient.stats.spilled_table == CHUNK - 16
    strict = pool(slots=16)
    with pytest.raises(PoolOverflowError) as err:
        strict.feed(as_events(raws))
    assert err.value.restore_kwargs == {"slots": 32}
    assert strict.stats.pool_overflow == CHUNK - 16


def run_service(raws, directory, eng, stop_after=None):
    svc = StreamService(eng, directory, checkpoint_every=3)
    for r in raws[:stop_after]:
        assert svc.submit(r, block=True, timeout=60.0).accepted
    svc.drain(pad=stop_after is None, timeout=120.0)
    metrics = svc.metrics
    svc.close(checkpoint=False)
    return metrics


def test_service_restore_mid_stream_matches_an_uninterrupted_run(tmp_path):
    n = 40 * CHUNK
    s = nexmark(n, seed=3)
    raws = [s.raw(i) for i in range(n)]
    run_service(raws, str(tmp_path / "whole"), pool())
    want = cumulative_matches(str(tmp_path / "whole"))
    d = str(tmp_path / "cut")
    # the first run stops 20 chunks in, its last checkpoint 2 chunks back;
    # the second takes the stream from the start (at-least-once ingestion)
    run_service(raws, d, pool(), stop_after=20 * CHUNK)
    m = run_service(raws, d, pool())
    assert m.skipped_chunks + m.replayed_chunks >= 20
    assert cumulative_matches(d) == want and want["hits"]


def test_service_heals_pool_exhaustion_by_growing_the_class(tmp_path):
    n = 24 * CHUNK
    s = nexmark(n, seed=4)
    raws = [s.raw(i) for i in range(n)]
    small = pool(slots=16)
    m = run_service(raws, str(tmp_path / "small"), small)
    run_service(raws, str(tmp_path / "big"), pool())
    assert m.overflows >= 1 and small.slots > 16 and small.wide_slots == 4
    assert cumulative_matches(str(tmp_path / "small")) == \
        cumulative_matches(str(tmp_path / "big"))


def test_regrow_moves_wide_rings_and_counts_stay_exact():
    """A wide ring regrown mid-stream (the heal of a ring overflow) keeps
    every live start where the wider ring would hold it: grown by only 8
    slots, the next seeds reach the restored starts within a few events,
    so one put in the wrong slot would be clobbered live and latch."""
    n = 24 * CHUNK
    s = nexmark(n, seed=6)
    events = as_events(s.raw(i) for i in range(n))
    eng = pool()
    half = feed_all(eng, events[:n // 2])
    assert eng.stats.keys_live > 0 and eng.stats.promoted > 0
    eng.regrow(264)
    assert eng.window.ring == 264 and eng.narrow_ring == 16
    got = np.concatenate([half, feed_all(eng, events[n // 2:])])
    assert np.array_equal(got, host_counts(events))


def test_key_audit_and_manifest_hold_live_keys_only():
    """Ever-new keys: the collision audit (and the checkpoint manifest that
    carries it) stays flat, while collisions among live keys still raise."""
    eng = pool()
    sizes = []
    for c in range(12):
        t0 = 40 * c
        eng.feed(as_events(filler(t0, CHUNK, person0=10_000 + CHUNK * c)))
        sizes.append(len(eng.manifest()["hash_to_key"]))
    assert max(sizes[3:]) <= 2 * CHUNK and sizes[-1] == sizes[-2]
    assert eng.stats.reclaimed >= 9 * CHUNK
    h = next(iter(eng._hash_to_key))
    with pytest.raises(ValueError, match="hash collision"):
        eng._audit({h: ("not", "this", "key")})


def test_scopes_of_the_step_are_in_the_lowered_program():
    eng = pool()
    events = as_events(filler(0, CHUNK))
    attrs, keys, ts = eng.encoder.encode_stream_keyed_ts(
        events, eng.key_attrs, eng.window.time_attr)
    text = eng._step.lower(attrs, keys, eng.state, ts).as_text(
        debug_info=True)
    for scope in ("classify", "reclaim", "assign", "promote", "scatter",
                  "relabel"):
        assert f"{scope}/" in text, scope


def test_narrow_class_on_the_fused_kernel_counts_exactly():
    """The narrow class's 16-start ring fits the fused Pallas kernel
    (interpret mode here); the wide class takes its own route."""
    n = 16 * CHUNK
    s = nexmark(n, seed=5)
    events = as_events(s.raw(i) for i in range(n))
    eng = pool(use_pallas=True)
    assert eng.routes["scan"].path == "pallas"
    assert np.array_equal(feed_all(eng, events), host_counts(events))


def test_four_key_q3_keeps_its_dense_step():
    """The 4-key Q3 cells build the dense lane engine, whose step and
    counts the pool leaves as they were: no pool stage in its program,
    one grid of lanes x lane_cap slots a chunk, counts equal to the host."""
    q3 = spec.load_config("stock_q3_r512")
    ve = VectorEngine(compile_query(q3["query"]), use_pallas=False,
                      max_window_events=128)
    eng = PartitionedStreamingEngine(ve, ("volume",), chunk_len=128,
                                     num_lanes=4, lane_cap=64,
                                     strict_overflow=True)
    s = streams.Stream(q3["generator"], 9)
    n = 8 * 128
    s.grow(n)
    events = as_events(s.raw(i) for i in range(n))
    got = np.concatenate([eng.feed(events[c:c + 128])[0]
                          for c in range(0, n, 128)])
    cq = compile_query(q3["query"])
    host = PartitionedEngine(
        lambda: Engine(cq.cea, window=cq.query.window,
                       consume_on_match=cq.query.consume_on_match),
        cq.query.partition_by)
    want = np.zeros(n, np.int64)
    for i, e in enumerate(events):
        for ce in host.process(e):
            want[ce.end] += 1
    assert np.array_equal(got, want) and want.sum() > 0
    assert eng.stats.slots_scanned == 8 * 4 * 64
    attrs, keys, ts = eng.encoder.encode_stream_keyed_ts(
        events[:128], eng.key_attrs, eng.window.time_attr)
    text = eng._step.lower(attrs, keys, eng.state, np.int32(0),
                           np.arange(128, dtype=np.int32), ts).as_text(
        debug_info=True)
    assert "assign/" in text and "classify/" not in text
    assert "reclaim/" not in text and "promote/" not in text
