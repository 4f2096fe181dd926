"""Compiled selection/consumption semantics on the device path (ISSUE 8).

DESIGN.md D2 (closed): STRICT / MAX / LAST / NXT and CONSUME BY ANY are
compiled into the determinization (`vector/symbolic.py`) instead of host
post-filters.  These tests pin device-native counts AND enumerated match
sets bit-equal to the host oracle — `core.engine.Engine` + per-position
`apply_strategy` — across all four engine layers: plain (`run_enumerate`),
streaming (chunk-straddling feeds + snapshot/restore), NULL-key
partitioned, and mixed-strategy packs (MultiQueryEngine / QueryFleet).
Construction-time rejection of unsupported semantics rides along
(satellites 1-2): no device engine may silently evaluate under ANY.
"""
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core import Event, compile_query
from repro.core.engine import Engine
from repro.core.partition import PartitionedEngine
from repro.core.query import resolve_semantics
from repro.core.selection import apply_strategy
from repro.runtime.fleet import QueryFleet
from repro.vector import VectorEngine
from repro.vector.multiquery import MultiQueryEngine, build_packing
from repro.vector.streaming import StreamingVectorEngine
from repro.vector.partitioned import PartitionedStreamingEngine

N = 12          # fixed stream length: one jit per cached engine

Q_CNT = "SELECT {s}* FROM S WHERE A ; B+ ; C WITHIN 6"
Q_TIME = "SELECT {s}* FROM S WHERE A ; B+ ; C WITHIN 7 [ts]"


def qtext(strategy="", window=Q_CNT, consume=False):
    s = f"{strategy} " if strategy else ""
    return window.format(s=s) + (" CONSUME BY ANY" if consume else "")


def mk_stream(seed, timed=False, n=N):
    rng = random.Random(seed)
    return [Event(rng.choice("ABC"), {"ts": float(i)} if timed else None)
            for i in range(n)]


def ceset(ces):
    return {(int(c.start), int(c.end), tuple(map(int, c.data)))
            for c in ces}


def host_sets(text, stream):
    """Per-position oracle: host Algorithm-1 engine + host post-filter."""
    cq = compile_query(text)
    eng = Engine(cq.cea, window=cq.query.window,
                 consume_on_match=cq.query.consume_on_match)
    return [ceset(apply_strategy(cq.query.strategy, eng.process(ev)))
            for ev in stream]


#: engines are cached across examples/params — rebuilding one per
#: hypothesis example would recompile its jitted pipeline every time
_ENGINES = {}


def engine_for(text, **kw):
    key = (text, tuple(sorted(kw.items())))
    if key not in _ENGINES:
        _ENGINES[key] = VectorEngine(text, use_pallas=False, **kw)
    return _ENGINES[key]


def check_native_enumerate(ve, text, stream):
    counts, matches = ve.run_enumerate([list(stream)])
    want = host_sets(text, stream)
    for t in range(len(stream)):
        got = ceset(matches.get((t, 0), []))
        assert got == want[t], (text, t, sorted(got), sorted(want[t]))
        assert int(counts[t, 0]) == len(want[t]), (text, t)


# ---------------------------------------------------------------------------
# plain engine: native counts + enumerated sets == host oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["", "ALL", "STRICT", "MAX", "LAST",
                                      "NEXT"])
def test_plain_native_parity_count_window(strategy):
    text = qtext(strategy)
    ve = engine_for(text)
    assert ve.native_semantics == (strategy not in ("", "ALL"))
    for seed in range(4):
        check_native_enumerate(ve, text, mk_stream(seed))


@pytest.mark.parametrize("strategy", ["MAX", "LAST"])
def test_plain_native_parity_time_window(strategy):
    text = qtext(strategy, window=Q_TIME)
    ve = engine_for(text, max_window_events=16)
    for seed in range(3):
        check_native_enumerate(ve, text, mk_stream(seed, timed=True))


@pytest.mark.parametrize("strategy", ["", "MAX", "LAST", "NEXT"])
def test_plain_consume_parity(strategy):
    """CONSUME BY ANY vs host Engine(consume_on_match=True): the emitted
    sets AND the post-emission state (later positions) must agree."""
    text = qtext(strategy, consume=True)
    ve = engine_for(text)
    assert ve.consumes == (True,)
    for seed in range(4):
        check_native_enumerate(ve, text, mk_stream(seed))


@given(st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=10, deadline=None)
def test_hypothesis_native_parity(seed):
    """Random streams through the cached native engines: counts, hits and
    enumerated sets equal the host oracle for every compiled strategy."""
    for strategy in ("MAX", "LAST", "NEXT", "STRICT"):
        text = qtext(strategy)
        check_native_enumerate(engine_for(text), text, mk_stream(seed))
    text = qtext("LAST", consume=True)
    check_native_enumerate(engine_for(text), text, mk_stream(seed))


# ---------------------------------------------------------------------------
# streaming: chunk-straddling matches + consume state across snapshots
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy,consume", [("MAX", False),
                                              ("LAST", True)])
def test_streaming_chunk_straddle_parity(strategy, consume):
    text = qtext(strategy, consume=consume)
    stream = mk_stream(11)
    want = host_sets(text, stream)
    se = StreamingVectorEngine(engine_for(text), chunk_len=4, batch=1,
                               arena_capacity=256)
    hits = []
    for c0 in range(0, N, 4):
        hits += se.feed([stream[c0:c0 + 4]])[1]
    got = se.enumerate_hits(hits)
    for t in range(N):
        assert ceset(got.get((t, 0), [])) == want[t], (text, t)
    assert se.manifest()["semantics"] == {
        "strategies": [strategy if strategy != "NEXT" else "NXT"],
        "consume": [consume]}


def test_streaming_snapshot_restores_consume_state():
    """A consuming engine's ring was cleared on match — restoring the
    snapshot must continue bit-identically (DESIGN.md §10)."""
    text = qtext("MAX", consume=True)
    stream = mk_stream(5)
    want = host_sets(text, stream)

    def fresh():
        return StreamingVectorEngine(
            VectorEngine(text, use_pallas=False), chunk_len=4, batch=1,
            arena_capacity=256)

    se = fresh()
    hits = se.feed([stream[:4]])[1]
    snap = se.snapshot()
    se2 = fresh()
    se2.restore(snap)
    for eng in (se, se2):
        h2 = list(hits)
        for c0 in range(4, N, 4):
            h2 += eng.feed([stream[c0:c0 + 4]])[1]
        got = eng.enumerate_hits(h2)
        for t in range(N):
            assert ceset(got.get((t, 0), [])) == want[t], t


def test_snapshot_refuses_cross_semantics_restore():
    """Same automaton, different compiled semantics — the manifest (and
    fingerprint) must refuse: the rings mean different run sets."""
    a = StreamingVectorEngine(VectorEngine(qtext("MAX", consume=True),
                                           use_pallas=False),
                              chunk_len=4, batch=1)
    b = StreamingVectorEngine(VectorEngine(qtext("MAX"), use_pallas=False),
                              chunk_len=4, batch=1)
    with pytest.raises(ValueError, match="incompatible"):
        b.restore(a.snapshot())


# ---------------------------------------------------------------------------
# partitioned: NULL keys + native semantics at global positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy,consume", [("MAX", False),
                                              ("LAST", True)])
def test_partitioned_null_key_parity(strategy, consume):
    text = qtext(strategy, consume=consume)
    cq = compile_query(text)
    rng = random.Random(3)
    events = [Event(rng.choice("ABC"),
                    {"k": k} if (k := rng.choice([1, 2, None])) is not None
                    else None)
              for _ in range(N)]
    host = PartitionedEngine(
        lambda: Engine(cq.cea, window=cq.query.window,
                       consume_on_match=cq.query.consume_on_match), ("k",))
    want = [ceset(apply_strategy(cq.query.strategy, host.process(ev)))
            for ev in events]
    pe = PartitionedStreamingEngine(
        VectorEngine(text, use_pallas=False), ("k",), chunk_len=6,
        num_lanes=4, arena_capacity=256)
    hits = []
    for c0 in range(0, N, 6):
        hits += pe.feed(events[c0:c0 + 6])[1]
    got = pe.enumerate_hits(hits)
    for p in range(N):
        assert ceset(got.get(p, [])) == want[p], (text, p)


# ---------------------------------------------------------------------------
# packed multiquery + fleet: per-query semantics in one pack
# ---------------------------------------------------------------------------

MIXED = [qtext(""), qtext("MAX"), qtext("LAST"), qtext("NEXT", consume=True)]


def test_multiquery_mixed_strategies_native():
    mq = MultiQueryEngine(MIXED, use_pallas=False)
    assert mq.strategies == ("ALL", "MAX", "LAST", "NXT")
    assert mq.consumes == (False, False, False, True)
    stream = mk_stream(7)
    counts, matches = mq.run_enumerate([list(stream)])
    for qi, text in enumerate(MIXED):
        want = host_sets(text, stream)
        for t in range(N):
            got = ceset(matches.get((t, 0, qi), []))
            assert got == want[t], (text, t)
            assert int(counts[t, 0, qi]) == len(want[t]), (text, t)


def test_fleet_mixed_strategies_native():
    fleet = QueryFleet(chunk_len=4, batch=1, epsilon=6, arena_capacity=256)
    qids = [fleet.add_query(t) for t in MIXED[:3]]
    stream = mk_stream(9)
    hits = []
    for c0 in range(0, N, 4):
        hits += fleet.feed([stream[c0:c0 + 4]])[1]
    for qid, text in zip(qids, MIXED[:3]):
        want = host_sets(text, stream)
        for p, b in hits:
            assert ceset(fleet.enumerate(qid, p, b)) == want[p], (text, p)


# ---------------------------------------------------------------------------
# rejection: no silent ANY evaluation anywhere (satellites 1-2)
# ---------------------------------------------------------------------------

def test_apply_strategy_rejects_unknown_even_when_empty():
    with pytest.raises(ValueError, match="BOGUS"):
        apply_strategy("BOGUS", [])


def test_resolve_semantics_rejects_strict_consume():
    cq = compile_query(qtext("STRICT", consume=True))
    with pytest.raises(ValueError, match="STRICT"):
        resolve_semantics(cq.query)


@pytest.mark.parametrize("build", [
    lambda t: VectorEngine(t, use_pallas=False),
    lambda t: MultiQueryEngine([qtext("MAX"), t], use_pallas=False),
    lambda t: build_packing([t]),
], ids=["vector", "multiquery", "packing"])
def test_engines_reject_unsupported_semantics_at_construction(build):
    with pytest.raises(ValueError, match="STRICT"):
        build(qtext("STRICT", consume=True))


def test_streaming_engines_reject_via_wrapped_engine():
    # streaming/partitioned wrap a constructed engine, so the raise
    # happens before any streaming object exists
    with pytest.raises(ValueError, match="STRICT"):
        StreamingVectorEngine(
            VectorEngine(qtext("STRICT", consume=True), use_pallas=False),
            chunk_len=4, batch=1)


def test_fleet_add_rejects_and_rolls_back():
    fleet = QueryFleet(chunk_len=4, batch=1, epsilon=6)
    qa = fleet.add_query(qtext("MAX"))
    with pytest.raises(ValueError, match="STRICT"):
        fleet.add_query(qtext("STRICT", consume=True))
    assert fleet.live_qids == [qa]
    fleet.feed([mk_stream(0, n=4)])          # bucket still serves


def test_explicit_conflicting_strategy_raises_on_native_engine():
    ve = engine_for(qtext("MAX"))
    with pytest.raises(ValueError, match="native semantics"):
        ve.run_enumerate([mk_stream(0)], strategy="NEXT")
    # matching explicit strategy is accepted (resolves to native)
    check_native_enumerate_strategy_ok = ve.run_enumerate(
        [mk_stream(0)], strategy="MAX")
    assert check_native_enumerate_strategy_ok[0].shape == (N, 1)


def test_legacy_post_filter_still_works_on_plain_engine():
    ve = engine_for(qtext(""))
    stream = mk_stream(2)
    _, native = ve.run_enumerate([list(stream)], strategy=None)
    _, post = ve.run_enumerate([list(stream)], strategy="LAST")
    want = host_sets(qtext("LAST"), stream)
    for t in range(N):
        assert ceset(post.get((t, 0), [])) == want[t], t
        assert ceset(post.get((t, 0), [])) <= ceset(native.get((t, 0), []))
