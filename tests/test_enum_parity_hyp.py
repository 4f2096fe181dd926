"""Property-based parity suites for the enumeration fast paths (DESIGN §13).

Two invariants guard PR 10's perf work:

* **delta fetch ≡ full fetch** — the persistent :class:`ArenaMirror` pulls
  only rows appended since its watermark; its node store must stay
  bit-identical to a from-scratch fetch of the whole device arena across
  chunk-straddling streaming feeds, partitioned lane eviction +
  snapshot/restore regrow (both invalidate the watermark), and fleet
  repack migrations;
* **vectorized walk ≡ DFS oracle** — ``enumerate_hits(...)`` (the
  frontier-vectorized Algorithm 2) must return lists bit-identical —
  order and ``steps`` charge included — to ``oracle=True`` (the per-root
  Python DFS, Algorithm 2 as written), for every compiled selection
  strategy × window kind.

Property-based variants sit next to seeded sweeps that cover the same
ground deterministically.
"""
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core import Event
from repro.runtime.fleet import QueryFleet
from repro.vector import StreamingVectorEngine, VectorEngine
from repro.vector.partitioned import PartitionedStreamingEngine
from repro.vector.tecs_arena import ArenaSnapshot

Q_CNT = "SELECT {s}* FROM S WHERE A ; B+ ; C WITHIN 11"
Q_TIME = "SELECT {s}* FROM S WHERE A ; B+ ; C WITHIN 7 [ts]"
Q_PART = "SELECT * FROM S WHERE A ; B+ ; C WITHIN 50 [t]"
STRATEGIES_CNT = ["", "STRICT", "MAX", "LAST", "NEXT"]
STRATEGIES_TIME = ["", "MAX", "LAST", "NEXT"]


def qtext(strategy="", window=Q_CNT):
    return window.format(s=f"{strategy} " if strategy else "")


def mk_stream(seed, n, timed=False, alphabet="ABCX"):
    rng = random.Random(seed)
    return [Event(rng.choice(alphabet), {"ts": float(i)} if timed else None)
            for i in range(n)]


def mk_keyed(seed, n, n_keys, dt=5.0):
    rng = random.Random(seed)
    return [Event(rng.choice("ABC"),
                  {"t": float(i) * dt, "uid": rng.randrange(n_keys)})
            for i in range(n)]


#: engines are cached across examples/params — rebuilding one per
#: hypothesis example would recompile its jitted pipeline every time
_ENGINES = {}


def streaming_for(text, batch=1, chunk=8, **kw):
    key = (text, batch, chunk, tuple(sorted(kw.items())))
    if key not in _ENGINES:
        ve = VectorEngine(text, use_pallas=False,
                          **({"max_window_events": 16}
                             if "[ts]" in text else {}))
        _ENGINES[key] = StreamingVectorEngine(
            ve, chunk_len=chunk, batch=batch, arena_capacity=1 << 14, **kw)
    eng = _ENGINES[key]
    eng.reset()
    return eng


def full_fetch(se) -> ArenaSnapshot:
    """From-scratch snapshot of the whole device arena (no mirror)."""
    return ArenaSnapshot(se._state["arena"])


def assert_store_parity(delta: ArenaSnapshot, full: ArenaSnapshot, ctx=""):
    """Delta-fetched mirror rows ≡ the device store, per live lane row."""
    np.testing.assert_array_equal(delta.ptr, full.ptr, err_msg=ctx)
    np.testing.assert_array_equal(delta.ovf, full.ovf, err_msg=ctx)
    for name in ("kind", "pos", "maxs", "left", "right"):
        d, f = getattr(delta, name), getattr(full, name)
        for lane in range(f.shape[0]):
            n = int(full.ptr[lane])
            np.testing.assert_array_equal(
                d[lane, :n], f[lane, :n],
                err_msg=f"{ctx} field {name} lane {lane}")


def assert_enum_parity(se, hits, query=0):
    """Vectorized walk ≡ per-root DFS: lists (order included) and steps."""
    vec = se.enumerate_hits(hits, query=query)
    dfs = se.enumerate_hits(hits, query=query, oracle=True)
    assert vec == dfs
    return vec


# ---------------------------------------------------------------------------
# delta fetch ≡ full fetch
# ---------------------------------------------------------------------------


def check_delta_streaming(seed, T=96, CH=8, B=2):
    """Chunk-straddling streaming: every sync is a strict delta append."""
    se = streaming_for(qtext(), batch=B, chunk=CH)
    streams = [mk_stream(seed * B + b, T) for b in range(B)]
    hits = []
    for lo in range(0, T, CH):
        _, h = se.feed([s[lo:lo + CH] for s in streams])
        hits += h
        assert_store_parity(se.arena_snapshot(), full_fetch(se),
                            ctx=f"chunk@{lo}")
    assert se.compile_count == 1
    if hits:
        assert_enum_parity(se, hits)


def check_delta_partitioned(seed, n_keys=6, chunks=8, CH=16):
    """Partitioned lane eviction (keys > lanes) + snapshot/restore regrow:
    the restore replaces the store wholesale, so the mirror must refetch
    from row 0 — and stay a delta afterwards."""
    def mk(mwe):
        ve = VectorEngine(Q_PART, use_pallas=False, max_window_events=mwe)
        return PartitionedStreamingEngine(
            ve, ("uid",), chunk_len=CH, num_lanes=4,
            arena_capacity=1 << 12, strict_overflow=True)

    events = mk_keyed(seed, chunks * CH, n_keys)
    pse = mk(8)
    hits = []
    for i in range(chunks // 2):
        _, h = pse.feed(events[i * CH:(i + 1) * CH])
        hits += h
        assert_store_parity(pse.arena_snapshot(), full_fetch(pse),
                            ctx=f"pre-regrow chunk {i}")
    # regrow through snapshot/restore: mirror watermark must drop to 0
    pse.restore(pse.snapshot(), max_window_events=64)
    assert pse._arena_mirror.fetched == 0
    for i in range(chunks // 2, chunks):
        _, h = pse.feed(events[i * CH:(i + 1) * CH])
        hits += h
        assert_store_parity(pse.arena_snapshot(), full_fetch(pse),
                            ctx=f"post-regrow chunk {i}")
    assert pse.stats.evicted_lanes > 0, "eviction never exercised"
    live = [p for p in hits if p in pse._roots]
    if live:
        vec = pse.enumerate_hits(live)
        assert vec == pse.enumerate_hits(live, oracle=True)


def check_delta_fleet(seed, chunks=6, CH=8):
    """Fleet repack (hot add/remove) migrates node rows between packings:
    each bucket engine's mirror must refetch and match a full fetch."""
    fleet = _ENGINES.get("fleet")
    if fleet is None:
        fleet = _ENGINES["fleet"] = QueryFleet(
            chunk_len=CH, batch=1, arena_capacity=1 << 13)
    fleet.reset()
    for qid in list(fleet.live_qids):
        fleet.remove_query(qid)
    qa = fleet.add_query("SELECT * FROM S WHERE A ; B+ ; C WITHIN 11")
    qb = fleet.add_query("SELECT * FROM S WHERE B+ WITHIN 11")

    def check(ctx):
        for bucket in fleet._buckets.values():
            assert_store_parity(bucket.engine.arena_snapshot(),
                                full_fetch(bucket.engine), ctx=ctx)

    stream = mk_stream(seed, chunks * CH)
    hits = []
    for i in range(chunks):
        _, h = fleet.feed([stream[i * CH:(i + 1) * CH]])
        hits += h
        check(f"chunk {i}")
        if i == 1:     # repack mid-stream: add joins qa's bucket
            qc = fleet.add_query("SELECT * FROM S WHERE A ; C WITHIN 11")
            check("post-add repack")
        if i == 3:     # repack again: removal shrinks the packing
            fleet.remove_query(qc)
            check("post-remove repack")
    # vectorized ≡ DFS through the fleet's bucket engines, per live query
    for qid in (qa, qb):
        bucket = fleet._find_bucket(qid)
        slot = bucket.qids.index(qid)
        live = [h for h in hits if h in bucket.engine._roots]
        vec = bucket.engine.enumerate_hits(live, query=slot)
        assert vec == bucket.engine.enumerate_hits(live, query=slot,
                                                   oracle=True)


def test_delta_fetch_streaming_seeded():
    check_delta_streaming(seed=7)


def test_delta_fetch_partitioned_seeded():
    check_delta_partitioned(seed=1)


def test_delta_fetch_fleet_seeded():
    check_delta_fleet(seed=3)


@given(st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=8, deadline=None)
def test_hypothesis_delta_fetch_streaming(seed):
    check_delta_streaming(seed)


@given(st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=4, deadline=None)
def test_hypothesis_delta_fetch_partitioned(seed):
    check_delta_partitioned(seed)


@given(st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=4, deadline=None)
def test_hypothesis_delta_fetch_fleet(seed):
    check_delta_fleet(seed)


# ---------------------------------------------------------------------------
# vectorized walk ≡ DFS oracle, per selection strategy × window kind
# ---------------------------------------------------------------------------


def check_vectorized_vs_dfs(seed, strategy, window, T=48, CH=8):
    text = qtext(strategy, window=window)
    se = streaming_for(text, batch=2, chunk=CH)
    timed = "[ts]" in window
    streams = [mk_stream(seed * 2 + b, T, timed=timed) for b in range(2)]
    hits = []
    for lo in range(0, T, CH):
        _, h = se.feed([s[lo:lo + CH] for s in streams])
        hits += h
    if hits:
        assert_enum_parity(se, hits)
    return len(hits)


@pytest.mark.parametrize("strategy", STRATEGIES_CNT)
def test_vectorized_vs_dfs_count_window(strategy):
    n = sum(check_vectorized_vs_dfs(s, strategy, Q_CNT) for s in range(3))
    assert n > 0, "seeded streams produced no hits"


@pytest.mark.parametrize("strategy", STRATEGIES_TIME)
def test_vectorized_vs_dfs_time_window(strategy):
    n = sum(check_vectorized_vs_dfs(s, strategy, Q_TIME) for s in range(3))
    assert n > 0, "seeded streams produced no hits"


@given(st.integers(min_value=0, max_value=2 ** 16),
       st.integers(min_value=0, max_value=len(STRATEGIES_CNT) - 1))
@settings(max_examples=10, deadline=None)
def test_hypothesis_vectorized_vs_dfs_count(seed, sidx):
    check_vectorized_vs_dfs(seed, STRATEGIES_CNT[sidx], Q_CNT)


@given(st.integers(min_value=0, max_value=2 ** 16),
       st.integers(min_value=0, max_value=len(STRATEGIES_TIME) - 1))
@settings(max_examples=10, deadline=None)
def test_hypothesis_vectorized_vs_dfs_time(seed, sidx):
    check_vectorized_vs_dfs(seed, STRATEGIES_TIME[sidx], Q_TIME)


def test_vectorized_walk_charges_dfs_steps():
    """The ``steps`` counter (Theorem 2's work bound) must charge the
    vectorized walk exactly the oracle DFS's node visits."""
    se = streaming_for(qtext(), batch=1, chunk=8)
    stream = mk_stream(11, 64)
    hits = []
    for lo in range(0, 64, 8):
        _, h = se.feed([stream[lo:lo + 8]])
        hits += h
    assert hits
    snap = se.arena_snapshot()
    lanes = [b for _, b in hits]
    roots = [int(se._roots[(p, b)][0]) for p, b in hits]
    ends = [p for p, _ in hits]
    s_vec, s_dfs = [0], [0]
    vec = snap.enumerate_batch(lanes, roots, ends, steps=s_vec)
    dfs = snap.enumerate_batch(lanes, roots, ends, steps=s_dfs, oracle=True)
    assert vec == dfs
    assert s_vec == s_dfs and s_vec[0] > 0
