"""Device tECS arena ⇔ host engine: enumerated match-SET parity (DESIGN §7).

The counting scan was already validated count-for-count; these tests assert
the stronger property the arena buys us: the *enumerated complex events*
(start, end, data) coming out of the device arena are bit-identical to the
host Algorithm 1 + Algorithm 2 output — on randomized query × stream sweeps,
across chunk boundaries, under PARTITION BY routing with NULL keys, and for
packed multi-query tables.  Property-based variants sit next to seeded
sweeps that cover the same ground deterministically.
"""
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core import compile_query
from repro.core.engine import Engine, WindowSpec
from repro.core.events import Event
from repro.core.partition import PartitionedEngine
from repro.vector import (ArenaOverflow, StreamingVectorEngine, VectorEngine,
                          tecs_arena)
from repro.vector.multiquery import MultiQueryEngine

QUERIES = [
    "SELECT * FROM S WHERE A ; B ; C",
    "SELECT * FROM S WHERE A ; B+ ; C",
    "SELECT * FROM S WHERE A ; (B OR C) ; A",
    # the WITHIN clause now binds the device window (DESIGN.md §9), so
    # epsilon-sweeping helpers use clause-free queries; window-bearing
    # queries are covered in tests/test_time_window.py
    "SELECT * FROM S WHERE B+",
]


def make_streams(seed, B, T, alphabet="ABCX"):
    rng = random.Random(seed)
    return [[Event(rng.choice(alphabet)) for _ in range(T)]
            for _ in range(B)]


def host_match_sets(qtext, stream, eps):
    """position → {(start, end, data)} per the host engine (Algorithm 1+2)."""
    eng = Engine(compile_query(qtext).cea, window=WindowSpec.events(eps))
    out = {}
    for t, ev in enumerate(stream):
        ces = eng.process(ev)
        if ces:
            out[t] = {(c.start, c.end, c.data) for c in ces}
    return out


def ce_set(ces):
    return {(c.start, c.end, c.data) for c in ces}


def check_parity(qtext, seed, eps, B=2, T=64):
    streams = make_streams(seed, B, T)
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    counts, matches = ve.run_enumerate([list(s) for s in streams])
    for b in range(B):
        want = host_match_sets(qtext, streams[b], eps)
        got = {t: ce_set(ces) for (t, bb), ces in matches.items() if bb == b}
        assert got == want, (qtext, seed, b)
        for t, s in want.items():
            # duplicate-free and count-consistent (runs ↔ events, Thm 3)
            assert counts[t, b] == len(s)


# ---------------------------------------------------------------------------
# seeded sweeps (always run)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qtext", QUERIES)
def test_whole_stream_match_set_parity(qtext):
    check_parity(qtext, seed=hash(qtext) % 1000, eps=9)


def test_parity_window_sweep():
    for eps in (3, 7, 16):
        check_parity(QUERIES[1], seed=eps, eps=eps, T=48)


def test_chunk_straddle_match_set_parity():
    """Chunks far smaller than the window: every match straddles a feed
    boundary; enumerated sets must still be exact, with ONE compile."""
    qtext, eps, T, CH, B = QUERIES[1], 11, 96, 8, 2
    streams = make_streams(21, B, T)
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    se = StreamingVectorEngine(ve, chunk_len=CH, batch=B,
                               arena_capacity=1 << 16)
    hits = []
    for lo in range(0, T, CH):
        _, h = se.feed([s[lo:lo + CH] for s in streams])
        hits += h
    res = se.enumerate_hits(hits)
    assert se.compile_count == 1
    for b in range(B):
        want = host_match_sets(qtext, streams[b], eps)
        got = {p: ce_set(ces) for (p, bb), ces in res.items()
               if bb == b and ces}
        assert got == want


def test_streaming_roots_survive_later_feeds():
    """Node ids are stable (append-only arena): a hit recorded in chunk k
    stays enumerable after later chunks have been fed."""
    qtext, eps, T, CH = QUERIES[0], 6, 64, 16
    streams = make_streams(5, 1, T)
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    se = StreamingVectorEngine(ve, chunk_len=CH, batch=1,
                               arena_capacity=1 << 15)
    first_hits = None
    for lo in range(0, T, CH):
        _, h = se.feed([s[lo:lo + CH] for s in streams])
        if first_hits is None and h:
            first_hits = list(h)
    assert first_hits, "stream produced no early matches"
    want = host_match_sets(qtext, streams[0], eps)
    for p, b in first_hits:
        assert ce_set(se.enumerate(p, b)) == want[p]


def test_partitioned_null_keys_match_set_parity():
    """Interleaved stream with NULL-key events: device per-lane arenas,
    relabelled to global positions, match the host dict-of-engines."""
    qtext, eps, T, CH, L = "SELECT * FROM S WHERE A ; B ; C", 9, 128, 32, 8
    rng = random.Random(77)
    events = [Event(rng.choice("ABCX"),
                    {"k": rng.choice(["x", "y", "z", None])})
              for _ in range(T)]
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    pe = ve.partitioned_streaming(["k"], chunk_len=CH, num_lanes=L,
                                  arena_capacity=1 << 16)
    hits = []
    for lo in range(0, T, CH):
        _, h = pe.feed(events[lo:lo + CH])
        hits += h
    assert pe.compile_count == 1
    assert pe.stats.dropped_null > 0   # the sweep must exercise NULL keys
    got = {p: ce_set(ces) for p, ces in pe.enumerate_hits(hits).items()}
    host = PartitionedEngine(
        lambda: Engine(compile_query(qtext).cea,
                       window=WindowSpec.events(eps)), ("k",))
    want = {}
    for i, ev in enumerate(events):
        ces = host.process(ev)
        if ces:
            want[i] = {(c.start, c.end, c.data) for c in ces}
    assert got == want


def test_multiquery_packed_match_set_parity():
    queries = QUERIES[:3]
    eps, B, T = 8, 2, 56
    streams = make_streams(31, B, T)
    mq = MultiQueryEngine(queries, epsilon=eps, use_pallas=False)
    counts, matches = mq.run_enumerate([list(s) for s in streams])
    for qi, qtext in enumerate(queries):
        for b in range(B):
            want = host_match_sets(qtext, streams[b], eps)
            got = {t: ce_set(ces) for (t, bb, qq), ces in matches.items()
                   if bb == b and qq == qi}
            assert got == want, (qtext, b)
            for t, s in want.items():
                assert counts[t, b, qi] == len(s)


def test_arena_overflow_raises_on_enumerate():
    """A lane past capacity refuses to enumerate (overflow policy, §7)."""
    qtext, eps, T = QUERIES[1], 12, 64
    streams = make_streams(3, 1, T, alphabet="ABBC")
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    with pytest.raises(ArenaOverflow):
        ve.run_enumerate([list(streams[0])], arena_capacity=32)


def test_arena_overflow_latches_in_scan():
    """The ovf flag latches inside the scan; the raw snapshot refuses too,
    and the counting side of the pipeline is untouched by arena overflow."""
    import jax.numpy as jnp
    from repro.kernels import ops
    qtext, eps, T, B = QUERIES[1], 12, 64, 1
    streams = make_streams(3, B, T, alphabet="ABBC")
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    attrs = ve.encode(streams)
    tbl = ve.tables
    m, _, trace = ops.cer_pipeline(
        attrs, ve.encoder.specs, tbl.class_of, tbl.class_ind, tbl.m_all,
        tbl.finals[None, :], ve.init_state(B), init_mask=tbl.init_mask,
        epsilon=eps, start_pos=0, route=ops.REF_ROUTE, return_trace=True)
    tables = ve.arena_tables()
    arena = tecs_arena.init_arena(B, 32, ve.ring, tables.num_states)
    gpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, B))
    arena, roots = tecs_arena.arena_scan(
        tables, arena, trace, gpos, jnp.zeros(B, jnp.int32),
        jnp.full((B,), T, jnp.int32), m > 0.5, epsilon=eps)
    snap = tecs_arena.ArenaSnapshot(arena)
    assert bool(snap.ovf[0])
    hit = np.asarray(roots)
    t, b, q = [int(x[0]) for x in np.nonzero(hit >= 0)]
    with pytest.raises(ArenaOverflow):
        list(snap.enumerate(b, hit[t, b, q], t))


# ---------------------------------------------------------------------------
# hypothesis variants (skip gracefully when hypothesis is missing)
# ---------------------------------------------------------------------------


@given(st.integers(min_value=0, max_value=2 ** 16),
       st.integers(min_value=0, max_value=len(QUERIES) - 1),
       st.integers(min_value=3, max_value=14))
@settings(max_examples=12, deadline=None)
def test_hypothesis_random_query_stream_parity(seed, qidx, eps):
    check_parity(QUERIES[qidx], seed=seed, eps=eps, B=1, T=48)


@given(st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=6, deadline=None)
def test_hypothesis_chunked_equals_whole(seed):
    """Chunked streaming enumeration ≡ one-shot enumeration of the whole
    stream (device vs device — no host in the loop)."""
    qtext, eps, T, CH = QUERIES[0], 7, 48, 12
    streams = make_streams(seed, 1, T)
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    counts, whole = ve.run_enumerate([list(streams[0])])
    se = StreamingVectorEngine(ve, chunk_len=CH, batch=1,
                               arena_capacity=1 << 15)
    hits = []
    for lo in range(0, T, CH):
        _, h = se.feed([streams[0][lo:lo + CH]])
        hits += h
    res = se.enumerate_hits(hits)
    got = {p: ce_set(ces) for (p, b), ces in res.items()}
    want = {t: ce_set(ces) for (t, b), ces in whole.items()}
    assert got == want
