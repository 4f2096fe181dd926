"""Block-vectorized tECS arena ⇔ per-event reference fold (DESIGN.md §8).

The block builder replays the reference fold's allocation order exactly
(fixed slot layout + chunk-level cumsum), so its node stores must come out
BIT-IDENTICAL on non-overflowing lanes — a much stronger oracle than
match-set parity: every ``kind``/``pos``/``max_start``/``left``/``right``
entry, the cell tables, bump pointers, overflow flags and emitted roots are
compared verbatim against :func:`repro.vector.tecs_arena.arena_scan` (the
retained per-event fold).  Sweeps cover whole streams, chunk-straddling
feeds, ragged per-lane offsets/valid-counts (the PARTITION BY contract),
packed multi-query tables, the segmented scan, and lane groups under a
record budget; the overflow latch is exercised under block allocation.
"""
import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core.engine import Engine, WindowSpec
from repro.core.events import Event
from repro.core import compile_query
from repro.core.partition import PartitionedEngine
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.vector import ArenaOverflow, StreamingVectorEngine, VectorEngine
from repro.vector import tecs_arena
from repro.vector.multiquery import MultiQueryEngine
from benchmarks.cer_paper import STOCK_QUERIES

QUERIES = [
    "SELECT * FROM S WHERE A ; B ; C",
    "SELECT * FROM S WHERE A ; B+ ; C",
    "SELECT * FROM S WHERE A ; (B OR C) ; A",
    # clause-free: these sweeps drive the window via epsilon= (the shim);
    # WITHIN-declared windows are covered in tests/test_time_window.py
    "SELECT * FROM S WHERE B+",
]


@pytest.fixture(autouse=True)
def _release_compiled():
    """Drop every compiled program after each test.  Each CPU executable
    keeps its own memory mappings until it is freed, and this file's
    sweeps compile thousands of them: held across the file, they reach the
    kernel's limit on mappings per process (``vm.max_map_count``, 65,530
    by default), and the next compile crashes the process."""
    yield
    jax.clear_caches()


def make_streams(seed, B, T, alphabet="ABCX"):
    rng = random.Random(seed)
    return [[Event(rng.choice(alphabet)) for _ in range(T)]
            for _ in range(B)]


def trace_of(engine, attrs, state, eps, start_pos=0, valid=None):
    """Counting pipeline (ref impl) → (matches, state', class trace)."""
    t = engine.tables
    finals = t.finals
    finals_q = finals if finals.ndim == 2 else finals[None, :]
    return ops.cer_pipeline(
        attrs, engine.encoder.specs, t.class_of, t.class_ind, t.m_all,
        finals_q, state, init_mask=t.init_mask, epsilon=eps,
        start_pos=start_pos, valid_counts=valid, route=ops.REF_ROUTE,
        return_trace=True)


def assert_stores_equal(a1, a2, r1, r2, cap, msg=""):
    """Full bit-equality of two arenas (sink slot excluded) + roots."""
    for k in ("ptr", "ovf", "cell"):
        np.testing.assert_array_equal(np.asarray(a1[k]), np.asarray(a2[k]),
                                      err_msg=f"{msg}:{k}")
    for k in ("kind", "pos", "maxs", "left", "right"):
        np.testing.assert_array_equal(
            np.asarray(a1[k])[:, :cap], np.asarray(a2[k])[:, :cap],
            err_msg=f"{msg}:{k}")
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2),
                                  err_msg=f"{msg}:roots")


def run_both(engine, streams, eps, chunk=None, cap=1 << 12,
             start=None, valid=None, **block_kw):
    """Feed chunks through fold and block arenas; assert equality each
    chunk; return the final (fold) arena + per-chunk roots."""
    attrs = jnp.asarray(engine.encoder.encode_streams(streams))
    T, B = attrs.shape[:2]
    chunk = chunk or T
    at = engine.arena_tables()
    a1 = tecs_arena.init_arena(B, cap, engine.ring, at.num_states)
    a2 = tecs_arena.init_arena(B, cap, engine.ring, at.num_states)
    state = engine.init_state(B)
    for lo in range(0, T, chunk):
        m, state, trace = trace_of(engine, attrs[lo:lo + chunk], state,
                                   eps, start_pos=lo % engine.ring)
        ch = trace.shape[0]
        gpos = jnp.broadcast_to(
            lo + jnp.arange(ch, dtype=jnp.int32)[:, None], (ch, B))
        s = (jnp.full((B,), lo % engine.ring, jnp.int32)
             if start is None else start)
        v = jnp.full((B,), ch, jnp.int32) if valid is None else valid
        a1, r1 = tecs_arena.arena_scan(at, a1, trace, gpos, s, v,
                                       m > 0.5, epsilon=eps)
        a2, r2 = tecs_arena.arena_scan_block(at, a2, trace, gpos, s, v,
                                             m > 0.5, epsilon=eps,
                                             **block_kw)
        assert_stores_equal(a1, a2, r1, r2, cap, f"chunk@{lo}")
    return a1


# ---------------------------------------------------------------------------
# seeded sweeps (always run)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qidx", range(len(QUERIES)))
def test_whole_stream_store_parity(qidx):
    ve = VectorEngine(QUERIES[qidx], epsilon=9, use_pallas=False)
    a = run_both(ve, make_streams(137 + qidx, 2, 64), eps=9)
    assert int(np.asarray(a["ptr"]).sum()) > 0  # the sweep built something


def test_window_sweep_store_parity():
    for eps in (3, 7, 16):
        ve = VectorEngine(QUERIES[1], epsilon=eps, use_pallas=False)
        run_both(ve, make_streams(eps, 2, 48), eps=eps)


def test_chunk_straddle_store_parity():
    """Chunks far smaller than the window: every carried cell crosses a
    chunk boundary, exercising the store-derived cell attributes."""
    ve = VectorEngine(QUERIES[1], epsilon=11, use_pallas=False)
    run_both(ve, make_streams(21, 2, 96), eps=11, chunk=8)


def test_ragged_lanes_store_parity():
    """Per-lane ring offsets and dense-prefix valid counts (the PARTITION
    BY contract): dead steps must be exact no-ops on both paths."""
    ve = VectorEngine(QUERIES[0], epsilon=8, use_pallas=False)
    streams = make_streams(5, 3, 40)
    run_both(ve, streams, eps=8,
             start=jnp.asarray([0, 5, 11], jnp.int32),
             valid=jnp.asarray([40, 17, 0], jnp.int32))


def test_multiquery_packed_store_parity():
    mq = MultiQueryEngine(QUERIES[:3], epsilon=8, use_pallas=False)
    run_both(mq, make_streams(31, 2, 56), eps=8, chunk=14)


def test_segmented_scan_store_parity():
    """n_seg > 1 splits the chunk into overlapping replayed segments; ids
    depend only on the absolute event index, so stores stay bit-equal."""
    ve = VectorEngine(QUERIES[2], epsilon=3, use_pallas=False)
    run_both(ve, make_streams(13, 2, 128), eps=3, chunk=64, n_seg=4)


@pytest.mark.parametrize("lanes", [1, 2])
def test_lane_groups_store_parity(monkeypatch, lanes):
    """A record budget below the batch runs the builder in lane groups
    (lax.map); stores must stay bit-identical to the fold."""
    ve = VectorEngine(QUERIES[1], epsilon=6, use_pallas=False)
    T, chunk = 48, 16
    at = ve.arena_tables()
    M = tecs_arena._block_layout(at, ve.ring, 6, 1 << 12).M
    monkeypatch.setattr(tecs_arena, "ARENA_RECORD_BYTES",
                        4 * chunk * M * lanes)
    assert tecs_arena.arena_lane_group(chunk, 4, M) == lanes
    run_both(ve, make_streams(3, 4, T), eps=6, chunk=chunk)


def test_overflow_latches_under_block_allocation():
    """Past-capacity lanes latch ovf, clamp into the sink, and refuse to
    enumerate — while lanes under capacity stay bit-exact and the counting
    side is untouched (overflow policy, DESIGN.md §7)."""
    eps, T = 12, 64
    ve = VectorEngine(QUERIES[1], epsilon=eps, use_pallas=False)
    streams = make_streams(3, 2, T, alphabet="ABBC") \
        [:1] + make_streams(9, 1, T, alphabet="AXCX")
    attrs = jnp.asarray(ve.encoder.encode_streams(streams))
    at = ve.arena_tables()
    cap = 128  # lane 0 builds ~478 nodes (overflows); lane 1 only ~85
    m, _, trace = trace_of(ve, attrs, ve.init_state(2), eps)
    gpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, 2))
    args = (trace, gpos, jnp.zeros(2, jnp.int32),
            jnp.full((2,), T, jnp.int32), m > 0.5)
    a1, r1 = tecs_arena.arena_scan(
        at, tecs_arena.init_arena(2, cap, ve.ring, at.num_states),
        *args, epsilon=eps)
    a2, r2 = tecs_arena.arena_scan_block(
        at, tecs_arena.init_arena(2, cap, ve.ring, at.num_states),
        *args, epsilon=eps)
    ovf = np.asarray(a2["ovf"])
    assert ovf[0] and not ovf[1]
    np.testing.assert_array_equal(ovf, np.asarray(a1["ovf"]))
    # the under-capacity lane stays bit-exact against the fold
    for k in ("kind", "pos", "maxs", "left", "right"):
        np.testing.assert_array_equal(np.asarray(a1[k])[1, :cap],
                                      np.asarray(a2[k])[1, :cap], err_msg=k)
    np.testing.assert_array_equal(np.asarray(r1)[:, 1], np.asarray(r2)[:, 1])
    snap = tecs_arena.ArenaSnapshot(a2)
    hit = np.asarray(r2)
    t, b, q = [int(x[0]) for x in np.nonzero(hit[:, :1] >= 0)]
    with pytest.raises(ArenaOverflow):
        list(snap.enumerate(0, hit[t, 0, q], t))


def test_streaming_block_vs_fold_match_sets():
    """End-to-end through the streaming engine: both arena impls enumerate
    the same complex events, and they match the host engine."""
    qtext, eps, T, CH = QUERIES[1], 11, 96, 16
    streams = make_streams(21, 1, T)
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    results = {}
    for impl in tecs_arena.ARENA_IMPLS:
        se = StreamingVectorEngine(ve, chunk_len=CH, batch=1,
                                   arena_capacity=1 << 15, arena_impl=impl)
        hits = []
        for lo in range(0, T, CH):
            _, h = se.feed([s[lo:lo + CH] for s in streams])
            hits += h
        assert se.compile_count == 1
        res = se.enumerate_hits(hits)
        results[impl] = {p: {(c.start, c.end, c.data) for c in ces}
                         for (p, _b), ces in res.items()}
    assert results["block"] == results["fold"]
    eng = Engine(compile_query(qtext).cea, window=WindowSpec.events(eps))
    want = {}
    for t, ev in enumerate(streams[0]):
        ces = eng.process(ev)
        if ces:
            want[t] = {(c.start, c.end, c.data) for c in ces}
    assert results["block"] == want


def test_partitioned_null_keys_block_vs_fold():
    """Interleaved NULL-keyed stream through the partitioned engine: block
    and fold arenas enumerate identically and match the host."""
    qtext, eps, T, CH, L = QUERIES[0], 9, 128, 32, 8
    rng = random.Random(77)
    events = [Event(rng.choice("ABCX"),
                    {"k": rng.choice(["x", "y", "z", None])})
              for _ in range(T)]
    ve = VectorEngine(qtext, epsilon=eps, use_pallas=False)
    results = {}
    for impl in tecs_arena.ARENA_IMPLS:
        pe = ve.partitioned_streaming(["k"], chunk_len=CH, num_lanes=L,
                                      arena_capacity=1 << 15,
                                      arena_impl=impl)
        hits = []
        for lo in range(0, T, CH):
            _, h = pe.feed(events[lo:lo + CH])
            hits += h
        assert pe.compile_count == 1
        assert pe.stats.dropped_null > 0
        results[impl] = {p: {(c.start, c.end, c.data) for c in ces}
                        for p, ces in pe.enumerate_hits(hits).items()}
    assert results["block"] == results["fold"]
    host = PartitionedEngine(
        lambda: Engine(compile_query(qtext).cea,
                       window=WindowSpec.events(eps)), ("k",))
    want = {}
    for i, ev in enumerate(events):
        ces = host.process(ev)
        if ces:
            want[i] = {(c.start, c.end, c.data) for c in ces}
    assert results["block"] == want


def test_layout_region_compression_is_static():
    """The slot layout drops states that can never allocate; the decode
    tables stay consistent with the region offsets."""
    ve = VectorEngine(QUERIES[1], epsilon=7, use_pallas=False)
    at = ve.arena_tables()
    lay = tecs_arena._block_layout(at, ve.ring, 7, 1 << 10)
    # dead state 0 can never allocate anywhere
    for states in lay.ext_states + lay.uni_states:
        assert 0 not in states
    # depth 0 never unions (empty accumulator)
    assert lay.uni_states[0] == ()
    assert lay.M == lay.off_chain + lay.E * lay.Q
    kind = lay.kind_static()
    assert kind.shape == (lay.M,)
    assert kind[lay.off_bottom] == 0                      # BOTTOM
    assert (lay.d_static() >= 0).sum() == lay.E * lay.Q   # chain slots


# ---------------------------------------------------------------------------
# the fold's sources: static slices and selects, no state-axis gather
# ---------------------------------------------------------------------------


def synthetic_tables(seed, S=7, C=6, Q=2, init=(1, 2)):
    """Random predecessor tables: several edges into most targets, so some
    (depth, target) pairs draw on three or more sources across classes,
    with padded tails and two init states."""
    rng = np.random.default_rng(seed)
    dm = np.where(rng.random((S, C)) < 0.6, rng.integers(1, S, (S, C)), 0)
    du = np.where(rng.random((S, C)) < 0.6, rng.integers(1, S, (S, C)), 0)
    finals_q = np.zeros((Q, S), bool)
    finals_q[:, 1:] = rng.random((Q, S - 1)) < 0.4
    finals_q[np.arange(Q), S - 1 - np.arange(Q)] = True
    return tecs_arena.build_tables(dm, du, finals_q, init)


def q3_tables():
    ve = VectorEngine(compile_query(STOCK_QUERIES["Q3"]),
                      max_window_events=16)
    return ve.arena_tables()


def run_tables_both(at, seed, W, eps, B=2, T=32, chunk=16, cap=1 << 14):
    """Random class traces and hits through fold and block arenas on the
    tables ``at``, ragged lanes, chunk by chunk; assert equality each
    chunk."""
    rng = np.random.default_rng(seed)
    C, Q = at.pred_idx.shape[0], at.num_queries
    cls = jnp.asarray(rng.integers(0, C, (T, B)), jnp.int32)
    hits = rng.random((T, B, Q)) < 0.3
    a1 = tecs_arena.init_arena(B, cap, W, at.num_states)
    a2 = tecs_arena.init_arena(B, cap, W, at.num_states)
    for lo in range(0, T, chunk):
        valid = jnp.asarray([chunk, chunk - 5][:B], jnp.int32)
        live = np.arange(chunk)[:, None] < np.asarray(valid)[None, :]
        h = jnp.asarray(hits[lo:lo + chunk] & live[:, :, None])
        gpos = jnp.broadcast_to(
            lo + jnp.arange(chunk, dtype=jnp.int32)[:, None], (chunk, B))
        start = jnp.full((B,), lo % W, jnp.int32)
        args = (cls[lo:lo + chunk], gpos, start, valid, h)
        a1, r1 = tecs_arena.arena_scan(at, a1, *args, epsilon=eps)
        a2, r2 = tecs_arena.arena_scan_block(at, a2, *args, epsilon=eps)
        assert_stores_equal(a1, a2, r1, r2, cap, f"chunk@{lo}")
    assert not np.asarray(a1["ovf"]).any()
    assert int(np.asarray(a1["ptr"]).sum()) > 0
    return a1


@pytest.mark.parametrize("case", ["synthetic0", "synthetic1", "synthetic2",
                                  "q3", "q3_lane_picks"])
def test_fold_sources_store_parity(monkeypatch, case):
    """The block fold picks each target's source cells by static slices
    and selects over the candidates the tables allow: on tables with
    fan-in of three and more, padded tails and two init states, and on
    Q3's own tables, the stores stay bit-identical to the per-event fold
    oracle.  ``q3_lane_picks`` takes the TPU's region picks (one lane
    slice per state) on this backend."""
    if case == "q3_lane_picks":
        monkeypatch.setattr(kref, "_lane_picks", lambda: True)
    if case.startswith("q3"):
        at, W, eps = q3_tables(), 16, 11
    else:
        at, W, eps = synthetic_tables(int(case[-1])), 8, 6
    lay = tecs_arena._block_layout(at, W, eps, 1 << 14)
    pi = np.clip(np.asarray(at.pred_idx), 0, at.num_states - 1)
    for k, per_s in enumerate(lay.src_cand):
        for s, cands in enumerate(per_s):
            assert set(cands) == set(pi[:, s, k].tolist())
    fan_in = max(len(c) for per_s in lay.src_cand for c in per_s)
    if case.startswith("q3"):
        assert fan_in == 2
    else:
        assert fan_in >= 3
        assert not np.asarray(at.pred_valid).all()
        assert len(at.init_states) == 2
    run_tables_both(at, seed=len(case) + fan_in, W=W, eps=eps)


def ring_wide_gathers(hlo_text, B, W):
    """Output shapes of the gathers in compiled HLO with ≥ B·W elements."""
    wide = []
    for m in re.finditer(r"= \w+\[([\d,]*)\][^=]*? gather\(", hlo_text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if int(np.prod(dims)) >= B * W:
            wide.append(dims)
    return wide


@pytest.mark.parametrize("lane_picks", [False, True])
def test_builder_has_no_state_axis_gather(monkeypatch, lane_picks):
    """Q3's block builder at W = 128 compiles with no ring-wide gather,
    with either backend's region picks: only the class-row gather of the
    predecessor tables and the hit-gated root chain (B·(ε+1)·Q elements)
    remain.  A partitioned arena engine records the plan as its arena
    route."""
    monkeypatch.setattr(kref, "_lane_picks", lambda: lane_picks)
    at, B, W, eps, T = q3_tables(), 4, 128, 63, 8
    lay = tecs_arena._block_layout(at, W, eps, 1 << 12)
    cells0 = tuple(jnp.full((B, W, at.num_states), v, jnp.int32)
                   for v in (-1, 0, -1, -1))

    def build(cells0, cls, hits, start, valid):
        return kref.arena_build_ref(
            cells0, cls, hits, start, valid, lay=lay,
            ptab=tecs_arena._ptab(at), finals_sq=at.finals_sq)

    text = jax.jit(build).lower(
        cells0, jnp.zeros((T, B), jnp.int32),
        jnp.zeros((T, B, at.num_queries), jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.full((B,), T, jnp.int32)).compile().as_text()
    assert " gather(" in text                   # the class-row gather
    assert ring_wide_gathers(text, B, W) == []

    pse = VectorEngine(compile_query(STOCK_QUERIES["Q3"]),
                       max_window_events=W).partitioned_streaming(
        ["volume"], chunk_len=16, num_lanes=2, arena_capacity=1 << 10)
    route = pse.routes["arena"]
    assert route == ops.arena_route(pse._arena_tables)
    assert route.path == "xla"
    assert "largest fan-in 2" in route.reason
    assert "no state-axis gather" in route.reason


# ---------------------------------------------------------------------------
# hypothesis variants (skip gracefully when hypothesis is missing)
# ---------------------------------------------------------------------------


@given(st.integers(min_value=0, max_value=2 ** 16),
       st.integers(min_value=0, max_value=len(QUERIES) - 1),
       st.integers(min_value=3, max_value=14))
@settings(max_examples=10, deadline=None)
def test_hypothesis_store_parity(seed, qidx, eps):
    ve = VectorEngine(QUERIES[qidx], epsilon=eps, use_pallas=False)
    run_both(ve, make_streams(seed, 1, 48), eps=eps, chunk=12)


@given(st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=5, deadline=None)
def test_hypothesis_segmented_parity(seed):
    ve = VectorEngine(QUERIES[0], epsilon=3, use_pallas=False)
    run_both(ve, make_streams(seed, 2, 96), eps=3, chunk=32, n_seg=2)
