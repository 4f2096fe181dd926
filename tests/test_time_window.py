"""Time windows as a first-class device concept (DESIGN.md §9).

The compiled query's ``WITHIN`` clause — count *and* time based — now
drives device evaluation end to end: the encoder emits a per-event
timestamp operand, the kernels evict by timestamp mask, the streaming /
PARTITION BY runtimes thread per-lane timestamps, and the tECS arena
expires cells by the same mask.  This suite pins:

* the ``epsilon=`` back-compat shim (contradictions raise, absence of a
  clause warns);
* device ≡ host count/hit/match-set parity on time-window queries —
  one-shot, chunk-straddling streaming, NULL-key PARTITION BY, packed
  multi-query, enumeration included;
* inclusive boundary semantics at equal timestamps;
* the ``max_window_events`` rate-bound overflow latch;
* the feed-time monotonicity audit.
"""
import random
import warnings

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import Event, compile_query
from repro.core.engine import Engine, WindowSpec
from repro.core.partition import PartitionedEngine
from repro.kernels import ops
from repro.kernels.window import (DeviceWindow, audit_monotone_ts,
                                  resolve_window)
from repro.vector import (PartitionedStreamingEngine, StreamingVectorEngine,
                          VectorEngine)
from repro.vector.multiquery import MultiQueryEngine

QT_TIME = "SELECT * FROM S WHERE A ; B+ ; C WITHIN 7 seconds"
QT_ATTR = "SELECT * FROM S WHERE A ; B+ ; C WITHIN 7 [ts]"


def ts_stream(seed, T, alphabet="ABCX", max_gap=3, time_attr=None,
              key_attrs=False):
    """Monotone integer timestamps with random (possibly zero) gaps —
    equal-timestamp runs and window-straddling jumps both occur."""
    rng = random.Random(seed)
    t, out = 0, []
    for _ in range(T):
        t += rng.randint(0, max_gap)
        attrs = {}
        if time_attr:
            attrs[time_attr] = t
        if key_attrs:
            attrs["uid"] = rng.choice(("u1", "u2", 7, None))
            if attrs["uid"] is None:
                del attrs["uid"]
        out.append(Event(rng.choice(alphabet), attrs,
                         timestamp=None if time_attr else float(t)))
    return out


def host_counts(qtext, stream):
    q = compile_query(qtext)
    eng = Engine(q.cea, window=q.query.window)
    return [len(eng.process(ev)) for ev in stream]


def host_match_sets(qtext, stream):
    q = compile_query(qtext)
    eng = Engine(q.cea, window=q.query.window)
    out = {}
    for t, ev in enumerate(stream):
        ces = eng.process(ev)
        if ces:
            out[t] = {(c.start, c.end, c.data) for c in ces}
    return out


def ce_set(ces):
    return {(c.start, c.end, c.data) for c in ces}


# ---------------------------------------------------------------------------
# epsilon= back-compat shim (satellite: guard across all four engines)
# ---------------------------------------------------------------------------


def test_epsilon_contradicting_count_clause_raises():
    with pytest.raises(ValueError, match="contradicts"):
        VectorEngine("SELECT * FROM S WHERE A ; B WITHIN 8 events",
                     epsilon=9, use_pallas=False)


def test_epsilon_agreeing_with_count_clause_ok():
    ve = VectorEngine("SELECT * FROM S WHERE A ; B WITHIN 8 events",
                      epsilon=8, use_pallas=False)
    assert ve.epsilon == 8 and ve.window.kind == "events"


def test_count_clause_drives_window_without_epsilon():
    ve = VectorEngine("SELECT * FROM S WHERE A ; B WITHIN 11 events",
                      use_pallas=False)
    assert ve.epsilon == 11 and ve.ring >= 12


def test_epsilon_contradicts_time_clause_raises():
    with pytest.raises(ValueError, match="time window"):
        VectorEngine(QT_TIME, epsilon=7, use_pallas=False)


def test_epsilon_without_clause_warns_deprecation():
    with pytest.warns(DeprecationWarning, match="WITHIN"):
        ve = VectorEngine("SELECT * FROM S WHERE A ; B", epsilon=5,
                          use_pallas=False)
    assert ve.epsilon == 5


def test_no_clause_no_epsilon_raises():
    with pytest.raises(ValueError, match="bounded window"):
        VectorEngine("SELECT * FROM S WHERE A ; B", use_pallas=False)


def test_multiquery_guard_mixed_windows_and_epsilon():
    with pytest.raises(ValueError, match="distinct WITHIN"):
        MultiQueryEngine(["SELECT * FROM S WHERE A ; B WITHIN 4 events",
                          "SELECT * FROM S WHERE B ; C WITHIN 5 events"],
                         use_pallas=False)
    with pytest.raises(ValueError, match="contradicts"):
        MultiQueryEngine(["SELECT * FROM S WHERE A ; B WITHIN 4 events",
                          "SELECT * FROM S WHERE B ; C WITHIN 4 events"],
                         epsilon=5, use_pallas=False)
    with pytest.raises(ValueError, match="distinct WITHIN"):
        # same kind+size but different clocks is still a mismatch (and the
        # message must not crash ordering None against a str time_attr)
        MultiQueryEngine(["SELECT * FROM S WHERE A ; B WITHIN 30 seconds",
                          "SELECT * FROM S WHERE B ; C WITHIN 30 [clk]"],
                         use_pallas=False)
    mq = MultiQueryEngine(["SELECT * FROM S WHERE A ; B WITHIN 4 events",
                           "SELECT * FROM S WHERE B ; C WITHIN 4 events"],
                          use_pallas=False)
    assert mq.epsilon == 4


def test_streaming_engines_inherit_query_window():
    ve = VectorEngine(QT_TIME, use_pallas=False, max_window_events=32)
    se = StreamingVectorEngine(ve, chunk_len=8, batch=2)
    assert se.window.is_time and se.window.size == 7.0
    pse = PartitionedStreamingEngine(ve, ("uid",), chunk_len=8, num_lanes=2)
    assert pse.window.is_time
    with pytest.raises(ValueError, match="time window"):
        # the guard fires at engine construction, before streaming wrappers
        StreamingVectorEngine(
            VectorEngine(QT_TIME, epsilon=9, use_pallas=False),
            chunk_len=8, batch=2)


def test_resolve_window_shapes():
    w = resolve_window(WindowSpec.events(5))
    assert (w.kind, w.epsilon, w.ring) == ("events", 5, 8)
    with pytest.raises(ValueError, match="TIME window"):
        # a rate bound on a count window is a contradiction, not a no-op
        resolve_window(WindowSpec.events(5), max_window_events=16)
    w = resolve_window(WindowSpec.time(30.0, "ts"), max_window_events=20)
    assert w.is_time and w.time_attr == "ts" and w.ring == 24
    assert w.epsilon == w.ring - 1
    w = DeviceWindow.time(2.5)  # default rate bound
    assert w.ring >= 64


# ---------------------------------------------------------------------------
# device ≡ host parity: one-shot counting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qtext,time_attr", [
    (QT_TIME, None),
    (QT_ATTR, "ts"),
    ("SELECT * FROM S WHERE A ; (B OR C) ; A WITHIN 5 seconds", None),
    ("SELECT * FROM S WHERE B+ WITHIN 4 seconds", None),
])
@pytest.mark.parametrize("seed", [0, 3])
def test_time_window_counts_match_host(qtext, time_attr, seed):
    T, B = 48, 2
    streams = [ts_stream(seed * 7 + b, T, time_attr=time_attr)
               for b in range(B)]
    ve = VectorEngine(qtext, use_pallas=False, max_window_events=T)
    counts, state = ve.run(streams)
    assert not ve.window_overflow(state).any()
    for b, s in enumerate(streams):
        assert counts[:, b].tolist() == host_counts(qtext, s), (qtext, b)


def test_time_window_fused_pallas_kernel_parity():
    """The fused Pallas kernel (interpret mode off-TPU) implements the same
    timestamp-ring eviction as the XLA/ref path."""
    T, B = 24, 3
    streams = [ts_stream(11 + b, T) for b in range(B)]
    ve_k = VectorEngine(QT_TIME, use_pallas=True, impl="fused",
                        max_window_events=T)
    ve_r = VectorEngine(QT_TIME, use_pallas=False, max_window_events=T)
    ck, sk = ve_k.run(streams)
    cr, sr = ve_r.run(streams)
    np.testing.assert_array_equal(ck, cr)
    np.testing.assert_array_equal(np.asarray(sk["C"]), np.asarray(sr["C"]))
    np.testing.assert_array_equal(np.asarray(sk["ts"]), np.asarray(sr["ts"]))
    np.testing.assert_array_equal(np.asarray(sk["ovf"]),
                                  np.asarray(sr["ovf"]))


def test_count_window_is_degenerate_time_window():
    """WITHIN n events ≡ WITHIN n [pos] over a stream timestamped by
    position — the unified eviction semantics (DESIGN.md §9)."""
    T, eps, seed = 40, 6, 5
    rng = random.Random(seed)
    types = [rng.choice("ABCX") for _ in range(T)]
    ev_cnt = [Event(t) for t in types]
    ev_time = [Event(t, {"pos": i}) for i, t in enumerate(types)]
    qc = f"SELECT * FROM S WHERE A ; B+ ; C WITHIN {eps} events"
    qt = f"SELECT * FROM S WHERE A ; B+ ; C WITHIN {eps} [pos]"
    cc, _ = VectorEngine(qc, use_pallas=False).run([ev_cnt])
    ct, _ = VectorEngine(qt, use_pallas=False,
                         max_window_events=eps + 1).run([ev_time])
    np.testing.assert_array_equal(cc, ct)


def test_equal_timestamps_at_boundary_inclusive():
    """Host semantics keep start i with ts_i == ts_j − size (inclusive);
    the device mask must agree exactly."""
    qtext = "SELECT * FROM S WHERE A ; B WITHIN 5 [ts]"
    for gap, expect in ((5, 1), (6, 0)):
        stream = [Event("A", {"ts": 0}), Event("B", {"ts": gap})]
        want = host_counts(qtext, stream)
        assert want[-1] == expect
        ve = VectorEngine(qtext, use_pallas=False, max_window_events=8)
        counts, _ = ve.run([stream])
        assert counts[:, 0].tolist() == want
    # a run of equal timestamps sits entirely inside any window
    stream = [Event(t, {"ts": 3}) for t in "AAABB"]
    ve = VectorEngine(qtext, use_pallas=False, max_window_events=8)
    counts, _ = ve.run([stream])
    assert counts[:, 0].tolist() == host_counts(qtext, stream)


# ---------------------------------------------------------------------------
# streaming: chunk-straddling time windows, compile-once
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_time_window_chunked_equals_whole_and_host(chunk):
    T, B = 48, 2
    streams = [ts_stream(31 + b, T, max_gap=4) for b in range(B)]
    ve = VectorEngine(QT_TIME, use_pallas=False, max_window_events=T)
    whole, _ = ve.run(streams)
    se = StreamingVectorEngine(ve, chunk_len=chunk, batch=B)
    parts = []
    for lo in range(0, T, chunk):
        c, _ = se.feed([s[lo:lo + chunk] for s in streams])
        parts.append(c)
    assert se.compile_count == 1
    np.testing.assert_array_equal(np.concatenate(parts), whole)
    for b, s in enumerate(streams):
        assert whole[:, b].tolist() == host_counts(QT_TIME, s)


def test_time_window_monotonicity_audit():
    ve = VectorEngine(QT_ATTR, use_pallas=False, max_window_events=16)
    se = StreamingVectorEngine(ve, chunk_len=4, batch=1)
    good = [Event("A", {"ts": v}) for v in (0, 1, 1, 5)]
    se.feed([good])
    bad = [Event("A", {"ts": v}) for v in (6, 7, 3, 8)]
    with pytest.raises(ValueError, match="monotone"):
        se.feed([bad])
    # regression across the chunk boundary is also caught
    se.reset()
    se.feed([good])
    with pytest.raises(ValueError, match="monotone"):
        se.feed([[Event("A", {"ts": v}) for v in (4, 9, 10, 11)]])
    assert audit_monotone_ts(np.asarray([[0.], [2.]])).tolist() == [2.0]


def test_rate_bound_overflow_latches():
    """More than max_window_events simultaneously-live starts: the lane's
    ovf flag latches; recognition continues without raising."""
    qtext = "SELECT * FROM S WHERE A ; B WITHIN 1000 [ts]"
    T = 24
    stream = [Event("A", {"ts": i}) for i in range(T)]  # all in-window
    ve = VectorEngine(qtext, use_pallas=False, max_window_events=8)
    counts, state = ve.run([stream])
    assert ve.window_overflow(state).tolist() == [True]
    se = StreamingVectorEngine(ve, chunk_len=8, batch=1)
    for lo in range(0, T, 8):
        se.feed([stream[lo:lo + 8]])
    assert se.window_overflow.tolist() == [True]
    # a sparse stream never latches
    ve2 = VectorEngine(qtext, use_pallas=False, max_window_events=8)
    sparse = [Event("A", {"ts": 2000 * i}) for i in range(T)]
    _, st2 = ve2.run([sparse])
    assert not ve2.window_overflow(st2).any()


# ---------------------------------------------------------------------------
# tECS arena: enumerated match sets under time windows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qtext,time_attr", [
    (QT_TIME, None),
    (QT_ATTR, "ts"),
    ("SELECT * FROM S WHERE B+ WITHIN 4 seconds", None),
])
@pytest.mark.parametrize("arena_impl", ["block", "fold"])
def test_time_window_enumeration_matches_host(qtext, time_attr, arena_impl):
    T, B, seed = 40, 2, 17
    streams = [ts_stream(seed + b, T, time_attr=time_attr)
               for b in range(B)]
    ve = VectorEngine(qtext, use_pallas=False, max_window_events=T,
                      arena_impl=arena_impl)
    counts, matches = ve.run_enumerate([list(s) for s in streams])
    for b, s in enumerate(streams):
        want = host_match_sets(qtext, s)
        got = {t: ce_set(ces) for (t, bb), ces in matches.items()
               if bb == b}
        assert got == want, (qtext, arena_impl, b)
        for t, st in want.items():
            assert counts[t, b] == len(st)


def test_time_window_arena_block_equals_fold_bitwise():
    """The block builder replays the fold's allocation order under
    time-window expiry too — full node stores (and roots) bit-identical,
    the same contract tests/test_arena_block.py pins for count windows."""
    import jax
    from repro.vector import tecs_arena
    T, B, seed = 32, 2, 23
    ve = VectorEngine(QT_TIME, use_pallas=False, max_window_events=T)
    streams = [ts_stream(seed + b, T) for b in range(B)]
    attrs, ts = ve.encode_ts(streams)
    tbl = ve.tables
    atables = ve.arena_tables()

    def run(arena_impl):
        state = ve.init_state(B)
        arena = tecs_arena.init_arena(B, 1 << 14, ve.ring,
                                      atables.num_states)
        step = jax.jit(lambda a, st, ar, t: tecs_arena.scan_chunk(
            atables, ar, a, st, specs=ve.encoder.specs,
            class_of=tbl.class_of, class_ind=tbl.class_ind,
            m_all=tbl.m_all, finals_q=tbl.finals[None, :],
            init_mask=tbl.init_mask, window=ve.window, start=0, gbase=0,
            route=ops.REF_ROUTE, arena_impl=arena_impl, event_ts=t))
        m, _, arena, roots = step(attrs, state, arena, ts)
        return np.asarray(m), arena, np.asarray(roots)

    m_b, ar_b, roots_b = run("block")
    m_f, ar_f, roots_f = run("fold")
    np.testing.assert_array_equal(m_b, m_f)
    np.testing.assert_array_equal(roots_b, roots_f)
    cap = 1 << 14
    for k in ("cell", "ptr", "ovf"):
        np.testing.assert_array_equal(np.asarray(ar_b[k]),
                                      np.asarray(ar_f[k]), err_msg=k)
    for k in ("kind", "pos", "maxs", "left", "right"):
        # sink slot excluded, as in tests/test_arena_block.py (the fold's
        # masked-out writes divert there by construction)
        np.testing.assert_array_equal(np.asarray(ar_b[k])[:, :cap],
                                      np.asarray(ar_f[k])[:, :cap],
                                      err_msg=k)
    for b in range(B):
        tecs_arena.check_invariants(tecs_arena.ArenaSnapshot(ar_b), b)


def test_time_window_streaming_enumeration_across_chunks():
    qtext, T, CH = QT_TIME, 48, 8
    streams = [ts_stream(41, T, max_gap=4)]
    ve = VectorEngine(qtext, use_pallas=False, max_window_events=T)
    se = StreamingVectorEngine(ve, chunk_len=CH, batch=1,
                               arena_capacity=1 << 15)
    hits = []
    for lo in range(0, T, CH):
        _, h = se.feed([s[lo:lo + CH] for s in streams])
        hits += h
    assert se.compile_count == 1
    res = se.enumerate_hits(hits)
    want = host_match_sets(qtext, streams[0])
    got = {p: ce_set(ces) for (p, b), ces in res.items() if ces}
    assert got == want


# ---------------------------------------------------------------------------
# PARTITION BY + packed multi-query under time windows
# ---------------------------------------------------------------------------


def test_time_window_partitioned_matches_host():
    qtext = "SELECT * FROM S WHERE A ; B+ ; C WITHIN 9 seconds"
    T, CH, L = 64, 16, 4
    stream = ts_stream(51, T, max_gap=2, key_attrs=True)
    q = compile_query(qtext)
    pe = PartitionedEngine(lambda: Engine(q.cea, window=q.query.window),
                           ("uid",))
    want_counts = [len(pe.process(e)) for e in stream]
    want_sets = {}
    pe2 = PartitionedEngine(lambda: Engine(q.cea, window=q.query.window),
                            ("uid",))
    for t, ev in enumerate(stream):
        ces = pe2.process(ev)
        if ces:
            want_sets[t] = ce_set(ces)

    ve = VectorEngine(qtext, use_pallas=False, max_window_events=T)
    pse = PartitionedStreamingEngine(ve, ("uid",), chunk_len=CH,
                                     num_lanes=L,
                                     arena_capacity=1 << 15)
    counts, hits = [], []
    for lo in range(0, T, CH):
        c, h = pse.feed(stream[lo:lo + CH])
        counts.append(c)
        hits += h
    assert pse.compile_count == 1
    assert pse.stats.spilled_table == 0 and pse.stats.evicted_lanes == 0
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))
    got = {p: ce_set(ces)
           for p, ces in pse.enumerate_hits(hits).items() if ces}
    assert got == want_sets


def test_time_window_partitioned_null_key_events_without_clock():
    """NULL-key events join no substream — the host drops them before ever
    reading a clock, so a NULL-key event with no timestamp (or an
    out-of-order one) must not crash or trip the audit on device."""
    qtext = "SELECT * FROM S WHERE A ; B WITHIN 5 [clk]"
    stream = []
    t = 0
    for i in range(16):
        if i % 5 == 4:
            stream.append(Event("A", {}))          # NULL key, NO clk attr
        else:
            t += 1
            stream.append(Event("AB"[i % 2], {"uid": "u1", "clk": t}))
    q = compile_query(qtext)
    pe = PartitionedEngine(lambda: Engine(q.cea, window=q.query.window),
                           ("uid",))
    want = [len(pe.process(e)) for e in stream]
    ve = VectorEngine(qtext, use_pallas=False, max_window_events=16)
    pse = PartitionedStreamingEngine(ve, ("uid",), chunk_len=16,
                                     num_lanes=2)
    counts, _ = pse.feed(stream)
    assert counts.tolist() == want


def test_time_window_run_accepts_per_lane_start_pos():
    """Per-lane start_pos vectors stay usable under time windows when
    events carry their own timestamps (no arrival-order fallback)."""
    T, B = 16, 2
    streams = [ts_stream(71 + b, T) for b in range(B)]
    ve = VectorEngine(QT_TIME, use_pallas=False, max_window_events=T)
    base, _ = ve.run(streams)
    lanes, _ = ve.run(streams, start_pos=jnp.zeros((B,), jnp.int32))
    np.testing.assert_array_equal(base, lanes)
    # transposed timestamp operands are rejected up front
    attrs, ts = ve.encode_ts(streams)
    with pytest.raises(ValueError, match="event_ts must be"):
        ve.pipeline(attrs, ve.init_state(B), event_ts=ts.T)


def test_time_window_packed_multiquery_matches_singles():
    queries = ["SELECT * FROM S WHERE A ; B WITHIN 6 seconds",
               "SELECT * FROM S WHERE B ; C WITHIN 6 seconds"]
    T, B = 32, 2
    streams = [ts_stream(61 + b, T) for b in range(B)]
    mq = MultiQueryEngine(queries, use_pallas=False, max_window_events=T)
    counts, _ = mq.run(streams)
    for qi, q in enumerate(queries):
        single, _ = VectorEngine(q, use_pallas=False,
                                 max_window_events=T).run(streams)
        np.testing.assert_array_equal(counts[:, :, qi], single, (qi,))
    for b, s in enumerate(streams):
        for qi, q in enumerate(queries):
            assert counts[:, b, qi].tolist() == host_counts(q, s)
