"""Dynamic query fleet (DESIGN.md §11): bucketed packings, the geometry
compile cache, live state migration across repacks, per-query cost
reports, and fleet-level crash recovery.

The fleet contract under test: every live query's counts/hits/enumerations
are bit-identical to a freshly built static engine fed the same events from
the query's add position; add/remove churn compiles at most one executable
per distinct bucket geometry; snapshots carry per-query membership and
per-bucket packing fingerprints, so a kill -9 mid-churn restores to the
exact pre-crash fleet.
"""
import os
import random
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core.events import Event
from repro.runtime.fleet import QueryFleet
from repro.vector.multiquery import (MultiQueryEngine, PackingInvariantError,
                                     build_packing, check_packing_invariants)
from repro.vector.partitioned import PartitionedStreamingEngine
from repro.vector.streaming import StreamingVectorEngine

Q_A = ("SELECT * FROM S WHERE (E AS a; E AS b) "
       "FILTER a[x > 6] AND b[x < 3] WITHIN 8 events")
Q_B = ("SELECT * FROM S WHERE (E AS a; E AS b) "
       "FILTER a[y > 7] AND b[y > 7] WITHIN 8 events")
Q_C = ("SELECT * FROM S WHERE (E AS a; E AS b) "
       "FILTER a[x > 5] AND b[y < 2] WITHIN 4 events")
Q_D = ("SELECT * FROM S WHERE (E AS a; E AS b; E AS c) "
       "FILTER a[x > 4] AND b[y > 4] AND c[x < 4] WITHIN 8 events")
Q_T = ("SELECT * FROM S WHERE (E AS a; E AS b) "
       "FILTER a[x > 6] AND b[x < 3] WITHIN 8 seconds")
POOL = [Q_A, Q_B, Q_C, Q_D]

T, B = 16, 2


def mk_chunks(seed, n):
    """n deterministic (B streams × T events) chunks; timestamp = position,
    one unit apart — so 'WITHIN 8 events' and 'WITHIN 8 seconds' agree."""
    rng = np.random.default_rng(seed)
    out = []
    for c in range(n):
        out.append([[Event("E", {"x": float(rng.integers(0, 10)),
                                 "y": float(rng.integers(0, 10))},
                           timestamp=float(c * T + t))
                     for t in range(T)] for _ in range(B)])
    return out


def static_counts(queries, chunks, **kw):
    """Oracle: a freshly packed static engine fed ``chunks`` from empty."""
    eng = MultiQueryEngine(queries, use_pallas=False, impl="ref", **kw)
    se = StreamingVectorEngine(eng, T, B, impl="ref")
    return [se.feed(c)[0][:, :, :len(queries)] for c in chunks]


def fleet_col(fleet, qid):
    return fleet.live_qids.index(qid)


# ---------------------------------------------------------------------------
# bucket parity & mixed windows (satellite 1)
# ---------------------------------------------------------------------------

def test_single_bucket_parity_with_static_engine():
    chunks = mk_chunks(0, 4)
    fleet = QueryFleet(chunk_len=T, batch=B)
    qa = fleet.add_query(Q_A)
    qb = fleet.add_query(Q_B)
    assert fleet.num_buckets == 1
    got = [fleet.feed(c)[0] for c in chunks]
    want = static_counts([Q_A, Q_B], chunks)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g[:, :, fleet_col(fleet, qa)],
                                      w[:, :, 0])
        np.testing.assert_array_equal(g[:, :, fleet_col(fleet, qb)],
                                      w[:, :, 1])


def test_multiquery_engine_error_names_the_fleet():
    with pytest.raises(ValueError, match="distinct WITHIN") as ei:
        MultiQueryEngine([Q_A, Q_C])
    assert "QueryFleet" in str(ei.value)


def test_mixed_windows_route_to_buckets():
    """Count 8 / count 4 / time 8s queries — three buckets, each matching
    its own static oracle (timestamps are one unit apart, so the time
    query's matches equal its count twin's)."""
    chunks = mk_chunks(1, 4)
    fleet = QueryFleet(chunk_len=T, batch=B)
    qa = fleet.add_query(Q_A)
    qc = fleet.add_query(Q_C)
    qt = fleet.add_query(Q_T)
    assert fleet.num_buckets == 3
    assert fleet.bucket_of(qa)[0] == "events"
    assert fleet.bucket_of(qt)[0] == "time"
    got = [fleet.feed(c)[0] for c in chunks]
    for q, text in ((qa, Q_A), (qc, Q_C)):
        want = static_counts([text], chunks)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g[:, :, fleet_col(fleet, q)],
                                          w[:, :, 0])
    # unit-spaced timestamps: WITHIN 8 seconds ≡ WITHIN 8 events
    for g in got:
        np.testing.assert_array_equal(g[:, :, fleet_col(fleet, qt)],
                                      g[:, :, fleet_col(fleet, qa)])


def test_add_bad_query_rolls_back():
    chunks = mk_chunks(2, 2)
    fleet = QueryFleet(chunk_len=T, batch=B)
    qa = fleet.add_query(Q_A)
    before = fleet.feed(chunks[0])[0]
    with pytest.raises(Exception):
        fleet.add_query("THIS IS NOT CEQL")
    assert fleet.live_qids == [qa]
    after = fleet.feed(chunks[1])[0]          # healthy resident survives
    want = static_counts([Q_A], chunks)
    np.testing.assert_array_equal(before[:, :, 0], want[0][:, :, 0])
    np.testing.assert_array_equal(after[:, :, 0], want[1][:, :, 0])
    with pytest.raises(KeyError):
        fleet.remove_query("nope")


# ---------------------------------------------------------------------------
# live migration across repacks (tentpole)
# ---------------------------------------------------------------------------

def test_churn_migration_parity():
    """add/feed/add/feed/remove/feed/re-add/feed: every live query's counts
    equal a fresh engine fed the query's post-add suffix."""
    chunks = mk_chunks(3, 6)
    fleet = QueryFleet(chunk_len=T, batch=B)
    qa = fleet.add_query(Q_A)
    g0 = fleet.feed(chunks[0])[0]
    qb = fleet.add_query(Q_B)                  # repack: A's run must survive
    g1 = fleet.feed(chunks[1])[0]
    g2 = fleet.feed(chunks[2])[0]
    fleet.remove_query(qb)                     # repack back down
    g3 = fleet.feed(chunks[3])[0]
    qb2 = fleet.add_query(Q_B)                 # re-added: starts empty
    g4 = fleet.feed(chunks[4])[0]
    g5 = fleet.feed(chunks[5])[0]

    # survivor A: continuous across all four packings
    want_a = static_counts([Q_A], chunks)
    for g, w in zip([g0, g1, g2, g3, g4, g5], want_a):
        np.testing.assert_array_equal(g[:, :, 0], w[:, :, 0])
    # B's first life: fresh engine over chunks 1-2
    want_b1 = static_counts([Q_B], chunks[1:3])
    np.testing.assert_array_equal(g1[:, :, 1], want_b1[0][:, :, 0])
    np.testing.assert_array_equal(g2[:, :, 1], want_b1[1][:, :, 0])
    # B's second life: state dropped at remove, fresh over chunks 4-5
    want_b2 = static_counts([Q_B], chunks[4:6])
    cb = fleet_col(fleet, qb2)
    np.testing.assert_array_equal(g4[:, :, cb], want_b2[0][:, :, 0])
    np.testing.assert_array_equal(g5[:, :, cb], want_b2[1][:, :, 0])
    assert qb not in fleet.live_qids


def test_churn_compile_cache_100_ops():
    """~100 add/removes over a live stream: at most one compile per distinct
    bucket geometry, and the overwhelming majority of ops are cache hits."""
    rng = random.Random(11)
    chunks = mk_chunks(4, 120)
    fleet = QueryFleet(chunk_len=T, batch=B)
    live = {}                      # query text -> (qid, chunks fed at add)
    for q in POOL:
        live[q] = (fleet.add_query(q), 0)
    ops = 0
    ci = 0
    while ops < 100:
        q = rng.choice(POOL)
        if q in live and len(live) > 1:
            fleet.remove_query(live.pop(q)[0])
        elif q not in live:
            live[q] = (fleet.add_query(q), ci)
        else:
            continue
        ops += 1
        if ops % 5 == 0:
            fleet.feed(chunks[ci])
            ci += 1
    assert fleet.compile_count <= fleet.distinct_geometries
    # the pool spans 2 windows × ≤2 query-slot buckets × 1 state bucket,
    # plus attr/class padding variants — far fewer geometries than ops
    assert fleet.distinct_geometries <= 8, fleet.distinct_geometries
    # ops that empty a bucket skip the cache entirely; every other repack
    # must hit it (builds are bounded by the distinct geometries)
    assert fleet.cache_hits >= 2 * ops // 3, fleet.cache_hits
    # the stream kept flowing: every survivor still matches a fresh oracle
    # fed its post-add suffix (live in-window runs carry across the feed)
    got = fleet.feed(chunks[ci])[0]
    for q, (qid, added_at) in live.items():
        want = static_counts([q], chunks[added_at:ci + 1])
        np.testing.assert_array_equal(got[:, :, fleet_col(fleet, qid)],
                                      want[-1][:, :, 0])


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=len(POOL) - 1),
                min_size=1, max_size=12),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_random_churn_match_parity(ops, seed):
    """Property: under any interleaving of add/remove/feed, the final feed's
    counts per live query equal a fresh engine fed that query's post-add
    suffix (hypothesis-driven churn schedules)."""
    chunks = mk_chunks(seed % 1000, len(ops) + 2)
    fleet = QueryFleet(chunk_len=T, batch=B)
    live = {}          # query text -> (qid, add position in chunks)
    base = fleet.add_query(Q_A)   # keep ≥1 resident so feeds are non-empty
    fed = 0
    for op in ops:
        q = POOL[op]
        if q == Q_A:
            fleet.feed(chunks[fed]); fed += 1
            continue
        if q in live:
            fleet.remove_query(live.pop(q)[0])
        else:
            live[q] = (fleet.add_query(q), fed)
    final = fleet.feed(chunks[fed])[0]
    want = static_counts([Q_A], chunks[:fed + 1])
    np.testing.assert_array_equal(final[:, :, fleet_col(fleet, base)],
                                  want[-1][:, :, 0])
    for q, (qid, added_at) in live.items():
        w = static_counts([q], chunks[added_at:fed + 1])
        np.testing.assert_array_equal(final[:, :, fleet_col(fleet, qid)],
                                      w[-1][:, :, 0])


def test_arena_enumeration_parity_after_churn():
    """tECS arena on: after a mid-stream repack, surviving queries'
    enumerations are identical to an engine that never repacked."""
    chunks = mk_chunks(5, 4)
    fleet = QueryFleet(chunk_len=T, batch=B, arena_capacity=1 << 12)
    qa = fleet.add_query(Q_A)
    qb = fleet.add_query(Q_B)
    hits = []
    hits += fleet.feed(chunks[0])[1]
    hits += fleet.feed(chunks[1])[1]
    fleet.remove_query(qb)                       # repack with the arena live
    hits += fleet.feed(chunks[2])[1]
    hits += fleet.feed(chunks[3])[1]

    eng = MultiQueryEngine([Q_A], use_pallas=False, impl="ref")
    se = StreamingVectorEngine(eng, T, B, impl="ref",
                               arena_capacity=1 << 12)
    shits = []
    for c in chunks:
        shits += se.feed(c)[1]

    def norm(ces):
        return {(c.start, c.end, c.data) for c in ces}
    checked = 0
    for p, b in shits:
        want = norm(se.enumerate(p, b, query=0))
        if not want:
            continue
        got = norm(fleet.enumerate(qa, p, b))
        assert got == want, (p, b)
        checked += 1
    assert checked > 0


# ---------------------------------------------------------------------------
# packing invariants (satellite 2)
# ---------------------------------------------------------------------------

def _padded_packing():
    return build_packing(
        [Q_A, Q_B], pad_states=16, pad_queries=4, pad_classes=16, pad_bits=8)


def test_packing_invariants_pass_on_padded_packing():
    pk = _padded_packing()
    assert pk.padded_states == 16 and pk.padded_queries == 4
    check_packing_invariants(pk)               # no raise
    # de-pack map partitions the real states and is -1 on padding
    own = pk.query_of_state()
    assert own.shape == (pk.padded_states,)
    assert (own[pk.num_states:] == -1).all()
    for slot in range(pk.num_queries):
        lo, hi = pk.state_range(slot)
        assert (own[lo:hi] == slot).all()


@pytest.mark.parametrize("corrupt", [
    "m_pad_row", "m_pad_class", "init_pad", "finals_pad", "class_of_pad"])
def test_packing_invariants_catch_live_padding(corrupt):
    import jax.numpy as jnp
    pk = _padded_packing()
    t = pk.tables
    if corrupt == "m_pad_row":                 # transition out of padding
        m = np.array(t.m_all)
        m[0, pk.num_states, 0] = 1.0
        t.m_all = jnp.asarray(m)
    elif corrupt == "m_pad_class":             # padded class comes alive
        if pk.num_classes == pk.padded_classes:
            pytest.skip("no padded classes in this packing")
        m = np.array(t.m_all)
        m[pk.num_classes] = np.eye(pk.padded_states)
        t.m_all = jnp.asarray(m)
    elif corrupt == "init_pad":                # padding gets seeded
        im = np.array(t.init_mask)
        im[pk.num_states] = 1.0
        t.init_mask = jnp.asarray(im)
    elif corrupt == "finals_pad":              # dead query slot matches
        fin = np.array(t.finals)
        fin[pk.num_queries, 0] = 1.0
        t.finals = jnp.asarray(fin)
    elif corrupt == "class_of_pad":            # padded bit-vector row live
        if pk.num_bits == pk.padded_bits:
            pytest.skip("no padded bit-vector rows in this packing")
        cof = np.array(t.class_of)
        cof[1 << pk.num_bits] = 1
        t.class_of = jnp.asarray(cof)
    with pytest.raises(PackingInvariantError):
        check_packing_invariants(pk)


# ---------------------------------------------------------------------------
# fleet snapshots & crash recovery (satellite 3)
# ---------------------------------------------------------------------------

def test_fleet_snapshot_restore_roundtrip():
    chunks = mk_chunks(6, 4)
    fleet = QueryFleet(chunk_len=T, batch=B)
    fleet.add_query(Q_A)
    fleet.add_query(Q_C)                       # two buckets
    fleet.feed(chunks[0]); fleet.feed(chunks[1])
    snap = fleet.snapshot()
    # buckets are recorded in sorted window order (4-event before 8-event)
    assert [b["qids"] for b in snap["meta"]["buckets"]] == [["q1"], ["q0"]]
    ref = [fleet.feed(c)[0] for c in chunks[2:]]

    f2 = QueryFleet(chunk_len=T, batch=B)
    f2.restore(snap)
    assert f2.live_qids == fleet.live_qids
    assert f2.position == 2 * T
    got = [f2.feed(c)[0] for c in chunks[2:]]
    for g, w in zip(got, ref):
        np.testing.assert_array_equal(g, w)


def test_fleet_restore_refuses_mismatch():
    fleet = QueryFleet(chunk_len=T, batch=B)
    fleet.add_query(Q_A)
    fleet.feed(mk_chunks(7, 1)[0])
    snap = fleet.snapshot()

    with pytest.raises(ValueError, match="chunk_len"):
        QueryFleet(chunk_len=2 * T, batch=B).restore(snap)
    # tampered membership: recorded fingerprint no longer matches
    bad = {"arrays": snap["arrays"],
           "meta": {**snap["meta"],
                    "queries": {"q0": Q_B}}}
    with pytest.raises(ValueError, match="fingerprint"):
        QueryFleet(chunk_len=T, batch=B).restore(bad)


_WORKER = textwrap.dedent("""
    import os, signal, sys
    sys.path.insert(0, {testdir!r})
    from repro.runtime import RecoveringStreamRunner
    from repro.runtime.fleet import QueryFleet
    from test_fleet import Q_A, Q_B, Q_C, T, B, mk_chunks

    directory, crash_after = sys.argv[1], int(sys.argv[2])
    chunks = mk_chunks(8, 12)
    fleet = QueryFleet(chunk_len=T, batch=B)
    fleet.add_query(Q_A, qid="qa")

    def apply_churn(i, fleet):
        # deterministic mid-stream churn, keyed to the chunk index so a
        # resumed worker reconstructs the same membership trajectory.
        # Applied BEFORE feeding chunk i: checkpoints taken inside
        # process() then cover exactly churn ops 0..i and feeds 0..i.
        if i == 2: fleet.add_query(Q_B, qid="qb")
        if i == 5: fleet.add_query(Q_C, qid="qc")
        if i == 8: fleet.remove_query("qb")

    runner = RecoveringStreamRunner(fleet, directory, every=3)
    runner.resume()
    for i in range(runner.chunk_index, len(chunks)):
        apply_churn(i, fleet)
        runner.process(chunks[i])
        if runner.chunk_index == crash_after:
            os.kill(os.getpid(), signal.SIGKILL)
    runner.close()
    print("fleet-worker-done", sorted(fleet.live_qids))
""")


def test_fleet_kill9_crash_recovery_mid_churn(tmp_path):
    """kill -9 a fleet worker mid-churn (after a repack, checkpoint behind
    the log); the restarted worker restores membership from the per-query
    manifest, replays with emission suppressed, and the cumulative match
    set equals an uninterrupted run."""
    import repro
    from repro.runtime import cumulative_matches
    worker = tmp_path / "fleet_worker.py"
    worker.write_text(_WORKER.format(testdir=os.path.dirname(__file__)))
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [p for p in (env.get("PYTHONPATH", ""),) if p])
    cmd = [sys.executable, str(worker)]

    d_ref = str(tmp_path / "uninterrupted")
    p = subprocess.run(cmd + [d_ref, "-1"], env=env, capture_output=True,
                       text=True)
    assert p.returncode == 0, p.stderr
    oracle = cumulative_matches(d_ref)
    assert oracle["hits"], "workload produced no matches"

    d = str(tmp_path / "crashed")
    # die after chunk 7: checkpoint sits at 6, the log reaches 7, and the
    # remove at i=8 has not happened yet — checkpoint behind log, mid-churn
    p = subprocess.run(cmd + [d, "8"], env=env)
    assert p.returncode == -signal.SIGKILL, p.returncode
    p = subprocess.run(cmd + [d, "-1"], env=env, capture_output=True,
                       text=True)
    assert p.returncode == 0, p.stderr
    assert cumulative_matches(d) == oracle


# ---------------------------------------------------------------------------
# repack-aware restore on the PARTITION BY engine
# ---------------------------------------------------------------------------

def test_partitioned_repack_restore_parity():
    """PARTITION BY lanes + a packing change in one restore: the survivor's
    per-position counts match a never-repacked run."""
    rng = random.Random(13)
    stream = [Event("E", {"x": float(rng.randrange(10)),
                          "y": float(rng.randrange(10)),
                          "uid": rng.choice(["u1", "u2", "u3"])})
              for _ in range(64)]
    chunks = [stream[lo:lo + 16] for lo in range(0, 64, 16)]

    def mk(queries, qids):
        pk = build_packing(queries, qids=qids)
        eng = MultiQueryEngine.from_packing(pk, use_pallas=False, impl="ref")
        return PartitionedStreamingEngine(eng, ("uid",), chunk_len=16,
                                          num_lanes=4)

    base = mk([Q_A], ("qa",))
    want = [base.feed(c)[0] for c in chunks]

    e2 = mk([Q_A, Q_B], ("qa", "qb"))
    for c in chunks[:2]:
        e2.feed(c)
    e3 = mk([Q_A, Q_D], ("qa", "qd"))          # drop qb, add qd, qa survives
    e3.restore(e2.snapshot(), migrate_packing=True)
    got = [e3.feed(c)[0] for c in chunks[2:]]
    for g, w in zip(got, want[2:]):
        np.testing.assert_array_equal(g[:, 0], w[:, 0])


# ---------------------------------------------------------------------------
# cost reports
# ---------------------------------------------------------------------------

def test_cost_report_populated():
    chunks = mk_chunks(9, 3)
    fleet = QueryFleet(chunk_len=T, batch=B, arena_capacity=1 << 12)
    qa = fleet.add_query(Q_A)
    qc = fleet.add_query(Q_C)
    for c in chunks:
        fleet.feed(c)
    rep = fleet.cost_report()
    assert set(rep) == {qa, qc}
    for qid in (qa, qc):
        r = rep[qid]
        assert r["states"] > 0
        assert r["events"] == len(chunks) * T * B
        assert r["bucket"] == fleet.bucket_of(qid)
        assert r["overflow_lanes"] == []
    total_hits = sum(rep[q]["hits"] for q in rep)
    total_matches = sum(rep[q]["matches"] for q in rep)
    assert total_matches >= total_hits > 0
    # arena accounting: a query with matches holds live cells and nodes
    hot = max(rep.values(), key=lambda r: r["matches"])
    assert hot["arena_cells"] > 0 and hot["arena_nodes"] > 0
