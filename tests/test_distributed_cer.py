"""Distributed CER pieces on the host mesh (compile + semantics)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import make_host_mesh, use_mesh
from repro.vector.distributed import (route_by_partition, sharded_cea_scan,
                                      sharded_cer_pipeline)
from repro.kernels import ops, ref


def tiny_tables():
    rng = np.random.default_rng(3)
    S, C = 5, 4
    M = np.zeros((C, S, S), np.float32)
    for s in range(1, S):
        for c in range(C):
            M[c, s, rng.integers(1, S)] += 1
    finals = np.zeros(S, np.float32)
    finals[S - 1] = 1
    return jnp.asarray(M), jnp.asarray(finals)


def test_sharded_scan_matches_local():
    mesh = make_host_mesh()
    M, finals = tiny_tables()
    T, B, eps = 20, 4, 5
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 4, (T, B)), jnp.int32)
    c0 = jnp.zeros((B, ops.ring_size(eps), 5), jnp.float32)
    with use_mesh(mesh):
        m_sh, c_sh = sharded_cea_scan(mesh, ids, M, finals, c0, epsilon=eps)
    m_loc, c_loc = ops.cea_scan(ids, M, finals, c0, epsilon=eps,
                                use_pallas=False)
    np.testing.assert_allclose(np.asarray(m_sh), np.asarray(m_loc))
    np.testing.assert_allclose(np.asarray(c_sh), np.asarray(c_loc))


def test_sharded_pipeline_matches_local_fused():
    """Sharded fused pipeline == local pipeline (zero-collective scaling)."""
    mesh = make_host_mesh()
    rng = np.random.default_rng(7)
    S, C, A, k = 5, 4, 3, 4
    specs = tuple((int(rng.integers(0, A)), int(rng.integers(0, 6)),
                   float(rng.normal())) for _ in range(k))
    class_of = jnp.asarray(rng.integers(0, C, 1 << k).astype(np.int32))
    class_ind = ops.class_indicator(np.asarray(class_of), C)
    M, finals = tiny_tables()
    finals_q = finals[None, :]
    init_mask = jnp.zeros(S).at[1].set(1.0)
    T, B, eps = 18, 4, 5
    attrs = jnp.asarray(rng.normal(size=(T, B, A)), jnp.float32)
    c0 = jnp.zeros((B, ops.ring_size(eps), S), jnp.float32)
    with use_mesh(mesh):
        m_sh, c_sh = sharded_cer_pipeline(
            mesh, attrs, specs, class_of, class_ind, M, finals_q, c0,
            init_mask=init_mask, epsilon=eps, start_pos=3, impl="fused",
            use_pallas=True)
    m_loc, c_loc = ops.cer_pipeline(
        attrs, specs, class_of, class_ind, M, finals_q, c0,
        init_mask=init_mask, epsilon=eps, start_pos=3, route=ops.REF_ROUTE)
    np.testing.assert_allclose(np.asarray(m_sh), np.asarray(m_loc))
    np.testing.assert_allclose(np.asarray(c_sh), np.asarray(c_loc))


def test_sharded_time_window_parity_with_host():
    """ROADMAP known gap: multi-lane `route_partitioned_chunk` with SHIPPED
    timestamps vs the host oracle, NULL-key rows included (DESIGN.md §9).

    Timestamps ride the router as a bitcast payload column; the local
    partitioned step must reproduce the host PartitionedEngine's per-
    substream time windows exactly (integer ticks: f32-exact)."""
    import random

    from repro.core import Event, compile_query
    from repro.core.engine import Engine, WindowSpec
    from repro.core.partition import NULL_KEY_HASH, PartitionedEngine
    from repro.vector import PartitionedStreamingEngine, VectorEngine
    from repro.vector.distributed import route_partitioned_chunk

    qtext = "SELECT * FROM S WHERE A ; B+ ; C WITHIN 12 seconds"
    rng = random.Random(19)
    t, stream = 0, []
    for _ in range(64):
        t += rng.randint(1, 2)
        stream.append(Event(rng.choice("ABC"),
                            {} if rng.random() < 0.1
                            else {"uid": rng.choice(["a", "b", None])},
                            timestamp=float(t)))
    q = compile_query(qtext)
    pe = PartitionedEngine(
        lambda: Engine(q.cea, window=WindowSpec.time(12.0)), ("uid",))
    want = [len(pe.process(e)) for e in stream]
    assert sum(want) > 0

    ve = VectorEngine(qtext, max_window_events=16)
    pse = PartitionedStreamingEngine(ve, ("uid",), chunk_len=16,
                                     num_lanes=8)
    mesh = make_host_mesh()
    got = np.zeros(len(stream), np.int64)
    hits = []
    for lo in range(0, len(stream), 16):
        attrs, keys, ts = ve.encoder.encode_stream_keyed_ts(
            stream[lo:lo + 16], ("uid",))
        pos = np.arange(lo, lo + 16, dtype=np.int32)
        with use_mesh(mesh):
            a2, k2, p2, ts2, valid, keep = route_partitioned_chunk(
                mesh, jnp.asarray(attrs), jnp.asarray(keys),
                jnp.asarray(pos), jnp.asarray(ts))
        # NULL-key rows (NULL uid or missing attr) drop sender-side
        np.testing.assert_array_equal(
            np.asarray(keep), keys != np.uint32(NULL_KEY_HASH))
        p2 = np.asarray(p2)
        counts, h = pse.feed_keyed(a2, k2, positions=p2, event_ts=ts2)
        got[p2[np.asarray(valid)]] = counts[np.asarray(valid)]
        hits += h
    assert got.tolist() == want
    assert sorted(hits) == [j for j, c in enumerate(want) if c > 0]
    # mesh-sharded operands respecialize the local step once against the
    # fresh (unsharded) initial state; it stays compiled thereafter
    assert pse.compile_count <= 2


def test_router_single_shard_identity_up_to_capacity():
    """On one shard the router is a bucket-compaction: every kept event lands
    in a slot of its own hash bucket."""
    mesh = make_host_mesh()
    N, A = 16, 3
    rng = np.random.default_rng(1)
    events = jnp.asarray(rng.normal(size=(N, A)), jnp.float32)
    keys = jnp.asarray(rng.integers(0, 100, (N,)), jnp.int32)
    with use_mesh(mesh):
        routed, keep = route_by_partition(mesh, events, keys)
    routed, keep = np.asarray(routed), np.asarray(keep)
    assert keep.all()  # single shard, capacity N ≥ all events
    # every original event row appears exactly once among routed rows
    ev = np.asarray(events)
    for i in range(N):
        assert any(np.allclose(ev[i], routed[j]) for j in range(N))
