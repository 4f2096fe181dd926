"""Fused single-pass pipeline + streaming runtime (DESIGN.md §3/§5).

Parity: fused Pallas kernel (interpret mode) vs the pure-jnp oracle over
shape/dtype sweeps; chunked-vs-whole-stream equivalence for chunk splits that
straddle the window; compile-once streaming with donated state.
"""
import random

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import Event
from repro.kernels import ops
from repro.vector import StreamingVectorEngine, VectorEngine
from repro.vector.multiquery import MultiQueryEngine


def random_pipeline(rng, S, C, A, k):
    """Random predicate specs, class table, counting tables."""
    specs = tuple((int(rng.integers(0, A)), int(rng.integers(0, 6)),
                   float(rng.normal())) for _ in range(k))
    class_of = rng.integers(0, C, 1 << k).astype(np.int32)
    M = np.zeros((C, S, S), np.float32)
    for s in range(1, S):
        for c in range(C):
            if rng.random() < 0.8:
                M[c, s, rng.integers(1, S)] += 1
    finals = (rng.random(S) < 0.4).astype(np.float32)
    finals[0] = 0.0
    init = np.zeros(S, np.float32)
    init[1] = 1.0
    return specs, class_of, M, finals, init


def pipeline_args(specs, class_of, M, finals_q, *, num_classes):
    return (jnp.asarray(class_of),
            ops.class_indicator(class_of, num_classes),
            jnp.asarray(M), jnp.asarray(finals_q))


def pipe(attrs, specs, class_of, class_ind, M, finals_q, c0, *, impl,
         return_trace=False, **kw):
    """``ops.cer_pipeline`` on the route ``impl`` plans for these shapes."""
    T, B, A = attrs.shape
    route = ops.plan_pipeline(
        T=T, B=B, A=A, W=c0.shape[1], S=M.shape[1], NC=M.shape[0],
        NQ=finals_q.shape[0], V=class_ind.shape[0], trace=return_trace,
        impl=impl)
    return ops.cer_pipeline(attrs, specs, class_of, class_ind, M, finals_q,
                            c0, route=route, return_trace=return_trace, **kw)


# ---------------------------------------------------------------------------
# fused kernel parity vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,C,k", [(4, 3, 2), (7, 5, 4), (16, 8, 6)])
@pytest.mark.parametrize("B,T,A", [(1, 9, 1), (8, 33, 3), (13, 17, 5)])
@pytest.mark.parametrize("eps", [3, 7])
def test_fused_pipeline_matches_ref(S, C, k, B, T, A, eps):
    rng = np.random.default_rng(S * 1000 + B * 10 + eps)
    specs, class_of, M, finals, init = random_pipeline(rng, S, C, A, k)
    attrs = jnp.asarray(rng.normal(size=(T, B, A)).astype(np.float32))
    c0 = jnp.zeros((B, ops.ring_size(eps), S), jnp.float32)
    args = pipeline_args(specs, class_of, M, finals[None, :], num_classes=C)
    kw = dict(init_mask=jnp.asarray(init), epsilon=eps)
    m_f, c_f = pipe(attrs, specs, *args, c0, **kw, impl="fused")
    m_u, c_u = pipe(attrs, specs, *args, c0, **kw, impl="unfused")
    m_r, c_r = pipe(attrs, specs, *args, c0, **kw, impl="ref")
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(m_u), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(c_f), np.asarray(c_r))


@pytest.mark.parametrize("S,C,k,B,T,A,eps", [(4, 3, 2, 8, 17, 2, 3),
                                             (9, 5, 4, 5, 21, 3, 7)])
def test_fused_pipeline_class_trace_matches_ref(S, C, k, B, T, A, eps):
    """return_trace parity on the real Pallas path (interpret mode): the
    kernel's class-id trace output — the tECS-arena operand (DESIGN §7) —
    must equal the oracle's bit-for-bit, and the 2-output (emit_trace off)
    and 3-output kernels must agree on matches/state."""
    rng = np.random.default_rng(S * 77 + B)
    specs, class_of, M, finals, init = random_pipeline(rng, S, C, A, k)
    attrs = jnp.asarray(rng.normal(size=(T, B, A)).astype(np.float32))
    c0 = jnp.zeros((B, ops.ring_size(eps), S), jnp.float32)
    args = pipeline_args(specs, class_of, M, finals[None, :], num_classes=C)
    kw = dict(init_mask=jnp.asarray(init), epsilon=eps)
    m_f, c_f, tr_f = pipe(attrs, specs, *args, c0, **kw,
                                      impl="fused", return_trace=True)
    m_2, c_2 = pipe(attrs, specs, *args, c0, **kw, impl="fused")
    m_r, c_r, tr_r = pipe(attrs, specs, *args, c0, **kw,
                                      impl="ref", return_trace=True)
    assert tr_f.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(tr_f), np.asarray(tr_r))
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(m_2), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(c_f), np.asarray(c_2))


def test_fused_pipeline_dynamic_start_pos_traced():
    """start_pos may be a traced scalar: one jitted executable, many offsets."""
    rng = np.random.default_rng(3)
    S, C, A, k, B, T, eps = 6, 4, 3, 4, 4, 12, 5
    specs, class_of, M, finals, init = random_pipeline(rng, S, C, A, k)
    attrs = jnp.asarray(rng.normal(size=(T, B, A)).astype(np.float32))
    c0 = jnp.zeros((B, ops.ring_size(eps), S), jnp.float32)
    args = pipeline_args(specs, class_of, M, finals[None, :], num_classes=C)
    kw = dict(init_mask=jnp.asarray(init), epsilon=eps)

    traces = []

    @jax.jit
    def step(a, c, sp):
        traces.append(1)
        return pipe(a, specs, *args, c, **kw,
                                start_pos=sp, impl="fused")

    for sp in (0, 5, 17):
        m_jit, _ = step(attrs, c0, jnp.asarray(sp, jnp.int32))
        m_ref, _ = pipe(attrs, specs, *args, c0, **kw,
                                    start_pos=sp, impl="ref")
        np.testing.assert_array_equal(np.asarray(m_jit), np.asarray(m_ref))
    assert len(traces) == 1  # dynamic start_pos → no per-offset recompile


@pytest.mark.parametrize("split", [1, 5, 8, 11])
def test_fused_chunked_equals_whole_stream(split):
    """Every chunk split — including ones straddling the ε-window — agrees
    with the whole-stream evaluation, for all three impls."""
    rng = np.random.default_rng(21)
    S, C, A, k, B, T, eps = 5, 4, 3, 4, 3, 16, 6
    specs, class_of, M, finals, init = random_pipeline(rng, S, C, A, k)
    attrs = rng.normal(size=(T, B, A)).astype(np.float32)
    c0 = jnp.zeros((B, ops.ring_size(eps), S), jnp.float32)
    args = pipeline_args(specs, class_of, M, finals[None, :], num_classes=C)
    kw = dict(init_mask=jnp.asarray(init), epsilon=eps)
    m_whole, _ = pipe(jnp.asarray(attrs), specs, *args, c0, **kw,
                                  impl="ref")
    for impl in ("fused", "unfused", "ref"):
        m1, c_mid = pipe(jnp.asarray(attrs[:split]), specs,
                                     *args, c0, **kw, impl=impl)
        m2, _ = pipe(jnp.asarray(attrs[split:]), specs, *args,
                                 c_mid, **kw, start_pos=split, impl=impl)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(m1), np.asarray(m2)]),
            np.asarray(m_whole), err_msg=f"impl={impl} split={split}")


# ---------------------------------------------------------------------------
# engine-level fused routing
# ---------------------------------------------------------------------------

def make_streams(seed, B, T, alphabet):
    rng = random.Random(seed)
    return [[Event(rng.choice(alphabet)) for _ in range(T)]
            for _ in range(B)]


@pytest.mark.parametrize("impl", ["fused", "unfused", "ref"])
def test_vector_engine_impl_routing(impl):
    streams = make_streams(2, 3, 40, "ABCX")
    base = VectorEngine("SELECT * FROM S WHERE A ; B+ ; C", epsilon=6,
                        use_pallas=False)
    want, _ = base.run(streams)
    ve = VectorEngine("SELECT * FROM S WHERE A ; B+ ; C", epsilon=6,
                      impl=impl)
    got, _ = ve.run(streams)
    np.testing.assert_array_equal(got, want)


def test_multiquery_fused_equals_unfused():
    queries = ["SELECT * FROM S WHERE A1 ; A2 ; A3",
               "SELECT * FROM S WHERE A1 ; A2+ ; A3",
               "SELECT * FROM S WHERE A2 ; (A1 OR A3)+ ; A2"]
    streams = make_streams(4, 3, 50, ["A1", "A2", "A3"])
    fused = MultiQueryEngine(queries, epsilon=9, impl="fused")
    unfused = MultiQueryEngine(queries, epsilon=9, impl="unfused")
    m_f, _ = fused.run(streams)
    m_u, _ = unfused.run(streams)
    np.testing.assert_array_equal(m_f, m_u)


# ---------------------------------------------------------------------------
# streaming runtime
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk_len", [8, 16])
def test_streaming_engine_compiles_once_bit_identical(chunk_len):
    """≥ 4 chunks through one executable, bit-identical to VectorEngine.run."""
    B, T = 2, 64
    streams = make_streams(7, B, T, "ABCX")
    ve = VectorEngine("SELECT * FROM S WHERE A ; B+ ; C", epsilon=6)
    full, _ = ve.run(streams)

    se = StreamingVectorEngine(ve, chunk_len=chunk_len, batch=B)
    parts, hits = [], []
    for lo in range(0, T, chunk_len):
        counts, h = se.feed([s[lo:lo + chunk_len] for s in streams])
        parts.append(counts)
        hits += h
    assert T // chunk_len >= 4
    assert se.compile_count == 1
    assert se.position == T
    np.testing.assert_array_equal(np.concatenate(parts), full)
    # hit positions are absolute and exactly the host-enumeration sites
    assert hits == ve.hit_positions(full)


def test_streaming_engine_boundary_straddles_window():
    """Chunk boundary inside an open window: runs must carry across feeds."""
    # A at the end of chunk 0, C at the start of chunk 1, eps covers both
    ev = [Event(t) for t in "XXXXXXXA"] + [Event(t) for t in "BCXXXXXX"]
    ve = VectorEngine("SELECT * FROM S WHERE A ; B ; C", epsilon=4)
    full, _ = ve.run([ev])
    se = StreamingVectorEngine(ve, chunk_len=8, batch=1)
    c1, _ = se.feed([ev[:8]])
    c2, h2 = se.feed([ev[8:]])
    np.testing.assert_array_equal(np.concatenate([c1, c2]), full)
    assert (9, 0) in h2  # the cross-boundary match closes at position 9


def test_streaming_engine_rejects_ragged_chunks():
    ve = VectorEngine("SELECT * FROM S WHERE A ; B", epsilon=3)
    se = StreamingVectorEngine(ve, chunk_len=8, batch=2)
    with pytest.raises(ValueError, match="chunk_len"):
        se.feed(make_streams(0, 2, 5, "AB"))


def test_streaming_engine_multiquery():
    queries = ["SELECT * FROM S WHERE A1 ; A2",
               "SELECT * FROM S WHERE A2 ; A1"]
    streams = make_streams(9, 2, 32, ["A1", "A2"])
    mq = MultiQueryEngine(queries, epsilon=5)
    full, _ = mq.run(streams)
    se = StreamingVectorEngine(mq, chunk_len=8, batch=2)
    parts = []
    for lo in range(0, 32, 8):
        counts, _ = se.feed([s[lo:lo + 8] for s in streams])
        parts.append(counts)
    assert se.compile_count == 1
    np.testing.assert_array_equal(np.concatenate(parts), full)


def test_streaming_reset():
    ve = VectorEngine("SELECT * FROM S WHERE A ; B", epsilon=3)
    se = StreamingVectorEngine(ve, chunk_len=8, batch=1)
    stream = [Event(t) for t in "ABXXXXAB"]
    c1, _ = se.feed([stream])
    se.reset()
    assert se.position == 0
    c2, _ = se.feed([stream])
    np.testing.assert_array_equal(c1, c2)
    assert se.compile_count == 1  # reset must not re-trace
