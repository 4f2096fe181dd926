"""Per-kernel Pallas (interpret=True) vs pure-jnp oracle, shape/dtype sweeps."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.ref import OP_EQ, OP_GE, OP_GT, OP_LE, OP_LT, OP_NE


def random_tables(rng, S, C):
    dm = rng.integers(0, S, (S, C))
    du = rng.integers(0, S, (S, C))
    M = np.zeros((C, S, S), np.float32)
    for s in range(1, S):
        for c in range(C):
            if dm[s, c]:
                M[c, s, dm[s, c]] += 1
            if du[s, c]:
                M[c, s, du[s, c]] += 1
    finals = (rng.random(S) < 0.4).astype(np.float32)
    finals[0] = 0.0
    return M, finals


# ---------------------------------------------------------------------------
# bitvector kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [1, 7, 64, 300])
@pytest.mark.parametrize("A,k", [(1, 1), (3, 4), (8, 12)])
def test_bitvector_shapes(B, A, k):
    rng = np.random.default_rng(B * 131 + A)
    attrs = rng.normal(size=(B, A)).astype(np.float32)
    specs = [(int(rng.integers(0, A)), int(rng.integers(0, 6)),
              float(rng.normal())) for _ in range(k)]
    got = ops.bitvector(jnp.asarray(attrs), specs, use_pallas=True)
    want = ops.bitvector(jnp.asarray(attrs), specs, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitvector_ops_exact():
    attrs = jnp.asarray([[1.0, 2.0], [2.0, 2.0], [3.0, -1.0]])
    specs = [(0, OP_EQ, 2.0), (0, OP_GT, 1.0), (1, OP_LE, 2.0),
             (1, OP_NE, -1.0), (0, OP_LT, 3.0), (0, OP_GE, 3.0)]
    got = np.asarray(ops.bitvector(attrs, specs))
    # row 0: eq0,gt0 -> bits: eq(1=0?no)... computed by hand:
    # e0=[1,2]: ==2:0 >1:0 | <=2:1 !=-1:1 <3:1 >=3:0 -> 0b011100 = 28
    # e1=[2,2]: ==2:1 >1:1 <=2:1 !=-1:1 <3:1 >=3:0 -> 0b011111 = 31
    # e2=[3,-1]: ==2:0 >1:1 <=2:1 !=-1:0 <3:0 >=3:1 -> 0b100110 = 38
    np.testing.assert_array_equal(got, [28, 31, 38])


def test_bitvector_nan_fails_all():
    """NULL attributes encode as NaN and must fail every comparison."""
    attrs = jnp.asarray([[np.nan]])
    specs = [(0, op, 0.0) for op in (OP_EQ, OP_LT, OP_LE, OP_GT, OP_GE)]
    got = int(np.asarray(ops.bitvector(attrs, specs))[0])
    assert got == 0


# ---------------------------------------------------------------------------
# cea_scan kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,C", [(4, 3), (7, 8), (16, 5)])
@pytest.mark.parametrize("B,T", [(1, 9), (8, 33), (13, 17)])
@pytest.mark.parametrize("eps", [3, 7])
def test_cea_scan_matches_oracle(S, C, B, T, eps):
    rng = np.random.default_rng(S * 1000 + B * 10 + eps)
    M, finals = random_tables(rng, S, C)
    ids = rng.integers(0, C, (T, B)).astype(np.int32)
    W = ops.ring_size(eps)
    c0 = np.zeros((B, W, S), np.float32)
    m_p, c_p = ops.cea_scan(jnp.asarray(ids), jnp.asarray(M),
                            jnp.asarray(finals), jnp.asarray(c0),
                            epsilon=eps, use_pallas=True)
    m_x, c_x = ops.cea_scan(jnp.asarray(ids), jnp.asarray(M),
                            jnp.asarray(finals), jnp.asarray(c0),
                            epsilon=eps, use_pallas=False)
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_x), rtol=0, atol=0)
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_x), rtol=0, atol=0)


def test_cea_scan_chunked_carry():
    """Scanning T events in one go == two chunks with carried state."""
    rng = np.random.default_rng(5)
    S, C, B, T, eps = 6, 4, 4, 24, 5
    M, finals = random_tables(rng, S, C)
    ids = rng.integers(0, C, (T, B)).astype(np.int32)
    W = ops.ring_size(eps)
    c0 = jnp.zeros((B, W, S), jnp.float32)
    for use_pallas in (False, True):
        m_full, _ = ops.cea_scan(jnp.asarray(ids), jnp.asarray(M),
                                 jnp.asarray(finals), c0, epsilon=eps,
                                 use_pallas=use_pallas)
        m1, c_mid = ops.cea_scan(jnp.asarray(ids[:10]), jnp.asarray(M),
                                 jnp.asarray(finals), c0, epsilon=eps,
                                 start_pos=0, use_pallas=use_pallas)
        m2, _ = ops.cea_scan(jnp.asarray(ids[10:]), jnp.asarray(M),
                             jnp.asarray(finals), c_mid, epsilon=eps,
                             start_pos=10, use_pallas=use_pallas)
        np.testing.assert_allclose(np.concatenate([m1, m2]),
                                   np.asarray(m_full))


def test_cea_scan_ring_padding_exact():
    """Any ring size W ≥ ε+1 yields identical matches (padding-invariance)."""
    rng = np.random.default_rng(9)
    S, C, B, T, eps = 5, 4, 2, 30, 4
    M, finals = random_tables(rng, S, C)
    ids = rng.integers(0, C, (T, B)).astype(np.int32)
    outs = []
    for W in (eps + 1, 8, 16):
        c0 = jnp.zeros((B, W, S), jnp.float32)
        m, _ = ops.cea_scan(jnp.asarray(ids), jnp.asarray(M),
                            jnp.asarray(finals), c0, epsilon=eps,
                            use_pallas=(W % 8 == 0))
        outs.append(np.asarray(m))
    np.testing.assert_allclose(outs[0], outs[1])
    np.testing.assert_allclose(outs[0], outs[2])


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 10), st.integers(1, 6), st.integers(1, 6),
       st.integers(1, 20), st.integers(1, 8), st.integers(0, 2**31 - 1))
def test_cea_scan_hypothesis(S, C, B, T, eps, seed):
    rng = np.random.default_rng(seed)
    M, finals = random_tables(rng, S, C)
    ids = rng.integers(0, C, (T, B)).astype(np.int32)
    W = ops.ring_size(eps)
    c0 = jnp.zeros((B, W, S), jnp.float32)
    m_p, _ = ops.cea_scan(jnp.asarray(ids), jnp.asarray(M),
                          jnp.asarray(finals), c0, epsilon=eps,
                          use_pallas=True)
    m_x, _ = ops.cea_scan(jnp.asarray(ids), jnp.asarray(M),
                          jnp.asarray(finals), c0, epsilon=eps,
                          use_pallas=False)
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_x))


def test_window_counts_only_within_epsilon():
    """A;B with ε=2: B at distance > 2 from A contributes no match."""
    # manual 3-state automaton: 1 -A/•-> 2 -B/•-> 3(final); 2 -True/◦-> 2
    S, C = 4, 4  # classes: 0 = neither, 1 = A, 2 = B, 3 = both (unused)
    M = np.zeros((C, S, S), np.float32)
    for c in (1, 3):
        M[c, 1, 2] += 1.0   # start: read A (mark)
    for c in range(C):
        M[c, 2, 2] += 1.0   # skip anything while waiting for B
    for c in (2, 3):
        M[c, 2, 3] += 1.0   # read B (mark) -> final
    finals = np.zeros(S, np.float32)
    finals[3] = 1.0
    #        A  .  .  B          distance 3 > eps=2 -> no match
    ids = np.asarray([[1], [0], [0], [2]], np.int32)
    c0 = jnp.zeros((1, ops.ring_size(2), S), jnp.float32)
    m, _ = ops.cea_scan(jnp.asarray(ids), jnp.asarray(M), jnp.asarray(finals),
                        c0, epsilon=2, use_pallas=True)
    assert m[3, 0] == 0
    #        A  .  B             distance 2 <= eps -> match
    ids2 = np.asarray([[1], [0], [2]], np.int32)
    m2, _ = ops.cea_scan(jnp.asarray(ids2), jnp.asarray(M), jnp.asarray(finals),
                         c0, epsilon=2, use_pallas=True)
    assert m2[2, 0] == 1
