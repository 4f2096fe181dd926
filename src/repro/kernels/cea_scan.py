"""Pallas TPU kernel: windowed counting-semiring CEA scan (DESIGN.md §3).

This is the inner loop of Algorithm 1, vectorized: per event and per stream,
advance the run-count tensor ``C[W, S]`` by the event's transition matrix and
emit the number of matches closing at that position.

Layout / schedule
-----------------
* grid = ``(nB, T)``: stream tiles × events.  The last grid dimension is
  iterated sequentially on TPU, so the run-count tensor for a stream tile
  lives in a VMEM scratch across all T steps — the HBM traffic per step is
  only the symbol ids (B_tile int32) and the per-step match counts, instead
  of 2×B×W×S f32 for a lax.scan over XLA ops.  This is the kernel's raison
  d'être: the state never leaves VMEM.
* The per-event transition matrix is gathered from the class table ``M_all``
  with a one-hot MXU matmul ``(B_tile, C) @ (C, S·S)`` — no dynamic slicing,
  and cheap next to the main ``(B_tile·W, S) @ (S, S)`` contraction whenever
  ``C ≤ W`` (true for all paper workloads).
* Blocks are padded by ``ops.py`` so that S is a multiple of 128 (MXU lane
  width) and W a multiple of 8 (f32 sublane) — see EXPERIMENTS.md §Perf for
  the small-S trade-off study.
* ``start_pos`` is a *dynamic* SMEM scalar (DESIGN.md §5): the ring slots it
  derives are computed per step from ``start_ref[0] + t``, so one compiled
  executable serves every chunk of a stream — chunked/streaming callers never
  recompile.  (It used to be a ``functools.partial``-baked static, which
  forced a fresh compile per chunk offset.)
* Windows: these legacy kernels implement the count-window (events)
  eviction rule only; time windows (DESIGN.md §9) route through the fused
  kernel / fused-XLA path.

VMEM budget per tile: C-scratch ``B_tile·W·S·4`` + ``M_all C·S·S·4`` +
blocks; ops.py checks it against the chip's scoped limit before launching.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: run counts are exact integers below 2^24; the TPU's default f32 matmul
#: rounds operands to bfloat16, so every count contraction asks for full
#: precision (a no-op on CPU)
_HIGHEST = jax.lax.Precision.HIGHEST


def _vmem_scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _ring_masks(j, W: int, epsilon: int):
    """Per-step ring-buffer masks for position ``j`` (traced int32 scalar).

    Seed a fresh run at slot ``j mod W`` and evict the start that just left
    the window, ``(j - ε - 1) mod W``.  ``%`` follows Python sign semantics,
    so early negative expire indices wrap to live-but-empty padded slots.
    Returns ``(seed_mask, clear)`` — both (W,) f32 0/1 masks.
    """
    arange_w = jax.lax.iota(jnp.int32, W)
    seed_mask = (arange_w == j % W).astype(jnp.float32)          # (W,)
    expire = (arange_w == (j - epsilon - 1) % W).astype(jnp.float32)
    return seed_mask, jnp.maximum(seed_mask, expire)


def latest_slot_counts(C2, fq, j, latest_q):
    """Per-query counts with LAST queries reduced to the latest live seed slot.

    Slots and seed positions biject inside the window, so LAST's
    "latest start" is "the youngest slot with a positive count".  Queries
    with ``latest_q == 0`` keep the plain sum over slots.

    C2: (B, W, S) f32 post-transition ring; fq: (Q, S) f32 final masks;
    j: (B,) int32 current positions; latest_q: (Q,) f32 0/1.
    Returns m: (B, Q) f32.
    """
    W = C2.shape[1]
    mw = jnp.einsum("bws,qs->bwq", C2, fq,
                    precision=_HIGHEST)                         # (B, W, Q)
    arange_w = jax.lax.iota(jnp.int32, W)
    age = (j[:, None] - arange_w[None, :]) % W                  # (B, W)
    posm = (mw > 0).astype(C2.dtype)
    younger = (age[:, :, None] < age[:, None, :]).astype(C2.dtype)
    blocked = jnp.einsum("bvw,bvq->bwq", younger, posm,
                         precision=_HIGHEST)                    # (B, W, Q)
    keep = posm * (1.0 - jnp.minimum(blocked, 1.0))
    m_latest = jnp.sum(mw * keep, axis=1)                       # (B, Q)
    m_all = jnp.sum(mw, axis=1)
    lq = latest_q.astype(C2.dtype)[None, :]
    return m_all * (1.0 - lq) + m_latest * lq


def consume_clear(C2, m, consume_sq):
    """CONSUME BY ANY's emit-then-clear, device form (DESIGN.md D2).

    After a position emits for a consuming query, the host engine drops its
    whole run set (``T = {}``), including the run seeded that very step.
    Here: any query with a positive (already live-masked) count zeroes the
    ring over the states it owns — ``consume_sq[q, s] = 1`` iff query ``q``
    consumes and owns packed state ``s`` (zero rows = non-consuming).

    C2: (B, W, S); m: (B, Q) live-masked counts; consume_sq: (Q, S).
    Returns the cleared ring.
    """
    trig = (m > 0).astype(C2.dtype)                             # (B, Q)
    clear_s = jnp.minimum(
        jnp.einsum("bq,qs->bs", trig, consume_sq.astype(C2.dtype),
                   precision=_HIGHEST), 1.0)
    return C2 * (1.0 - clear_s)[:, None, :]


def _cea_scan_kernel(start_ref,                                  # SMEM scalar
                     ids_ref, m_all_ref, finals_ref, c_in_ref,   # inputs
                     matches_ref, c_out_ref,                     # outputs
                     c_scratch,                                  # VMEM scratch
                     *, W: int, S: int, NC: int, B_tile: int, T: int,
                     epsilon: int, init_state: int):
    t = pl.program_id(1)

    # load the stream tile's state into VMEM scratch on the first event
    @pl.when(t == 0)
    def _init():
        c_scratch[...] = c_in_ref[...]

    ids = ids_ref[:, 0]                                        # (B_tile,)
    # gather transition matrices via one-hot MXU matmul
    onehot = (ids[:, None] == jax.lax.iota(jnp.int32, NC)[None, :]
              ).astype(jnp.float32)                            # (B_tile, C)
    m_flat = m_all_ref[...].reshape(NC, S * S)
    M = jnp.dot(onehot, m_flat,
                preferred_element_type=jnp.float32).reshape(B_tile, S, S)

    # ring-buffer update: evict the start that just left the window
    # (j - ε - 1) and seed a fresh run (start = j) at init_state
    j = start_ref[0] + t
    seed_mask, clear = _ring_masks(j, W, epsilon)
    init_oh = (jax.lax.iota(jnp.int32, S) == init_state
               ).astype(jnp.float32)                           # (S,)
    C = c_scratch[...]                                         # (B_tile, W, S)
    C = C * (1.0 - clear)[None, :, None] \
        + seed_mask[None, :, None] * init_oh[None, None, :]

    # advance all runs: batched counting-semiring matmul on the MXU
    C = jax.lax.dot_general(
        C, M, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                    # (B_tile, W, S)
    c_scratch[...] = C

    # matches closing at this event: mass on final states
    finals = finals_ref[0, :]                                  # (S,)
    matches_ref[:, 0] = jnp.sum(C * finals[None, None, :], axis=(1, 2))

    # write the final state back to HBM once, on the last event
    @pl.when(t == T - 1)
    def _flush():
        c_out_ref[...] = c_scratch[...]


def cea_scan_pallas(class_ids: jnp.ndarray, m_all: jnp.ndarray,
                    finals: jnp.ndarray, c0: jnp.ndarray,
                    start_pos: jnp.ndarray,
                    *, epsilon: int, init_state: int = 1,
                    b_tile: int = 8, interpret: bool = False):
    """Raw pallas_call; use :func:`repro.kernels.ops.cea_scan` instead.

    class_ids: (B, T) int32 — symbol class per stream per event
    m_all:     (C, S, S) f32
    finals:    (1, S) f32
    c0:        (B, W, S) f32, W ≥ epsilon + 1
    start_pos: (1,) int32 — dynamic stream offset of the chunk's first event
    returns    (matches (B, T) f32, c_final (B, W, S) f32)
    """
    B, T = class_ids.shape
    NC, S, _ = m_all.shape
    W = c0.shape[1]
    assert B % b_tile == 0, (B, b_tile)
    assert W >= epsilon + 1, (W, epsilon)
    grid = (B // b_tile, T)

    kernel = functools.partial(
        _cea_scan_kernel, W=W, S=S, NC=NC, B_tile=b_tile, T=T,
        epsilon=epsilon, init_state=init_state)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # start_pos
            pl.BlockSpec((b_tile, 1), lambda b, t: (b, t)),       # ids
            pl.BlockSpec((NC, S, S), lambda b, t: (0, 0, 0)),     # M_all
            pl.BlockSpec((1, S), lambda b, t: (0, 0)),            # finals
            pl.BlockSpec((b_tile, W, S), lambda b, t: (b, 0, 0)),  # C0
        ],
        out_specs=[
            pl.BlockSpec((b_tile, 1), lambda b, t: (b, t)),        # matches
            pl.BlockSpec((b_tile, W, S), lambda b, t: (b, 0, 0)),  # C_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T), jnp.float32),
            jax.ShapeDtypeStruct((B, W, S), jnp.float32),
        ],
        scratch_shapes=[_vmem_scratch((b_tile, W, S))],
        interpret=interpret,
    )(start_pos, class_ids, m_all, finals, c0)


def _cea_scan_multi_kernel(start_ref, ids_ref, m_all_ref, finals_ref, init_ref,
                           c_in_ref, matches_ref, c_out_ref, c_scratch,
                           *, W: int, S: int, NC: int, NQ: int, B_tile: int,
                           T: int, epsilon: int):
    """Packed multi-query variant: multi-hot seeding + per-query finals."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        c_scratch[...] = c_in_ref[...]

    ids = ids_ref[:, 0]
    onehot = (ids[:, None] == jax.lax.iota(jnp.int32, NC)[None, :]
              ).astype(jnp.float32)
    m_flat = m_all_ref[...].reshape(NC, S * S)
    M = jnp.dot(onehot, m_flat,
                preferred_element_type=jnp.float32).reshape(B_tile, S, S)

    j = start_ref[0] + t
    seed_mask, clear = _ring_masks(j, W, epsilon)
    init = init_ref[0, :]                                      # (S,) multi-hot
    C = c_scratch[...]
    C = C * (1.0 - clear)[None, :, None] \
        + seed_mask[None, :, None] * init[None, None, :]
    C = jax.lax.dot_general(
        C, M, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    c_scratch[...] = C

    finals = finals_ref[...]                                   # (NQ, S)
    per_q = jax.lax.dot_general(
        C.reshape(B_tile * W, S), finals.T, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(B_tile, W, NQ)
    matches_ref[:, 0, :] = jnp.sum(per_q, axis=1)

    @pl.when(t == T - 1)
    def _flush():
        c_out_ref[...] = c_scratch[...]


def cea_scan_multi_pallas(class_ids, m_all, finals_q, init_mask, c0,
                          start_pos, *, epsilon: int, b_tile: int = 8,
                          interpret: bool = False):
    """class_ids (B, T) | m_all (C, S, S) | finals_q (Q, S) | init (1, S)
    | c0 (B, W, S) | start_pos (1,) int32 → (matches (B, T, Q), c_final)."""
    B, T = class_ids.shape
    NC, S, _ = m_all.shape
    NQ = finals_q.shape[0]
    W = c0.shape[1]
    assert B % b_tile == 0 and W >= epsilon + 1
    grid = (B // b_tile, T)
    kernel = functools.partial(
        _cea_scan_multi_kernel, W=W, S=S, NC=NC, NQ=NQ, B_tile=b_tile, T=T,
        epsilon=epsilon)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # start_pos
            pl.BlockSpec((b_tile, 1), lambda b, t: (b, t)),
            pl.BlockSpec((NC, S, S), lambda b, t: (0, 0, 0)),
            pl.BlockSpec((NQ, S), lambda b, t: (0, 0)),
            pl.BlockSpec((1, S), lambda b, t: (0, 0)),
            pl.BlockSpec((b_tile, W, S), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b_tile, 1, NQ), lambda b, t: (b, t, 0)),
            pl.BlockSpec((b_tile, W, S), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, NQ), jnp.float32),
            jax.ShapeDtypeStruct((B, W, S), jnp.float32),
        ],
        scratch_shapes=[_vmem_scratch((b_tile, W, S))],
        interpret=interpret,
    )(start_pos, class_ids, m_all, finals_q, init_mask, c0)
