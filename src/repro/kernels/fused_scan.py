"""Pallas TPU kernel: fused single-pass CER pipeline (DESIGN.md §3, §5).

The unfused device path is three dispatches per chunk —

    bitvector (predicate bits)  →  class_of gather  →  counting CEA scan

— with two ``(T·B)``-sized intermediates (``bits``, ``class_ids``) bouncing
through HBM between launches.  This kernel fuses the whole pipeline into ONE
``pallas_call``: per event step it evaluates the k predicates on the raw
attribute block, folds the packed bit-vector into a symbol class, gathers the
transition matrix, and advances the windowed run-count ring — all in VMEM.
The only per-grid-step HBM traffic is the ``(A, B_tile, t_tile)`` attribute
block in and the ``(NQ, B_tile, t_tile)`` match counts out; the ``(B, W, S)``
state never leaves VMEM between events.

Class folding without dynamic gathers
-------------------------------------
``class_of`` is a ``(2^k,)`` lookup table; TPU kernels want matmuls, not
gathers.  ops.py pre-expands it into a one-hot *indicator* ``(2^k, C)`` with
``ind[v, c] = [class_of[v] = c]``; the kernel then computes

    M  =  onehot(bits over 2^k) @ ind @ M_all.reshape(C, S·S)

as two MXU matmuls (``M_all`` arrives flattened from ops.py).  For paper workloads k ≤ 14 and C ≪ 2^k, so the
indicator is tiny next to ``M_all``.

The kernel is NQ-generalized: ``finals`` is ``(NQ, S)`` and the seed vector
``init`` is multi-hot, so the same kernel serves the single-query engine
(NQ = 1, one-hot init) and the packed multi-query engine (block-diagonal
``M_all``, one initial state per query block).

``start_pos`` is a dynamic *per-lane* ``(B, 1)`` operand — one compiled
executable serves every chunk of an unbounded stream (DESIGN.md §5), and
PARTITION BY lanes can sit at independent substream offsets (DESIGN.md §6).
A companion ``(B, 1)`` valid-count operand marks each lane's dense prefix of
real events this chunk; steps past it leave the lane's state untouched and
emit zero matches, so routed chunks with ragged per-lane fills stay exact.

Time windows (DESIGN.md §9, static ``time_size``): the kernel carries a
``(B_tile, W)`` per-slot start-timestamp ring in VMEM scratch next to the
count ring, evicts every slot whose start left the window (any number per
step) and latches a per-lane rate-bound overflow flag when a seed slot is
still live.  The count path (``time_size=None``) compiles to exactly
the classic single-slot-eviction kernel — a static specialization, not a
runtime branch.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitvector import _CMP
from .cea_scan import _HIGHEST

# Events per grid step: one lane width.  Per-event operands are laid out
# with the event axis on the 128 lanes — attrs ``(A, B, T)``, timestamps and
# the class trace ``(B, T)``, matches ``(NQ, B, T)`` — so HBM arrays carry no
# tile padding and each block is ``(·, b_tile, t_tile)`` with ``b_tile`` on
# the sublanes.  Mosaic cannot slice the lane axis at a dynamic offset, so
# the kernel walks the block's events with a loop and moves each event's
# column in and out by a masked lane reduction / select.  Chunks shorter
# than this take one block of the whole chunk; longer ones are padded to a
# multiple of it with dead steps.
DEFAULT_T_TILE = 128


def _dot(a, b):
    """f32 MXU contraction at full precision — run counts are exact
    integers below 2^24, and the TPU's default f32 matmul rounds operands
    to bfloat16."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=_HIGHEST)


def _bdot(a, b):
    """Batched ``(B, M, K) × (B, K, N) → (B, M, N)`` at full precision."""
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=_HIGHEST)


def _fused_scan_kernel(*refs,                                    # see below
                       specs: Tuple[Tuple[int, int, float], ...],
                       V: int, W: int, S: int, NQ: int, A: int,
                       B_tile: int, T: int, epsilon: int, t_tile: int,
                       emit_trace: bool, time_size,
                       has_latest: bool, has_consume: bool):
    """Kernel body; ``refs`` order (time-mode refs only when ``time_size``
    is set, trace ref only with ``emit_trace``, selection/consumption refs
    only with their static flags):

    inputs   start, valid, [ts], attrs, ind, m_flat, finals, init,
             [latest], [consume], c_in, [ts_ring_in, ovf_in]
    outputs  matches, c_out, [ts_ring_out, ovf_out], [trace]
    scratch  c, [ts_ring, ovf]

    Every per-lane quantity is a ``(B_tile, 1)`` column (the lane tile on
    sublanes), so the body stays in the 2-D layouts Mosaic tiles natively.
    """
    timed = time_size is not None
    it = iter(refs)
    start_ref, valid_ref = next(it), next(it)                  # (B_tile, 1)
    ts_ref = next(it) if timed else None                       # (B_tile, tt)
    attrs_ref, ind_ref, m_ref = next(it), next(it), next(it)
    finals_ref, init_ref = next(it), next(it)
    latest_ref = next(it) if has_latest else None              # (1, NQ)
    consume_ref = next(it) if has_consume else None            # (NQ, S)
    c_in_ref = next(it)
    tsr_in_ref = next(it) if timed else None                   # (B_tile, W)
    ovf_in_ref = next(it) if timed else None                   # (B_tile, 1)
    matches_ref, c_out_ref = next(it), next(it)
    tsr_out_ref = next(it) if timed else None
    ovf_out_ref = next(it) if timed else None
    trace_ref = next(it) if emit_trace else None
    c_scratch = next(it)
    tsr_scratch = next(it) if timed else None
    ovf_scratch = next(it) if timed else None
    tt = pl.program_id(1)

    @pl.when(tt == 0)
    def _init():
        c_scratch[...] = c_in_ref[...]
        if timed:
            tsr_scratch[...] = tsr_in_ref[...]
            ovf_scratch[...] = ovf_in_ref[...]

    m_flat = m_ref[...]                                        # (NC, S·S)
    finals = finals_ref[...]                                   # (NQ, S)
    init = init_ref[...]                                       # (1, S)
    iota_v = jax.lax.broadcasted_iota(jnp.int32, (1, V), 1)
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (1, t_tile), 1)
    start = start_ref[...]                                     # (B_tile, 1)
    valid = valid_ref[...]                                     # (B_tile, 1)
    attrs_blk = attrs_ref[...]                                 # (A, Bt, tt)
    ts_blk = ts_ref[...] if timed else None                    # (Bt, tt)
    matches_ref[...] = jnp.zeros(matches_ref.shape, jnp.float32)
    if emit_trace:
        trace_ref[...] = jnp.zeros(trace_ref.shape, jnp.int32)

    def event(ti, carry):
        t = tt * t_tile + ti
        at_t = iota_t == ti                                    # (1, tt)
        # --- stage 1: predicate bits, unrolled over the k atoms ----------
        # (the event's column leaves the lane axis by a masked reduction)
        bits = jnp.zeros((B_tile, 1), dtype=jnp.int32)
        cols = {}
        for i, (col, op, thr) in enumerate(specs):
            if col not in cols:
                cols[col] = jnp.sum(jnp.where(at_t, attrs_blk[col], 0.0),
                                    axis=1, keepdims=True)     # (B_tile, 1)
            bit = _CMP[op](cols[col], jnp.float32(thr))
            bits = bits | (bit.astype(jnp.int32) << i)

        # --- stage 2: fold bits → class (one-hot matmul, no gather) -------
        onehot_v = (bits == iota_v).astype(jnp.float32)       # (B_tile, 2^k)
        cls = _dot(onehot_v, ind_ref[...])                     # (B_tile, C)
        if emit_trace:
            # class-id trace operand for the tECS arena (DESIGN.md §7):
            # cls is exactly one-hot (indicator rows are one-hot, padded
            # rows all-zero and never selected), so argmax recovers the
            # integer class id.
            cid = jnp.argmax(cls, axis=1, keepdims=True).astype(jnp.int32)
            trace_ref[...] = jnp.where(at_t, cid, trace_ref[...])
        M = _dot(cls, m_flat).reshape(B_tile, S, S)

        # --- stage 3: windowed semiring step -------------------------------
        # per-lane positions: each PARTITION BY lane sits at its own
        # substream offset, and only the first valid[b] slots of a lane
        # carry real events this chunk (dense-prefix contract) — dead steps
        # are no-ops.  Seeding is position-driven in both window modes
        # (DESIGN.md §9); eviction is the one-hot count rule or the
        # timestamp-ring mask.
        j = start + t                                          # (B_tile, 1)
        live_b = t < valid                                     # (B_tile, 1)
        live = live_b.astype(jnp.float32)
        seed_b = iota_w == j % W                               # (B_tile, W)
        if timed:
            ts_t = jnp.sum(jnp.where(at_t, ts_blk, 0.0), axis=1,
                           keepdims=True)                      # (B_tile, 1)
            tsr = tsr_scratch[...]                             # (B_tile, W)
            expire_b = tsr < ts_t - jnp.float32(time_size)
            # rate bound: the seed slot's previous start is still live
            over = jnp.max((seed_b & ~expire_b).astype(jnp.int32), axis=1,
                           keepdims=True) > 0
            ovf_scratch[...] = jnp.where(over & live_b, 1, ovf_scratch[...])
            tsr_scratch[...] = jnp.where(seed_b & live_b, ts_t, tsr)
        else:
            expire_b = iota_w == (j - epsilon - 1) % W
        seed_mask = seed_b.astype(jnp.float32)
        clear = jnp.maximum(seed_mask, expire_b.astype(jnp.float32))
        C = c_scratch[...]                                     # (B_tile,W,S)
        C_new = C * (1.0 - clear)[:, :, None] \
            + seed_mask[:, :, None] * init[None, :, :]
        C_new = _bdot(C_new, M)
        C = C_new * live[:, :, None] + C * (1.0 - live)[:, :, None]
        c_scratch[...] = C

        per_q = jax.lax.dot_general(
            C.reshape(B_tile * W, S), finals, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_HIGHEST
        ).reshape(B_tile, W, NQ)
        if has_latest:
            # LAST (DESIGN.md D2): reduce per-slot counts to the youngest
            # live slot — slots and seed positions biject in the window, so
            # "latest start" is "smallest (j - w) mod W with a positive
            # count".  Queries with latest flag 0 keep the plain slot sum.
            lq = latest_ref[...]                               # (1, NQ)
            age = (j - iota_w) % W                             # (B_tile, W)
            posm = (per_q > 0).astype(jnp.float32)
            # older[b, w, v]: slot v's start is younger than slot w's
            older = (age[:, None, :] < age[:, :, None]
                     ).astype(jnp.float32)                     # (B, W, W)
            blocked = _bdot(older, posm)                       # (B, W, NQ)
            keep = posm * (1.0 - jnp.minimum(blocked, 1.0))
            m_t = (jnp.sum(per_q, axis=1) * (1.0 - lq)
                   + jnp.sum(per_q * keep, axis=1) * lq)
        else:
            m_t = jnp.sum(per_q, axis=1)                       # (B_tile, NQ)
        m_t = m_t * live
        for q in range(NQ):
            matches_ref[q] = jnp.where(at_t, m_t[:, q:q + 1], matches_ref[q])
        if has_consume:
            # CONSUME BY ANY's emit-then-clear: after the counts are out,
            # any consuming query with a hit zeroes the states it owns —
            # including the run seeded this very step, as the host does.
            trig = (m_t > 0).astype(jnp.float32)               # (B_tile, NQ)
            clear_s = jnp.minimum(_dot(trig, consume_ref[...]), 1.0)
            c_scratch[...] = C * (1.0 - clear_s)[:, None, :]
        return carry

    jax.lax.fori_loop(0, t_tile, event, 0)

    @pl.when(tt == T // t_tile - 1)
    def _flush():
        c_out_ref[...] = c_scratch[...]
        if timed:
            tsr_out_ref[...] = tsr_scratch[...]
            ovf_out_ref[...] = ovf_scratch[...]


def fused_scan_pallas(attrs: jnp.ndarray, class_ind: jnp.ndarray,
                      m_flat: jnp.ndarray, finals_q: jnp.ndarray,
                      init_mask: jnp.ndarray, c0: jnp.ndarray,
                      start_lanes: jnp.ndarray, valid_lanes: jnp.ndarray,
                      *, specs: Sequence[Tuple[int, int, float]],
                      epsilon: int, b_tile: int = 8,
                      t_tile: int = DEFAULT_T_TILE,
                      interpret: bool = False, emit_trace: bool = False,
                      time_size=None, event_ts=None, ts_ring0=None,
                      ovf0=None, latest_q=None, consume_sq=None):
    """Raw pallas_call; use :func:`repro.kernels.ops.cer_pipeline` instead.

    attrs:       (A, B, T) f32 — raw encoded event attributes
    class_ind:   (2^k, C) f32 — one-hot class indicator (padded rows zero)
    m_flat:      (C, S·S) f32 — transition matrices, flattened row-major
    finals_q:    (NQ, S) f32
    init_mask:   (1, S) f32 multi-hot seed vector
    c0:          (B, W, S) f32, W ≥ epsilon + 1
    start_lanes: (B, 1) int32 dynamic per-lane substream offsets
    valid_lanes: (B, 1) int32 per-lane live-event counts this chunk
                 (pass T for every lane to disable dead-step masking)
    t_tile:      events per grid step (must divide T; a multiple of 128,
                 or all of T, for the compiled kernel)
    returns      (matches (NQ, B, T) f32, c_final (B, W, S) f32) — plus,
                 with ``emit_trace`` (static, per call site), a trailing
                 ``(B, T) int32`` output: the per-event symbol class, the
                 tECS-arena trace operand (DESIGN.md §7).  Counting-only
                 callers keep the two-output kernel, paying neither the
                 argmax nor the extra HBM write.

    Time windows (``time_size`` set, static; DESIGN.md §9): pass
    ``event_ts`` (B, T) f32 per-event timestamps, ``ts_ring0`` (B, W) f32
    per-slot start-timestamp ring and ``ovf0`` (B, 1) int32 latched
    rate-bound flags; the return gains ``(ts_ring (B, W) f32, ovf (B, 1)
    int32)`` between ``c_final`` and the trace.  Eviction masks every slot
    whose start timestamp left the window; ``epsilon`` is ignored.

    Selection/consumption (DESIGN.md D2, both static per call site):
    ``latest_q`` (1, NQ) f32 flags LAST queries (per-slot counts reduce to
    the youngest live slot); ``consume_sq`` (NQ, S) f32 maps CONSUME BY ANY
    queries to the states they clear after an emitting step.  ``None``
    compiles the classic ANY kernel — a static specialization, like the
    window modes.
    """
    A, B, T = attrs.shape
    NC = m_flat.shape[0]
    V = class_ind.shape[0]
    NQ, S = finals_q.shape
    W = c0.shape[1]
    timed = time_size is not None
    assert m_flat.shape == (NC, S * S), (m_flat.shape, S)
    assert B % b_tile == 0, (B, b_tile)
    assert T % t_tile == 0, (T, t_tile)
    assert timed or W >= epsilon + 1, (W, epsilon)
    assert start_lanes.shape == (B, 1), start_lanes.shape
    assert valid_lanes.shape == (B, 1), valid_lanes.shape
    grid = (B // b_tile, T // t_tile)

    kernel = functools.partial(
        _fused_scan_kernel, specs=tuple(specs), V=V, W=W, S=S, A=A,
        NQ=NQ, B_tile=b_tile, T=T, epsilon=epsilon, t_tile=t_tile,
        emit_trace=emit_trace, time_size=time_size,
        has_latest=latest_q is not None,
        has_consume=consume_sq is not None)

    lane_col = pl.BlockSpec((b_tile, 1), lambda b, t: (b, 0))
    ring_spec = pl.BlockSpec((b_tile, W), lambda b, t: (b, 0))
    step_row = pl.BlockSpec((b_tile, t_tile), lambda b, t: (b, t))
    in_specs = [
        lane_col,                                              # start_pos
        lane_col,                                              # valid
    ]
    operands = [start_lanes, valid_lanes]
    if timed:
        assert event_ts.shape == (B, T), event_ts.shape
        in_specs.append(step_row)                              # event ts
        operands.append(event_ts)
    in_specs += [
        pl.BlockSpec((A, b_tile, t_tile), lambda b, t: (0, b, t)),  # attrs
        pl.BlockSpec((V, NC), lambda b, t: (0, 0)),            # indicator
        pl.BlockSpec((NC, S * S), lambda b, t: (0, 0)),        # M_all
        pl.BlockSpec((NQ, S), lambda b, t: (0, 0)),            # finals
        pl.BlockSpec((1, S), lambda b, t: (0, 0)),             # init
    ]
    operands += [attrs, class_ind, m_flat, finals_q, init_mask]
    if latest_q is not None:
        assert latest_q.shape == (1, NQ), (latest_q.shape, NQ)
        in_specs.append(pl.BlockSpec((1, NQ), lambda b, t: (0, 0)))
        operands.append(latest_q)
    if consume_sq is not None:
        assert consume_sq.shape == (NQ, S), (consume_sq.shape, NQ, S)
        in_specs.append(pl.BlockSpec((NQ, S), lambda b, t: (0, 0)))
        operands.append(consume_sq)
    in_specs.append(pl.BlockSpec((b_tile, W, S), lambda b, t: (b, 0, 0)))
    operands.append(c0)                                        # C0
    if timed:
        in_specs += [ring_spec, lane_col]                      # ts ring, ovf
        operands += [ts_ring0, ovf0]

    out_specs = [
        pl.BlockSpec((NQ, b_tile, t_tile), lambda b, t: (0, b, t)),  # matches
        pl.BlockSpec((b_tile, W, S), lambda b, t: (b, 0, 0)),    # C_final
    ]
    out_shape = [
        jax.ShapeDtypeStruct((NQ, B, T), jnp.float32),
        jax.ShapeDtypeStruct((B, W, S), jnp.float32),
    ]
    if timed:
        out_specs += [ring_spec, lane_col]
        out_shape += [jax.ShapeDtypeStruct((B, W), jnp.float32),
                      jax.ShapeDtypeStruct((B, 1), jnp.int32)]
    if emit_trace:
        out_specs.append(step_row)
        out_shape.append(jax.ShapeDtypeStruct((B, T), jnp.int32))

    scratch = [pltpu.VMEM((b_tile, W, S), jnp.float32)]
    if timed:
        scratch += [pltpu.VMEM((b_tile, W), jnp.float32),
                    pltpu.VMEM((b_tile, 1), jnp.int32)]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="cer_fused_scan",
    )(*operands)
