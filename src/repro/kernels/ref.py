"""Pure-jnp oracles for the Pallas kernels (ground truth for allclose tests).

Shapes / conventions shared with the kernels:

* ``attrs``      — ``(B, A)`` f32: one row per event, numerically-encoded
                   attributes (categoricals pre-encoded on host).
* ``bitvec``     — ``(B,)`` int32: packed predicate bits (bit i ⇔ P_i holds).
* ``C``          — ``(B, W, S)`` f32: windowed run-count tensor; ``W`` ring
                   slots indexed by ``start mod W``; ``S`` det states
                   (0 = dead, 1 = initial).
* ``M_all``      — ``(C, S, S)`` f32 counting-semiring transition matrices.
* ``class_ids``  — ``(T, B)`` int32 symbol class per event per stream.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tecs import BOTTOM, OUTPUT, UNION
from .cea_scan import _HIGHEST, consume_clear, latest_slot_counts

# op codes shared with the bit-vector kernel
OP_EQ, OP_NE, OP_LT, OP_LE, OP_GT, OP_GE = range(6)

ARENA_NULL = -1  # empty cell / absent child (shared with vector/tecs_arena)


def bitvector_ref(attrs: jnp.ndarray, attr_idx: jnp.ndarray,
                  op_code: jnp.ndarray, threshold: jnp.ndarray) -> jnp.ndarray:
    """(B, A) f32 × k predicate specs → (B,) int32 packed bit-vectors."""
    vals = attrs[:, attr_idx]                      # (B, k)
    thr = threshold[None, :]                       # (1, k)
    results = jnp.stack([
        vals == thr, vals != thr, vals < thr,
        vals <= thr, vals > thr, vals >= thr,
    ], axis=0)                                      # (6, B, k)
    bits = jnp.take_along_axis(
        results, op_code[None, None, :].astype(jnp.int32), axis=0)[0]  # (B, k)
    weights = (1 << jnp.arange(attr_idx.shape[0], dtype=jnp.int32))
    return jnp.sum(bits.astype(jnp.int32) * weights[None, :], axis=1)


def class_trace_ref(attrs: jnp.ndarray, attr_idx: jnp.ndarray,
                    op_code: jnp.ndarray, threshold: jnp.ndarray,
                    class_of: jnp.ndarray) -> jnp.ndarray:
    """(T, B, A) attrs → (T, B) int32 symbol-class trace.

    The per-event symbol class is the *trace operand* of the device tECS
    arena (vector/tecs_arena.py, DESIGN.md §7): it determines which
    predecessor edges fire at each step, so the arena builder never has to
    re-evaluate predicates on raw events.
    """
    T, B, A = attrs.shape
    bits = bitvector_ref(attrs.reshape(T * B, A), attr_idx, op_code,
                         threshold)
    return class_of[bits].reshape(T, B).astype(jnp.int32)


def cea_step_ref(C: jnp.ndarray, M: jnp.ndarray, seed_slot: jnp.ndarray,
                 expire_slot: jnp.ndarray, finals: jnp.ndarray,
                 init_state: int = 1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One windowed CEA step (Algorithm 1's update, dense form).

    C:           (B, W, S) run counts by (stream, start-ring-slot, state)
    M:           (B, S, S) per-stream transition matrix for this event
    seed_slot:   () int32 — ring slot of the current position (j mod W); a
                 fresh run (start = j) is seeded there.  With W ≥ ε+1 the
                 slot is guaranteed empty (its previous occupant was evicted
                 when it crossed the window boundary).
    expire_slot: () int32 — slot of start j-ε-1, which just left the window
                 (ring padding W > ε+1 keeps ring arithmetic exact).
    finals:      (S,) f32 mask of accepting det states.
    Returns (C', matches) with matches (B,) = matches closing at this step.
    """
    B, W, S = C.shape
    arange_w = jnp.arange(W)
    clear = (arange_w == seed_slot) | (arange_w == expire_slot)   # (W,)
    C = C * (1.0 - clear.astype(C.dtype))[None, :, None]
    seed_oh = (arange_w == seed_slot).astype(C.dtype)
    init_oh = (jnp.arange(S) == init_state).astype(C.dtype)
    C = C + seed_oh[None, :, None] * init_oh[None, None, :]
    # advance every live run by this event: counting-semiring matmul
    C = jnp.einsum("bws,bst->bwt", C, M, precision=_HIGHEST)
    matches = jnp.einsum("bws,s->b", C, finals.astype(C.dtype),
                         precision=_HIGHEST)
    return C, matches


def cea_scan_ref(C0: jnp.ndarray, M_all: jnp.ndarray, class_ids: jnp.ndarray,
                 finals: jnp.ndarray, epsilon: int, start_pos: int = 0,
                 init_state: int = 1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan ``cea_step_ref`` over T events with window ``end-start ≤ epsilon``.

    Requires ring size W ≥ epsilon + 1.  Returns (C_T, matches (T, B)).
    """
    B, W, S = C0.shape
    assert W >= epsilon + 1, (W, epsilon)
    T = class_ids.shape[0]
    finals_f = finals.astype(C0.dtype)

    def step(C, inputs):
        t, ids = inputs
        M = M_all[ids]                     # (B, S, S) gather
        j = start_pos + t
        seed_slot = j % W
        expire_slot = (j - epsilon - 1) % W
        C, m = cea_step_ref(C, M, seed_slot, expire_slot, finals_f, init_state)
        return C, m

    ts = jnp.arange(T, dtype=jnp.int32)
    C_T, matches = jax.lax.scan(step, C0, (ts, class_ids))
    return C_T, matches


def cea_scan_multi_ref(C0, M_all: jnp.ndarray,
                       class_ids: jnp.ndarray, finals_q: jnp.ndarray,
                       init_mask: jnp.ndarray, epsilon: int,
                       start_pos=0, valid_counts=None,
                       window=None, event_ts=None,
                       latest_q=None, consume_sq=None):
    """Packed multi-query scan oracle (see vector/multiquery.py).

    finals_q: (Q, S) per-query final-state masks; init_mask: (S,) multi-hot
    (one initial state per packed query block).  Returns
    (C_T, matches (T, B, Q)).

    ``start_pos`` may be a scalar (all streams at the same offset) or a
    ``(B,)`` vector of per-lane substream positions (PARTITION BY lanes,
    DESIGN.md §6) — the ring seed/expire slots are derived per lane.
    ``valid_counts`` (optional, ``(B,)`` int32) marks the dense prefix of
    each lane that carries real events this chunk: steps ``t ≥ n_b`` are
    no-ops for lane ``b`` (state unchanged, zero matches, position does not
    advance).

    Selection/consumption semantics (DESIGN.md D2): ``latest_q`` (``(Q,)``
    f32, optional) reduces LAST queries' counts to the latest live seed
    slot; ``consume_sq`` (``(Q, S)`` f32, optional) applies CONSUME BY
    ANY's emit-then-clear over each consuming query's states.  ``None``
    (the default) leaves the classic graph untouched.

    Time windows (DESIGN.md §9): pass ``window`` (a
    :class:`repro.kernels.window.DeviceWindow` with ``kind='time'``) and
    ``event_ts`` ``(T, B) f32``; ``C0`` is then the
    ``{"C", "ts", "ovf"}`` state pytree — eviction masks every slot whose
    start timestamp left the window, and a seed slot still live inside the
    window latches the lane's rate-bound ``ovf`` flag.  Count windows keep
    the classic single-slot eviction (the degenerate case ``ts ≡
    position``), bare-array state, and this exact code path.
    """
    timed = window is not None and window.is_time
    if not timed:
        return _scan_multi_count_ref(C0, M_all, class_ids, finals_q,
                                     init_mask, epsilon, start_pos,
                                     valid_counts, latest_q, consume_sq)
    C0_, tsr0, ovf0 = C0["C"], C0["ts"], C0["ovf"]
    B, W, S = C0_.shape
    T = class_ids.shape[0]
    size = jnp.float32(window.size)
    fq = finals_q.astype(C0_.dtype)
    im = init_mask.astype(C0_.dtype)
    start = jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32), (B,))
    valid = (None if valid_counts is None
             else jnp.asarray(valid_counts, jnp.int32))
    arange_w = jnp.arange(W)

    def step(carry, inputs):
        C, tsr, ovf = carry
        t, ids, ts_t = inputs
        M = M_all[ids]
        j = start + t                                              # (B,)
        seed = arange_w[None, :] == (j % W)[:, None]               # (B, W)
        expire = tsr < ts_t[:, None] - size                       # (B, W)
        # rate-bound overflow: the seed slot's previous start is still live
        over = jnp.any(seed & ~expire, axis=1)                    # (B,)
        clear = (seed | expire).astype(C.dtype)
        C2 = C * (1.0 - clear)[:, :, None] \
            + seed.astype(C.dtype)[:, :, None] * im[None, None, :]
        C2 = jnp.einsum("bws,bst->bwt", C2, M, precision=_HIGHEST)
        if latest_q is None:
            m = jnp.einsum("bws,qs->bq", C2, fq, precision=_HIGHEST)
        else:
            m = latest_slot_counts(C2, fq, j, latest_q)
        tsr2 = jnp.where(seed, ts_t[:, None], tsr)
        if valid is not None:
            live = t < valid                                       # (B,)
            lf = live.astype(C.dtype)
            C2 = C2 * lf[:, None, None] + C * (1.0 - lf)[:, None, None]
            m = m * lf[:, None]
            tsr2 = jnp.where(live[:, None], tsr2, tsr)
            over = over & live
        if consume_sq is not None:
            C2 = consume_clear(C2, m, consume_sq)
        return (C2, tsr2, ovf | over), m

    ts_steps = jnp.arange(T, dtype=jnp.int32)
    ev_ts = jnp.asarray(event_ts, jnp.float32)
    (C_T, tsr_T, ovf_T), matches = jax.lax.scan(
        step, (C0_, tsr0, ovf0), (ts_steps, class_ids, ev_ts))
    return {"C": C_T, "ts": tsr_T, "ovf": ovf_T}, matches


def _scan_multi_count_ref(C0: jnp.ndarray, M_all: jnp.ndarray,
                          class_ids: jnp.ndarray, finals_q: jnp.ndarray,
                          init_mask: jnp.ndarray, epsilon: int,
                          start_pos=0, valid_counts=None,
                          latest_q=None, consume_sq=None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Count-window scan body (the unchanged classic eviction rule)."""
    B, W, S = C0.shape
    assert W >= epsilon + 1
    T = class_ids.shape[0]
    fq = finals_q.astype(C0.dtype)
    im = init_mask.astype(C0.dtype)
    start = jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32), (B,))
    valid = (None if valid_counts is None
             else jnp.asarray(valid_counts, jnp.int32))
    arange_w = jnp.arange(W)

    def step(C, inputs):
        t, ids = inputs
        M = M_all[ids]
        j = start + t                                              # (B,)
        seed = (arange_w[None, :] == (j % W)[:, None]).astype(C.dtype)
        expire = (arange_w[None, :]
                  == ((j - epsilon - 1) % W)[:, None]).astype(C.dtype)
        clear = jnp.maximum(seed, expire)                          # (B, W)
        C2 = C * (1.0 - clear)[:, :, None] \
            + seed[:, :, None] * im[None, None, :]
        C2 = jnp.einsum("bws,bst->bwt", C2, M, precision=_HIGHEST)
        if latest_q is None:
            m = jnp.einsum("bws,qs->bq", C2, fq, precision=_HIGHEST)
        else:
            m = latest_slot_counts(C2, fq, j, latest_q)
        if valid is not None:
            live = (t < valid).astype(C.dtype)                     # (B,)
            C2 = C2 * live[:, None, None] + C * (1.0 - live)[:, None, None]
            m = m * live[:, None]
        if consume_sq is not None:
            C2 = consume_clear(C2, m, consume_sq)
        return C2, m

    ts = jnp.arange(T, dtype=jnp.int32)
    C_T, matches = jax.lax.scan(step, C0, (ts, class_ids))
    return C_T, matches



# ---------------------------------------------------------------------------
# block-vectorized tECS arena builder (DESIGN.md §8)
# ---------------------------------------------------------------------------
#
# The per-event arena fold (vector/tecs_arena.arena_scan) scatters into the
# (B, capacity) node store many times per event — on backends without true
# in-place scatter that copies the whole store per write, which is what made
# arena-on scans ~1000× slower than counting-only ones.  The block builder
# splits the update into
#
#   1. a minimal sequential recurrence over the chunk — ONLY the per-cell
#      attribute table (node id / is-union / union children, four (B, W, S)
#      int32 arrays) is carried, one unrolled union-gadget fold per
#      predecessor depth per event (`arena_block_step`); each target
#      state reads its predecessor cells through static slices and
#      selects over the static candidate sources (`fold_sources`), never
#      a gather along the state axis; and
#
#   2. the per-event records of every allocation slot's validity and
#      child references, emitted by the same step in a fixed layout.
#
# Node ids are *virtual* while the chunk is in flight:
#
#   virtual id of the node allocated at (event t, step slot m)  =
#       voffset + t·M + m            (voffset = capacity + 1, so virtual ids
#                                     never collide with real store ids)
#
# Every event exposes the same static layout of M allocation slots (bottom,
# per-fold-depth extend/union regions, same-slot root folds, right-chain),
# so ids need no sequential allocator: the caller turns the validity mask
# into real ids with ONE chunk-level exclusive cumsum, translates virtual
# references in one vectorized pass, and lands every SoA field with one
# batched store update per chunk (tecs_arena.arena_scan_block).
#
# The record reconstruction consumes the emitted trace, so the allocation
# plan can never diverge from the recurrence.
#
# The record regions run over target states 1..S−1 only: the dead state 0
# never has predecessor edges, so its cells can never allocate — dropping
# the column shrinks every record array by 1/S for free (the id sequence is
# unchanged: those slots never allocated anything).


@dataclass(frozen=True)
class ArenaBlockLayout:
    """Static per-event slot layout of the block tECS builder.

    Slot regions, in id order (children always precede parents):

    * ``off_bottom``  — 1 slot: the event's ``new_bottom`` node.
    * ``off_ext[k]``  — W·|ext_states[k]| slots per fold depth k: extend
      nodes.  Only states with a *marking* predecessor edge at depth k
      (under some class) can ever extend — the rest are compressed away.
    * ``off_uni[k]``  — 3·W·|uni_states[k]| slots per fold depth k ≥ 1:
      the union gadget's up-to-3 nodes per cell.  Only states with > k
      predecessor edges (under some class) can union at depth k; depth 0
      never unions (empty accumulator), so ``off_uni[0] = −1``.
    * ``off_fs[fi]``  — 3·W·Q slots per *relevant* final state (final for
      ≥ 1 query) after the first: the same-slot root fold.  −1 for fi = 0.
    * ``off_chain``   — (ε+1)·Q slots: the Fig. 5(e) right-chain, ordered by
      decreasing start age (oldest first) so chain links point backwards.

    ``src_cand[k][s]`` holds the static candidate sources of target state
    ``s`` at fold depth ``k`` (:func:`fold_sources`): the fold reads them
    by slices and selects.

    The compression is purely static (from the predecessor tables), so the
    id sequence produced by the chunk-level cumsum still matches the
    per-event reference fold's allocation order exactly — the dropped
    slots could never allocate there either — and node stores come out
    bit-identical on non-overflowing lanes, which the parity suite
    asserts.
    """

    W: int
    S: int
    K: int
    Q: int
    epsilon: int
    cap: int
    init_states: Tuple[int, ...]
    fin_states: Tuple[int, ...]
    ext_states: Tuple[Tuple[int, ...], ...]   # per fold depth k
    uni_states: Tuple[Tuple[int, ...], ...]   # per fold depth k (k=0: ())
    src_cand: Tuple[Tuple[Tuple[int, ...], ...], ...]  # [k][s] → sources
    off_bottom: int
    off_ext: Tuple[int, ...]
    off_uni: Tuple[int, ...]
    off_fs: Tuple[int, ...]
    off_chain: int
    M: int

    @property
    def E(self) -> int:
        return self.epsilon + 1

    @property
    def voffset(self) -> int:
        """First virtual id (one past the store's sink slot)."""
        return self.cap + 1

    def _region_tables(self):
        """(kind, w_of, d_of) static (M,) decode tables (cached)."""
        cached = getattr(self, "_tables_cache", None)
        if cached is not None:
            return cached
        kind = np.full(self.M, UNION, np.int32)
        w_of = np.zeros(self.M, np.int32)
        d_of = np.full(self.M, -1, np.int32)
        kind[self.off_bottom] = BOTTOM
        for k, off in enumerate(self.off_ext):
            n = len(self.ext_states[k])
            kind[off:off + self.W * n] = OUTPUT
            w_of[off:off + self.W * n] = np.repeat(np.arange(self.W), n)
        for k, off in enumerate(self.off_uni):
            if off >= 0:
                n = len(self.uni_states[k])
                w_of[off:off + 3 * self.W * n] = np.repeat(
                    np.arange(self.W), 3 * n)
        for off in self.off_fs:
            if off >= 0:
                w_of[off:off + 3 * self.W * self.Q] = np.repeat(
                    np.arange(self.W), 3 * self.Q)
        # chain slots: slot w is dynamic ((j − d) mod W); record d instead
        d_of[self.off_chain:self.off_chain + self.E * self.Q] = np.repeat(
            np.arange(self.epsilon, -1, -1), self.Q)
        object.__setattr__(self, "_tables_cache", (kind, w_of, d_of))
        return kind, w_of, d_of

    def kind_static(self) -> np.ndarray:
        """(M,) int32 node kind per slot — static, never emitted."""
        return self._region_tables()[0]

    def pos_is_event(self) -> np.ndarray:
        """(M,) bool — slots whose ``pos`` label is the event position."""
        return self.kind_static() != UNION

    def w_static(self) -> np.ndarray:
        """(M,) int32 ring slot per layout slot (chain slots: see d_static)."""
        return self._region_tables()[1]

    def d_static(self) -> np.ndarray:
        """(M,) int32 chain age d (slot = (j−d) mod W); −1 off-chain."""
        return self._region_tables()[2]


def fold_sources(pred_idx_np) -> Tuple[Tuple[Tuple[int, ...], ...], ...]:
    """Static candidate sources of the predecessor fold, ``[k][s]``.

    The sorted set of values ``clip(pred_idx[c, s, k], 0, S−1)`` takes over
    every class ``c``, padded tails included, so selecting among them by
    the lane's class row equals a gather of the source cell for every
    input, bit for bit.  The fan-in of (k, s) is the set's size.
    """
    pi = np.asarray(pred_idx_np)
    pi = np.clip(pi, 0, pi.shape[1] - 1)
    return tuple(tuple(tuple(int(v) for v in np.unique(pi[:, s, k]))
                       for s in range(pi.shape[1]))
                 for k in range(pi.shape[2]))


def arena_block_layout(W: int, S: int, K: int, Q: int, epsilon: int,
                       cap: int, init_states, finals_sq_np, pred_idx_np,
                       pred_mark_np, pred_valid_np) -> ArenaBlockLayout:
    """Build the static slot layout for one (query tables, ring, capacity).

    ``pred_idx_np``/``pred_mark_np``/``pred_valid_np``: the (C, S, K)
    predecessor tables — they determine each target's candidate sources
    (:func:`fold_sources`) and which target states can allocate at each
    fold depth (region compression, see :class:`ArenaBlockLayout`).
    """
    fin = tuple(int(s) for s in range(S)
                if np.asarray(finals_sq_np)[s].any())
    pm = np.asarray(pred_mark_np).astype(bool)
    pv = np.asarray(pred_valid_np).astype(bool)
    ext_states = tuple(
        tuple(int(s) for s in range(S) if (pv[:, s, k] & pm[:, s, k]).any())
        for k in range(K))
    uni_states = tuple(
        () if k == 0 else
        tuple(int(s) for s in range(S) if pv[:, s, k].any())
        for k in range(K))
    off = 0
    off_bottom = off
    off += 1
    off_ext: List[int] = []
    off_uni: List[int] = []
    for k in range(K):
        off_ext.append(off)
        off += W * len(ext_states[k])
        if k == 0:
            off_uni.append(-1)
        else:
            off_uni.append(off)
            off += 3 * W * len(uni_states[k])
    off_fs: List[int] = []
    for fi in range(len(fin)):
        if fi == 0:
            off_fs.append(-1)
        else:
            off_fs.append(off)
            off += 3 * W * Q
    off_chain = off
    off += (epsilon + 1) * Q
    return ArenaBlockLayout(
        W=W, S=S, K=K, Q=Q, epsilon=epsilon, cap=cap,
        init_states=tuple(int(s) for s in init_states), fin_states=fin,
        ext_states=ext_states, uni_states=uni_states,
        src_cand=fold_sources(pred_idx_np), off_bottom=off_bottom,
        off_ext=tuple(off_ext), off_uni=tuple(off_uni),
        off_fs=tuple(off_fs), off_chain=off_chain, M=off)


def pack_pred_tables(pred_idx, pred_mark, pred_valid) -> np.ndarray:
    """Stack the three (C, S, K) predecessor tables → (C, S, K, 3) int32.

    One packed table means ONE gather per event inside the recurrence
    instead of three.  Returns numpy (callers cache it across jit traces;
    a traced constant must never be cached — it would leak the tracer).
    """
    return np.stack([np.asarray(pred_idx).astype(np.int32),
                     np.asarray(pred_mark).astype(np.int32),
                     np.asarray(pred_valid).astype(np.int32)], axis=-1)


def _union_gadget(acc, contrib, cval, v0):
    """One vectorized application of the paper's union gadgets (Fig. 5 a–d).

    acc/contrib: ``(id, is_union, left, right)`` tuples of broadcast-
    compatible int32 arrays (ids are virtual or real; NULL = empty).
    cval: bool — positions where ``contrib`` participates.  v0: int32 —
    virtual id of the gadget's first slot (slots v0, v0+1, v0+2).  All
    participants share the cell's max-start (that equality is what makes
    the gadgets vectorize — DESIGN.md §7), so no time-order comparison is
    needed.

    Returns ``(acc', records)`` where records is the 3-slot record tuple
    ``(valid0, left0, right0, valid12, left1, right1, left2, right2)``:
    slot 0 carries the pairwise union (cases a/b) or the spliced ``u2``
    (cases c/d); slots 1–2 carry ``u1``/``u`` of the union×union splice.
    The records are dead code for the in-scan recurrence (XLA removes
    them); the vectorized reconstruction consumes them.
    """
    a_id, a_u, a_l, a_r = acc
    c_id, c_u, c_l, c_r = contrib
    prev = a_id != ARENA_NULL
    do_u = cval & prev
    both = do_u & (a_u > 0) & (c_u > 0)
    single = do_u & ~both
    # (a): acc non-union → left = acc; (b): acc union → left = contrib
    case_a = single & (a_u == 0)
    l1 = jnp.where(case_a, a_id, c_id)
    r1 = jnp.where(case_a, c_id, a_id)
    # (c)/(d): both unions → 3 nodes splice the two odepth-1 chains.  The
    # right children share the cell's max-start, so the reference fold's
    # time-order comparison always resolves left = acc.right.
    rec0_l = jnp.where(single, l1, a_r)
    rec0_r = jnp.where(single, r1, c_r)
    n_id = jnp.where(do_u, jnp.where(both, v0 + 2, v0),
                     jnp.where(cval, c_id, a_id))
    n_u = jnp.where(do_u, 1, jnp.where(cval & ~prev, c_u, a_u))
    n_l = jnp.where(do_u, jnp.where(both, a_l, l1),
                    jnp.where(cval, c_l, a_l))
    n_r = jnp.where(do_u, jnp.where(both, v0 + 1, r1),
                    jnp.where(cval, c_r, a_r))
    records = (do_u, rec0_l, rec0_r, both, c_l, v0, a_l, v0 + 1)
    return (n_id, n_u, n_l, n_r), records


def _interleave3(a, b, c, shape):
    """Stack three gadget-slot arrays → (B, 3·n) in 0/1/2 slot order."""
    B = shape[0]
    return jnp.stack([jnp.broadcast_to(a, shape).reshape(B, -1),
                      jnp.broadcast_to(b, shape).reshape(B, -1),
                      jnp.broadcast_to(c, shape).reshape(B, -1)],
                     axis=-1).reshape(B, -1)


def _state_rank(states, S: int) -> jnp.ndarray:
    """(S,) int32 region rank of each state (0 for absent states).

    Built from lazy iota comparisons — Pallas kernels cannot capture
    constant arrays; absent states' ranks are never selected (their
    allocation masks are statically false).
    """
    iota_s = jax.lax.iota(jnp.int32, S)
    rank = jnp.zeros((S,), jnp.int32)
    for i, s in enumerate(states):
        rank = jnp.where(iota_s == s, i, rank)
    return rank


def _lane_picks() -> bool:
    """Whether region picks take one lane slice per state (the TPU)."""
    return jax.default_backend() == "tpu"


def _region_cols(xs, states) -> jnp.ndarray:
    """(B, W, S) arrays → (B, W·|states|·|xs|) record rows: per ring slot,
    per state of ``states``, the arrays' columns in order.

    On the TPU one lane slice per state, stacked along the lane axis: a
    stack on a new minor axis of |xs| lanes is copied through a padded
    layout there.  Elsewhere one slice per run of consecutive states: a
    slice per state makes the CPU backend recompute each slice's producer
    in a fusion of its own, half again the compiled builder.
    """
    B = xs[0].shape[0]
    if _lane_picks():
        cols = jnp.stack([x[:, :, s] for s in states for x in xs], axis=2)
        return cols.reshape(B, -1)
    runs = []
    for s in states:
        if runs and runs[-1][1] == s:
            runs[-1][1] = s + 1
        else:
            runs.append([s, s + 1])
    cols = [jnp.concatenate([x[:, :, a:b] for a, b in runs], axis=2)
            for x in xs]
    cols = cols[0] if len(xs) == 1 else jnp.stack(cols, axis=-1)
    return cols.reshape(B, -1)


def _fold_sources(fields, idx_k, cands):
    """Each target's predecessor cells at one fold depth, without a gather.

    fields: (B, W, S) int32 arrays; idx_k: (B, S) int32 per-lane source
    row (clipped to the state range); cands: per target state the static
    candidate sources (:func:`fold_sources`), which hold every value
    ``idx_k`` can take.  A target with one candidate is a static slice;
    more run a select chain over the candidates' slices.  Returns one
    (B, W, S) array per field, equal to ``take_along_axis(x, idx_k, 2)``.
    """
    cols = [[] for _ in fields]
    for s, cs in enumerate(cands):
        picks = [(idx_k[:, s] == c)[:, None] for c in cs[1:]]
        for f, x in enumerate(fields):
            col = x[:, :, cs[0]]
            for c, p in zip(cs[1:], picks):
                col = jnp.where(p, x[:, :, c], col)
            cols[f].append(col)
    return tuple(jnp.stack(c, axis=2) for c in cols)


def _clear_seed(cells, j, live, vbase, *, lay: ArenaBlockLayout,
                expire_t=None):
    """Ring maintenance for one event: expire + seed ``new_bottom(j)``.

    cells: ``(cid, cisU, cleft, cright)`` (B, W, S) int32; j/vbase: (B,)
    int32; live: (B,) bool.  Returns the fold-input table (seed bottom
    visible as a predecessor source; non-live lanes untouched).

    ``expire_t`` (optional, (B, W) bool) overrides the count-window
    single-slot rule with a precomputed eviction mask — the time-window
    path (DESIGN.md §9): slots whose start timestamp left the window, any
    number of them per step.  The mask is computed in closed form outside
    the scan (``repro.vector.tecs_arena`` via :func:`arena_slot_starts`),
    so the builder recurrence carries no timestamp ring of its own.
    """
    cid, cisU, cleft, cright = cells
    W, S = lay.W, lay.S
    arange_w = jax.lax.iota(jnp.int32, W)
    seed = (arange_w[None, :] == (j % W)[:, None]) & live[:, None]
    if expire_t is None:
        expire = (arange_w[None, :]
                  == ((j - lay.epsilon - 1) % W)[:, None]) & live[:, None]
    else:
        expire = (expire_t > 0) & live[:, None]
    cid = jnp.where((seed | expire)[:, :, None], ARENA_NULL, cid)
    iota_s = jax.lax.iota(jnp.int32, S)
    init_oh = jnp.zeros((S,), bool)
    for s0 in lay.init_states:
        init_oh = init_oh | (iota_s == s0)
    seed_cells = seed[:, :, None] & init_oh[None, None, :]
    cid = jnp.where(seed_cells, (vbase + lay.off_bottom)[:, None, None], cid)
    cisU = jnp.where(seed_cells, 0, cisU)
    return cid, cisU, cleft, cright


def _fold_cells(cells_in, cls_t, live, vbase, *, lay: ArenaBlockLayout,
                ptab):
    """The predecessor folds for one event: four (B, W, S) → new cell table.

    Returns ``(acc, pieces)`` — acc is the post-fold ``(id, isU, left,
    right)`` tuple, pieces the slot-layout-ordered list of per-region
    record tuples (``(valid, left)`` for extend regions — their right
    child is always NULL — and ``(valid, left, right)`` for union
    regions), each (B, region_size) int32, restricted to the states that
    can statically allocate there (region compression).
    """
    cid_in, cisU_in, cleft, cright = cells_in
    B, W, S = cid_in.shape
    pt = jnp.asarray(ptab)[cls_t]                          # (B, S, K, 3)
    iota_w = jax.lax.iota(jnp.int32, W)
    pieces = []
    acc = None

    def sel(x, states):            # (B, W, S) → (B, W·|states|), w-major
        if not states:
            return jnp.zeros((B, 0), jnp.int32)
        return _region_cols([jnp.broadcast_to(x, (B, W, S))], states)

    for k in range(lay.K):
        idx_k = jnp.clip(pt[:, :, k, 0], 0, S - 1)            # (B, S)
        src_id, src_u, src_l, src_r = _fold_sources(
            (cid_in, cisU_in, cleft, cright), idx_k, lay.src_cand[k])
        mk = pt[:, :, k, 1][:, None, :] > 0
        cval = ((pt[:, :, k, 2][:, None, :] > 0) & (src_id != ARENA_NULL)
                & live[:, None, None])                     # (B, W, S)
        m_ext = cval & mk
        e_states = lay.ext_states[k]
        n_e = len(e_states)
        v_ext = (vbase[:, None, None] + lay.off_ext[k]
                 + iota_w[None, :, None] * n_e
                 + _state_rank(e_states, S)[None, None, :])
        pieces.append((sel(m_ext.astype(jnp.int32), e_states),
                       sel(src_id, e_states)))
        contrib = (jnp.where(m_ext, v_ext, src_id),
                   jnp.where(cval & ~mk, src_u, 0), src_l, src_r)
        if acc is None:
            null3 = jnp.full((B, W, S), ARENA_NULL, jnp.int32)
            acc = (jnp.where(cval, contrib[0], null3),
                   jnp.where(cval, contrib[1], 0),
                   jnp.where(cval, contrib[2], null3),
                   jnp.where(cval, contrib[3], null3))
        else:
            u_states = lay.uni_states[k]
            n_u = len(u_states)
            v0 = (vbase[:, None, None] + lay.off_uni[k]
                  + 3 * (iota_w[None, :, None] * n_u
                         + _state_rank(u_states, S)[None, None, :]))
            acc, recs = _union_gadget(acc, contrib, cval, v0)
            v_do, l0, r0, v_both, l1_, r1_, l2_, r2_ = recs

            def tri(a, b, c):      # (B, W·n·3): slots 0/1/2 per cell
                return _region_cols(
                    [jnp.broadcast_to(x, (B, W, S)) for x in (a, b, c)],
                    u_states)

            if n_u:
                pieces.append((
                    tri(v_do.astype(jnp.int32), v_both.astype(jnp.int32),
                        v_both.astype(jnp.int32)),
                    tri(l0, l1_, l2_), tri(r0, r1_, r2_)))
            else:
                z = jnp.zeros((B, 0), jnp.int32)
                pieces.append((z, z, z))
    return acc, pieces


def _roots_step(cells_t, hit_t, j, vbase, *, lay: ArenaBlockLayout,
                finals_sq):
    """Root construction for one event, from the POST-event cell table.

    Same-slot final cells fold through the union gadgets, then slots chain
    right-wards in decreasing start order (Fig. 5(e)).  NOTE matches the
    reference fold: ``hit_t`` alone gates the folds (the counting scan
    already zeroes matches on dead steps).  Returns (pieces, root).
    """
    cid, cisU, cleft, cright = cells_t
    B, W, S = cid.shape
    Q = lay.Q
    hit_t = hit_t > 0
    pieces = []
    sa = None
    fs_ix = jax.lax.iota(jnp.int32, W * Q).reshape(W, Q)
    for fi, s_f in enumerate(lay.fin_states):
        cval = ((cid[:, :, s_f] != ARENA_NULL)[:, :, None]
                & (finals_sq[s_f][None, None, :] > 0)
                & hit_t[:, None, :])                       # (B, W, Q)
        contrib = tuple(
            jnp.broadcast_to(c[:, :, s_f][:, :, None], (B, W, Q))
            for c in (cid, cisU, cleft, cright))
        if sa is None:
            nullq = jnp.full((B, W, Q), ARENA_NULL, jnp.int32)
            sa = (jnp.where(cval, contrib[0], nullq),
                  jnp.where(cval, contrib[1], 0),
                  jnp.where(cval, contrib[2], nullq),
                  jnp.where(cval, contrib[3], nullq))
        else:
            v0 = vbase[:, None, None] + lay.off_fs[fi] + 3 * fs_ix[None]
            sa, recs = _union_gadget(sa, contrib, cval, v0)
            v_do, l0, r0, v_both, l1_, r1_, l2_, r2_ = recs
            sh = (B, W, Q)
            pieces.append((
                _interleave3(v_do.astype(jnp.int32),
                             v_both.astype(jnp.int32),
                             v_both.astype(jnp.int32), sh),
                _interleave3(l0, l1_, l2_, sh),
                _interleave3(r0, r1_, r2_, sh)))
    if sa is None:  # no final states at all: no roots ever
        sa = (jnp.full((B, W, Q), ARENA_NULL, jnp.int32),) * 4

    # right-chain over slots in decreasing start order (oldest start first)
    E = lay.E
    d_arr = lay.epsilon - jax.lax.iota(jnp.int32, E)
    slot_d = (j[:, None] - d_arr[None, :]) % W             # (B, E)
    gidx = jnp.broadcast_to(slot_d[:, :, None], (B, E, Q))
    m_id = jnp.take_along_axis(sa[0], gidx, axis=1)        # (B, E, Q)
    m_val = m_id != ARENA_NULL
    rank = jnp.cumsum(m_val.astype(jnp.int32), axis=1)
    v_chain = (vbase[:, None, None] + lay.off_chain
               + (jax.lax.iota(jnp.int32, E)[:, None] * Q
                  + jax.lax.iota(jnp.int32, Q)[None, :])[None])
    alloc = m_val & (rank >= 2)
    elem = jnp.where(m_val, jnp.where(alloc, v_chain, m_id), ARENA_NULL)
    pos_e = jnp.where(m_val, jax.lax.iota(jnp.int32, E)[None, :, None], -1)
    last = jax.lax.cummax(pos_e, axis=1)
    prev_pos = jnp.concatenate(
        [jnp.full((B, 1, Q), -1, jnp.int32), last[:, :-1]], axis=1)
    prev_elem = jnp.take_along_axis(elem, jnp.clip(prev_pos, 0, E - 1),
                                    axis=1)
    prev_elem = jnp.where(prev_pos >= 0, prev_elem, ARENA_NULL)
    pieces.append((alloc.astype(jnp.int32).reshape(B, -1),
                   m_id.reshape(B, -1), prev_elem.reshape(B, -1)))
    root = jnp.take_along_axis(elem, jnp.clip(last[:, -1:], 0, E - 1),
                               axis=1)[:, 0]
    root = jnp.where(last[:, -1] >= 0, root, ARENA_NULL)   # (B, Q)
    return pieces, root


def arena_block_step(cells, cls_t, hit_t, j, live, vbase, *,
                     lay: ArenaBlockLayout, ptab, finals_sq,
                     sparse_roots: bool = False, sparse_steps: bool = False,
                     expire_t=None, consume_t=None):
    """One event of the block builder: recurrence + record emission.

    cells: four (B, W, S) int32 arrays (id / is-union / left / right).
    cls_t/j/vbase: (B,) int32 (``vbase`` is per-lane: segmented execution
    places lanes at different stream offsets).  hit_t: (B, Q) int32.
    live: (B,) bool.  ``expire_t`` (optional, (B, W)): precomputed
    time-window eviction mask (see :func:`_clear_seed`).  ``consume_t``
    (optional, (B, S)): CONSUME BY ANY clear mask — after the event's
    roots are recorded, cells of the flagged states drop across every
    ring slot (the host's emit-then-clear order: the counting kernels
    zero the same states in the count ring, this is the node-level
    mirror).  Clearing allocates nothing, so the record layout and the
    chunk-level id assignment are untouched.  Returns
    ``(cells', (valid, left, right), root)`` — the per-event record rows
    (B, M) in slot-layout order and root (B, Q).

    ``sparse_roots`` wraps the root construction in a ``lax.cond``: steps
    without any hit skip the fold/chain work entirely at runtime (hits are
    sparse in most streams).  ``sparse_steps`` does the same for the whole
    step — all-dead steps (the rank tail of under-filled lanes after the
    partitioned scatter) skip fold, emission and roots at runtime and
    return the cell table unchanged with all-invalid records.  Both
    branches emit identical rows because the records are canonical:
    ``left``/``right`` are NULL wherever ``valid`` is 0.
    """
    B = cls_t.shape[0]
    Q = lay.Q

    def live_step(cells):
        cells_in = _clear_seed(cells, j, live, vbase, lay=lay,
                               expire_t=expire_t)
        acc, pieces = _fold_cells(cells_in, cls_t, live, vbase, lay=lay,
                                  ptab=ptab)
        lv = live[:, None, None]
        out = tuple(jnp.where(lv, a, c) for a, c in zip(acc, cells_in))

        def roots(_):
            return _roots_step(out, hit_t, j, vbase, lay=lay,
                               finals_sq=finals_sq)

        if sparse_roots:
            n_fs = max(len(lay.fin_states) - 1, 0)

            def no_roots(_):
                zfs = jnp.zeros((B, 3 * lay.W * Q), jnp.int32)
                zch = jnp.zeros((B, lay.E * Q), jnp.int32)
                return ([(zfs, zfs, zfs)] * n_fs + [(zch, zch, zch)],
                        jnp.full((B, Q), ARENA_NULL, jnp.int32))

            root_pieces, root = jax.lax.cond(jnp.any(hit_t > 0), roots,
                                             no_roots, None)
        else:
            root_pieces, root = roots(None)

        if consume_t is not None:
            clr = (consume_t > 0) & live[:, None]              # (B, S)
            out = ((jnp.where(clr[:, None, :], ARENA_NULL, out[0]),)
                   + out[1:])

        all_pieces = pieces + list(root_pieces)
        nullcol = jnp.full((B, 1), ARENA_NULL, jnp.int32)

        def third(p):              # extend regions have no right child
            return p[2] if len(p) == 3 else jnp.full_like(p[1], ARENA_NULL)

        valid = jnp.concatenate(
            [live.astype(jnp.int32)[:, None]] + [p[0] for p in all_pieces],
            axis=1)
        left = jnp.concatenate([nullcol] + [p[1] for p in all_pieces],
                               axis=1)
        right = jnp.concatenate([nullcol] + [third(p) for p in all_pieces],
                                axis=1)
        ok = valid > 0
        left = jnp.where(ok, left, ARENA_NULL)
        right = jnp.where(ok, right, ARENA_NULL)
        return out, (valid, left, right), root

    if not sparse_steps:
        return live_step(cells)

    def dead_step(cells):
        zv = jnp.zeros((B, lay.M), jnp.int32)
        nl = jnp.full((B, lay.M), ARENA_NULL, jnp.int32)
        return cells, (zv, nl, nl), jnp.full((B, Q), ARENA_NULL, jnp.int32)

    return jax.lax.cond(jnp.any(live), live_step, dead_step, cells)


def pick_segments(T: int, W: int, max_seg: int = 8) -> int:
    """Number of parallel chunk segments for the recurrence scan.

    The cell table has finite memory (window ε+1 ≤ W): a segment's start
    state is reproduced exactly by replaying the W preceding events from
    an empty table (every run alive at the handoff started inside the
    replay; virtual node ids depend only on the absolute event index, so
    the replayed prefix computes identical ids and its emissions are
    simply discarded).  Splitting a T-event chunk into n segments turns a
    T-step × B-wide scan into a (W + T/n)-step × nB-wide scan.  Requires
    T/n ≥ W (segment replays never leave the chunk) and n | T.

    NOTE: on CPU XLA the builder step is bandwidth-bound, so the replay
    overhead loses — measured slower for every n > 1 — and the default
    everywhere is n_seg = 1.  The knob exists for accelerator backends
    where shorter scans amortize per-step launch cost.
    """
    best = 1
    for n in range(2, max_seg + 1):
        if T % n == 0 and T // n >= W:
            best = n
    return best


def arena_build_ref(cells0, class_ids, hits, start, valid_counts, *,
                    lay: ArenaBlockLayout, ptab, finals_sq,
                    n_seg: int = 1, expire=None, consume=None):
    """Block tECS builder over one chunk — the pure-jnp oracle.

    cells0: four (B, W, S) int32 arrays (chunk-start cell table).
    class_ids: (T, B) int32.  hits: (T, B, Q) int32/bool.
    start/valid_counts: (B,) int32.  n_seg: parallel segments
    (:func:`pick_segments`).  ``expire`` (optional, (T, B, W) bool):
    precomputed per-step time-window eviction masks (DESIGN.md §9; count
    windows pass None and keep the closed-form single-slot rule).
    ``consume`` (optional, (T, B, S) bool): per-step CONSUME BY ANY clear
    masks (precomputed from the counting scan's matches) — applied after
    each event's roots, see :func:`arena_block_step`.  Returns
    ``(cells_T, valid, left, right, roots)`` with the record
    arrays (T, B, M) int32 in slot-layout order and roots (T, B, Q), on
    virtual ids.  The arena's only builder on every platform
    (``tecs_arena.arena_scan_block``).
    """
    xs, cells0_seg = segment_operands(cells0, class_ids, hits, start,
                                      valid_counts, lay=lay, n_seg=n_seg,
                                      expire=expire, consume=consume)

    def step(cells, x):
        cls_t, hit_t, j, live, vb = x[:5]
        extra = list(x[5:])
        exp_t = extra.pop(0) if expire is not None else None
        con_t = extra.pop(0) if consume is not None else None
        out, recs, root = arena_block_step(
            cells, cls_t, hit_t, j, live, vb, lay=lay, ptab=ptab,
            finals_sq=finals_sq, sparse_roots=True, sparse_steps=True,
            expire_t=exp_t, consume_t=con_t)
        return out, recs + (root,)

    cells_fin, ys = jax.lax.scan(step, cells0_seg, xs)
    return assemble_records(cells_fin, ys[:3], ys[3],
                            class_ids.shape[0], class_ids.shape[1],
                            lay=lay, n_seg=n_seg)


def segment_operands(cells0, class_ids, hits, start, valid_counts, *,
                     lay: ArenaBlockLayout, n_seg: int, expire=None,
                     consume=None):
    """Build the (steps, n_seg·B, …) scan operands for segmented execution.

    Segment g owns global steps [g·G, (g+1)·G) and runs W extra replay
    steps before them (segment 0 replays into the void: those steps are
    dead, its start cells are the carried chunk-start table; later
    segments start from empty cells).  ``expire`` (optional, (T, B, W))
    appends the precomputed time-eviction mask as a sixth operand — it is
    closed-form in the absolute event index, so segment replays index the
    same global rows and reproduce the handoff state exactly.  ``consume``
    (optional, (T, B, S)) appends the CONSUME BY ANY clear masks the same
    way (also indexed by absolute step, so replays reproduce the clears).
    Returns ``((cls, hit, j, live, vbase[, expire][, consume]),
    cells0_seg)``.
    """
    T, B = class_ids.shape
    W = lay.W
    Q = lay.Q
    hits = jnp.asarray(hits).astype(jnp.int32)
    if n_seg == 1:
        ts = jnp.arange(T, dtype=jnp.int32)
        j = start[None, :] + ts[:, None]
        live = ts[:, None] < valid_counts[None, :]
        vb = jnp.broadcast_to((lay.voffset + ts * lay.M)[:, None], (T, B))
        xs = (class_ids, hits, j, live, vb)
        if expire is not None:
            xs = xs + (jnp.asarray(expire).astype(jnp.int32),)
        if consume is not None:
            xs = xs + (jnp.asarray(consume).astype(jnp.int32),)
        return xs, tuple(cells0)
    assert T % n_seg == 0 and T // n_seg >= W, (T, n_seg, W)
    G = T // n_seg
    steps = W + G
    t_idx = (jnp.arange(n_seg, dtype=jnp.int32)[:, None] * G - W
             + jnp.arange(steps, dtype=jnp.int32)[None, :])   # (n_seg, steps)
    tc = jnp.clip(t_idx, 0, T - 1)

    def seg(x):                    # (T, B, ...) → (steps, n_seg·B, ...)
        g = x[tc]                  # (n_seg, steps, B, ...)
        return jnp.moveaxis(g, 0, 1).reshape((steps, n_seg * B)
                                             + x.shape[2:])

    t_real = jnp.moveaxis(jnp.broadcast_to(
        t_idx[:, :, None], (n_seg, steps, B)), 0, 1).reshape(steps, -1)
    live = (t_real >= 0) & (t_real < jnp.tile(valid_counts, n_seg)[None, :])
    j = jnp.tile(start, n_seg)[None, :] + t_real
    vb = lay.voffset + t_real * lay.M
    null_cells = tuple(jnp.full_like(c, ARENA_NULL) for c in cells0)
    cells0_seg = tuple(
        jnp.concatenate([c0] + [n0] * (n_seg - 1), axis=0)
        for c0, n0 in zip(cells0, null_cells))
    xs = (seg(class_ids), seg(hits), j, live, vb)
    if expire is not None:
        xs = xs + (seg(jnp.asarray(expire).astype(jnp.int32)),)
    if consume is not None:
        xs = xs + (seg(jnp.asarray(consume).astype(jnp.int32)),)
    return xs, cells0_seg


def assemble_records(cells_fin, recs, roots, T, B, *,
                     lay: ArenaBlockLayout, n_seg: int):
    """Reorder segmented scan emissions back to (T, B, …) record arrays.

    Each segment's first W steps are replay (or dead, for segment 0) and
    are dropped; segment-owned rows interleave back into stream order.
    """
    W = lay.W

    def unseg(y):                  # (steps, n_seg·B, ...) → (T, B, ...)
        if n_seg == 1:
            return y
        steps = y.shape[0]
        G = steps - W
        y = y[W:].reshape((G, n_seg, B) + y.shape[2:])
        return jnp.moveaxis(y, 1, 0).reshape((T, B) + y.shape[3:])

    valid, left, right = (unseg(y) for y in recs)
    roots = unseg(roots)
    cells_T = tuple(c[-B:] for c in cells_fin) if n_seg > 1 else cells_fin
    return cells_T, valid, left, right, roots


def arena_slot_starts(sstart0, gpos, start, valid_counts, *, W: int):
    """(T, B, W) per-step slot-start table, in closed form (no scan).

    Slot w at step t was last seeded at step ``t' = t_eff − ((start +
    t_eff − w) mod W)`` with ``t_eff = min(t, valid−1)`` (dead steps never
    seed); if that is negative the slot kept its chunk-start label
    ``sstart0``.  Feeds the ``max_start`` decode of the store update, and —
    fed with event *timestamps* instead of positions — the closed-form
    per-slot timestamp table behind the time-window eviction masks
    (DESIGN.md §9): seeding is position-driven in both window modes, so
    the same recurrence-free decode applies.
    """
    T, B = gpos.shape
    ts = jnp.arange(T, dtype=jnp.int32)[:, None, None]     # (T, 1, 1)
    t_eff = jnp.minimum(ts, jnp.maximum(valid_counts, 0)[None, :, None] - 1)
    w = jnp.arange(W, dtype=jnp.int32)[None, None, :]
    t_seed = t_eff - (start[None, :, None] + t_eff - w) % W
    g = jnp.take_along_axis(jnp.moveaxis(gpos, 1, 0)[:, None, :],
                            jnp.moveaxis(jnp.clip(t_seed, 0, T - 1),
                                         1, 0), axis=2)    # (B, T, W)
    g = jnp.moveaxis(g, 1, 0)
    return jnp.where(t_seed >= 0, g, sstart0[None])
