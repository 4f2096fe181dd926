"""Array-backed tECS arena on device (paper §5.1–5.2, DESIGN.md §7).

The host tECS (:mod:`repro.core.tecs`) is a pointer DAG built one node at a
time; the device scan previously stopped at match *counts* and re-ran the
host engine at every hit position (the old deviation D1).  This module closes
that gap: the tECS is maintained **on device** as a structure-of-arrays node
store — ``kind/pos/max_start/left/right`` int32 arrays with a per-lane bump
allocator — updated inside the same jitted step as the counting scan, using
the paper's ``new_bottom``/``extend``/``union``/``merge`` discipline
(time-ordered unions, 3-bounded output-depth via the Fig. 5 gadgets) as
vectorized updates over the ``(B, W, S)`` state ring.

Keying (the vectorization insight)
----------------------------------
Algorithm 1 keys its hash table ``T`` by det state and aggregates nodes of
different starts in *union-lists*.  The device ring already splits runs by
start slot, so the arena keys cells by ``(start-slot w, det state s)``: every
run in a cell shares one start position, hence one ``max_start`` — which is
exactly the precondition of the paper's ``union`` gadgets.  Per event the
cell update is

    cell'[w, s'] = ⋃ over predecessors p of
                     extend(cell[w, p], j)   for marking   edges p →• s'
                     cell[w, p]              for unmarking edges p →◦ s'

with the seed slot cleared and re-seeded with ``new_bottom(j)`` and the
expired slot dropped — the exact node-level mirror of the counting step, so
counts and enumerated sets agree by construction (runs ↔ complex events,
Thm 3).  At hit positions a *root* is built per query: same-slot cells fold
with the union gadgets (equal max-start), then slots chain right-wards in
decreasing start order (Fig. 5(e) merge) — ready for Algorithm 2.

Enumeration stays output-linear: every node reachable from a root is inside
the window (the ring evicts expired starts before they can be referenced),
so the DFS prune never cuts a productive branch, and the gadget discipline
keeps output-depth ≤ 3 (checked by ``check_invariants`` and the paper-claims
tests).

Allocation
----------
Each lane owns ``capacity`` node slots plus one *sink* slot at index
``capacity``.  Per update the number of nodes needed per cell is computed
(extend: 1; union: 1, or 3 for the union×union gadget) and lanes assign ids
by exclusive cumulative sum from their bump pointer.  The production path
(:func:`arena_scan_block`, DESIGN.md §8) batches this over whole chunks: a
lean scan emits fixed-layout node records on a *virtual* id space, ONE
chunk-level cumsum assigns real ids, and each SoA field lands with one
batched store update per chunk; the per-event fold (:func:`arena_scan`) is
kept as the parity reference.  When a lane's pointer would pass
``capacity`` the lane's ``ovf`` flag latches and all further writes clamp
into the sink slot: recognition (counts/hits) is unaffected, but
enumeration for that lane raises until the arena is reset/compacted
(overflow policy, DESIGN.md §7).

Node ids are bump-ordered, so children always have smaller ids than their
parents — fetched arenas are topologically sorted by construction, which the
invariant checker exploits.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.events import ComplexEvent
from ..core.tecs import (BOTTOM, OUTPUT, UNION, enumerate_arena,
                         enumerate_arena_batch)
from ..kernels import ref as kref
from ..kernels import window as wkern

NULL = -1  # empty cell / absent child
_NO_CAP = 1 << 62  # per-root match cap meaning "unbounded" (enumerate_batch)

ARENA_IMPLS = ("block", "fold")  # block: vectorized (default); fold: per-event


def check_arena_impl(arena_impl: str) -> str:
    """Validate an ``arena_impl`` selector (shared by every engine ctor)."""
    if arena_impl not in ARENA_IMPLS:
        raise ValueError(
            f"arena_impl must be one of {ARENA_IMPLS}, got {arena_impl!r}")
    return arena_impl


# ---------------------------------------------------------------------------
# static tables: predecessor lists of the det CEA, by (class, target state)
# ---------------------------------------------------------------------------


@dataclass
class ArenaTables:
    """Per-query static tables driving the arena update.

    ``pred_*[c, s', k]`` lists the ≤ K predecessor edges into det state
    ``s'`` under symbol class ``c``: source state, marking flag (• = extend,
    ◦ = pass-through), and a validity mask for the padded tail.
    """

    pred_idx: jnp.ndarray    # (C, S, K) int32 source det state
    pred_mark: jnp.ndarray   # (C, S, K) bool  — True: •-edge (extend)
    pred_valid: jnp.ndarray  # (C, S, K) bool
    finals_sq: jnp.ndarray   # (S, Q) bool — final-state masks, per query
    init_states: Tuple[int, ...]  # seed targets (one per packed query block)
    num_states: int
    num_queries: int
    max_indegree: int


def build_tables(delta_mark: np.ndarray, delta_unmark: np.ndarray,
                 finals_q: np.ndarray, init_states: Sequence[int]
                 ) -> ArenaTables:
    """Invert forward ``delta`` tables into per-target predecessor lists.

    delta_mark/delta_unmark: (S, C) int32 forward maps, 0 = dead (dropped).
    finals_q: (Q, S) bool/float final-state masks.
    """
    dm = np.asarray(delta_mark)
    du = np.asarray(delta_unmark)
    S, C = dm.shape
    preds: List[List[List[Tuple[int, bool]]]] = \
        [[[] for _ in range(S)] for _ in range(C)]
    for p in range(1, S):          # dead state 0 is never a source
        for c in range(C):
            t = int(dm[p, c])
            if t != 0:
                preds[c][t].append((p, True))   # marks first: extends are
            t = int(du[p, c])                   # non-union, cheapest gadget
            if t != 0:
                preds[c][t].append((p, False))
    K = max(1, max(len(preds[c][s]) for c in range(C) for s in range(S)))
    pred_idx = np.zeros((C, S, K), np.int32)
    pred_mark = np.zeros((C, S, K), bool)
    pred_valid = np.zeros((C, S, K), bool)
    for c in range(C):
        for s in range(S):
            for k, (p, m) in enumerate(preds[c][s]):
                pred_idx[c, s, k] = p
                pred_mark[c, s, k] = m
                pred_valid[c, s, k] = True
    fq = np.asarray(finals_q).astype(bool)
    return ArenaTables(
        pred_idx=jnp.asarray(pred_idx),
        pred_mark=jnp.asarray(pred_mark),
        pred_valid=jnp.asarray(pred_valid),
        finals_sq=jnp.asarray(fq.T),
        init_states=tuple(int(s) for s in init_states),
        num_states=S, num_queries=fq.shape[0], max_indegree=K)


def tables_from_symbolic(symbolic) -> ArenaTables:
    """Arena tables for a single :class:`~repro.vector.symbolic.SymbolicCEA`."""
    return build_tables(symbolic.delta_mark, symbolic.delta_unmark,
                        symbolic.finals[None, :], (symbolic.initial,))


def tables_from_packed(symbolics, offsets, class_of, reps) -> ArenaTables:
    """Arena tables for the packed multi-query engine (block-diagonal CEA).

    ``reps[c]`` is a representative bit-vector of joint class ``c``; each
    query block maps it through its own class partition.  Block-local dead
    states (0) stay "none"; live targets/sources shift by the block offset.
    """
    n_classes = int(np.asarray(class_of).max()) + 1
    S_hat = sum(s.num_states for s in symbolics)
    dm = np.zeros((S_hat, n_classes), np.int32)
    du = np.zeros((S_hat, n_classes), np.int32)
    finals = np.zeros((len(symbolics), S_hat), bool)
    inits = []
    for qi, sym in enumerate(symbolics):
        off = offsets[qi]
        for c in range(n_classes):
            cq = int(sym.class_of[reps[c]])
            for s in range(1, sym.num_states):
                t = int(sym.delta_mark[s, cq])
                if t != 0:
                    dm[off + s, c] = off + t
                t = int(sym.delta_unmark[s, cq])
                if t != 0:
                    du[off + s, c] = off + t
        finals[qi, off:off + sym.num_states] = sym.finals
        inits.append(off + sym.initial)
    return build_tables(dm, du, finals, inits)


# ---------------------------------------------------------------------------
# device arena state
# ---------------------------------------------------------------------------


def init_arena(batch: int, capacity: int, ring: int, num_states: int) -> dict:
    """Fresh arena pytree: per-lane node store + cell table + bump pointer.

    Index ``capacity`` of every field array is the overflow sink slot.
    """
    shape = (batch, capacity + 1)
    return {
        "kind": jnp.full(shape, NULL, jnp.int32),
        "pos": jnp.full(shape, NULL, jnp.int32),
        "maxs": jnp.full(shape, NULL, jnp.int32),
        "left": jnp.full(shape, NULL, jnp.int32),
        "right": jnp.full(shape, NULL, jnp.int32),
        "cell": jnp.full((batch, ring, num_states), NULL, jnp.int32),
        "ptr": jnp.zeros((batch,), jnp.int32),
        "ovf": jnp.zeros((batch,), bool),
    }


def _alloc(ar: dict, need: jnp.ndarray) -> Tuple[dict, jnp.ndarray]:
    """Bump-allocate ``need[b, m]`` nodes per slot; returns base id per slot.

    A slot needing ``n`` nodes owns ids ``base .. base+n-1``.  Lanes that
    would pass capacity latch ``ovf``; their ids clamp into the sink at
    write time.
    """
    cap = ar["kind"].shape[1] - 1
    csum = jnp.cumsum(need, axis=1)
    base = ar["ptr"][:, None] + csum - need
    new_ptr = ar["ptr"] + csum[:, -1]
    out = dict(ar)
    out["ovf"] = ar["ovf"] | (new_ptr > cap)
    out["ptr"] = jnp.minimum(new_ptr, cap)
    return out, base


def _write(ar: dict, ids: jnp.ndarray, mask: jnp.ndarray, *,
           kind, pos, maxs, left, right) -> dict:
    """Masked SoA scatter of one node per (lane, slot); invalid → sink."""
    cap = ar["kind"].shape[1] - 1
    b = jnp.arange(ids.shape[0])[:, None]
    wid = jnp.where(mask & (ids < cap), ids, cap)
    out = dict(ar)
    for name, val in (("kind", kind), ("pos", pos), ("maxs", maxs),
                      ("left", left), ("right", right)):
        v = jnp.broadcast_to(jnp.asarray(val, jnp.int32), ids.shape)
        out[name] = ar[name].at[b, wid].set(v)
    return out


def _gather(field: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """field[b, ids[b, m]] with NULL-safe clamping (callers mask)."""
    b = jnp.arange(ids.shape[0])[:, None]
    return field[b, jnp.clip(ids, 0, field.shape[1] - 1)]


def _ref(ids: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Node *reference* for freshly allocated ids (overflow → sink id)."""
    return jnp.minimum(ids, cap)


def _union_fold(ar: dict, acc: jnp.ndarray, contrib: jnp.ndarray,
                valid: jnp.ndarray) -> Tuple[dict, jnp.ndarray]:
    """One fold iteration of the paper's ``union`` (Fig. 5 gadgets (a)–(d)).

    acc/contrib/valid: (B, M) node ids + mask.  Where ``valid``:
    ``acc := acc is NULL ? contrib : union(acc, contrib)``.  Inputs must be
    safe nodes with equal max-start (guaranteed per-cell / per-slot); the
    result is safe, time-ordered, and output-depth ≤ 3.
    """
    cap = ar["kind"].shape[1] - 1
    has_acc = acc != NULL
    do_u = valid & has_acc
    ka = _gather(ar["kind"], acc) == UNION
    kc = _gather(ar["kind"], contrib) == UNION
    both = do_u & ka & kc
    single = do_u & ~both
    need = jnp.where(do_u, jnp.where(both, 3, 1), 0)
    ar, base = _alloc(ar, need)

    m = jnp.maximum(_gather(ar["maxs"], acc), _gather(ar["maxs"], contrib))
    # (a): acc non-union → left = acc; (b): contrib non-union → left = contrib
    case_a = single & ~ka
    l1 = jnp.where(case_a, acc, contrib)
    r1 = jnp.where(case_a, contrib, acc)
    # (c)/(d): both unions → 3 nodes splice the two odepth-1 chains
    n1l = _gather(ar["left"], acc)
    n1r = _gather(ar["right"], acc)
    n2l = _gather(ar["left"], contrib)
    n2r = _gather(ar["right"], contrib)
    m1r = _gather(ar["maxs"], n1r)
    m2r = _gather(ar["maxs"], n2r)
    ge = m1r >= m2r
    # id0: the single-case union, or u2 = n1.right ∪ n2.right (time-ordered)
    ar = _write(ar, base, single | both,
                kind=UNION, pos=NULL,
                maxs=jnp.where(single, m, jnp.maximum(m1r, m2r)),
                left=jnp.where(single, l1, jnp.where(ge, n1r, n2r)),
                right=jnp.where(single, r1, jnp.where(ge, n2r, n1r)))
    # id1: u1 = n2.left ∨ u2 ; id2: u = n1.left ∨ u1
    ar = _write(ar, base + 1, both, kind=UNION, pos=NULL, maxs=m,
                left=n2l, right=_ref(base, cap))
    ar = _write(ar, base + 2, both, kind=UNION, pos=NULL, maxs=m,
                left=n1l, right=_ref(base + 1, cap))
    new_acc = jnp.where(
        do_u, jnp.where(both, _ref(base + 2, cap), _ref(base, cap)),
        jnp.where(valid, contrib, acc))
    return ar, new_acc


# ---------------------------------------------------------------------------
# the arena scan: one chunk of T events, vectorized over lanes
# ---------------------------------------------------------------------------


def arena_scan(tables: ArenaTables, arena: dict, class_ids: jnp.ndarray,
               gpos: jnp.ndarray, start: jnp.ndarray, valid: jnp.ndarray,
               hits: jnp.ndarray, *, epsilon: int, expire=None,
               consume=None) -> Tuple[dict, jnp.ndarray]:
    """Maintain the tECS arena over one chunk — per-event reference fold.

    This is the slow-but-obviously-faithful implementation (one traced
    inner fold and one store scatter chain per event); the production path
    is :func:`arena_scan_block`, which replays this fold's allocation order
    with block-level id assignment and one scatter per field per CHUNK
    (DESIGN.md §8).  Kept as the parity oracle: tests pin the block path's
    node stores bit-identical against it.

    class_ids: (T, B) int32 symbol classes (the kernel's trace operand).
    gpos:      (T, B) int32 *global* stream position per step (node labels);
               ignored where dead.
    start:     (B,) int32 ring-local substream offsets (consumed mod W).
    valid:     (B,) int32 dense prefix of real events per lane this chunk.
    hits:      (T, B, Q) bool — positions with ≥ 1 match (from the counting
               scan); roots are built (and nodes allocated) only there.
    expire:    optional (T, B, W) bool — precomputed time-window eviction
               masks (:func:`window_expire_masks`, DESIGN.md §9); cells in
               expired slots drop before the predecessor folds and root
               construction, exactly like the counting ring.  ``epsilon``
               then only sets the root-chain extent (``ring − 1``: every
               live start is within the last W positions).  None keeps the
               count-window single-slot rule.
    consume:   optional (T, B, S) bool — CONSUME BY ANY clear masks
               (precomputed from the counting scan's matches): after an
               event's roots are recorded, cells of the flagged states
               drop across every ring slot — the node-level mirror of the
               counting kernels' ring clear (host emit-then-clear order).
    Returns (arena', roots (T, B, Q) int32) — roots are NULL where no hit.
    """
    T, B = class_ids.shape
    W = arena["cell"].shape[1]
    S = tables.num_states
    Q = tables.num_queries
    cap = arena["kind"].shape[1] - 1
    arange_w = jnp.arange(W)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (B,))

    def step(ar, xs):
        t, cls_t, gpos_t, hit_t = xs[:4]
        extra = list(xs[4:])
        j = start + t                                           # (B,)
        live = t < valid
        seed = (arange_w[None, :] == (j % W)[:, None])
        if expire is None:
            expire_t = (arange_w[None, :]
                        == ((j - epsilon - 1) % W)[:, None])
        else:
            expire_t = extra.pop(0)
        consume_t = extra.pop(0) if consume is not None else None
        clear = (seed | expire_t) & live[:, None]
        cell = jnp.where(clear[:, :, None], NULL, ar["cell"])

        # -- new_bottom(j) at the seed slot's initial state(s) --------------
        ar, base = _alloc(ar, live.astype(jnp.int32)[:, None])
        id_bot = base[:, 0]
        ar = _write(ar, base, live[:, None], kind=BOTTOM,
                    pos=gpos_t[:, None], maxs=gpos_t[:, None],
                    left=NULL, right=NULL)
        b_idx = jnp.arange(B)
        seed_slot = j % W
        for s0 in tables.init_states:
            old = cell[b_idx, seed_slot, s0]
            cell = cell.at[b_idx, seed_slot, s0].set(
                jnp.where(live, _ref(id_bot, cap), old))

        # -- transition: fold predecessor edges into each (slot, state) ----
        pk_all = jnp.moveaxis(tables.pred_idx[cls_t], 2, 0)     # (K, B, S)
        mk_all = jnp.moveaxis(tables.pred_mark[cls_t], 2, 0)
        vk_all = jnp.moveaxis(tables.pred_valid[cls_t], 2, 0)

        def fold_k(carry, xs_k):
            acc, ark = carry
            pk, mk, vk = xs_k                                   # (B, S)
            src = jnp.take_along_axis(
                cell, jnp.broadcast_to(jnp.clip(pk, 0, S - 1)[:, None, :],
                                       (B, W, S)), axis=2)      # (B, W, S)
            cvalid = vk[:, None, :] & (src != NULL) & live[:, None, None]
            m_ext = (cvalid & mk[:, None, :]).reshape(B, W * S)
            ark, base_e = _alloc(ark, m_ext.astype(jnp.int32))
            src_f = src.reshape(B, W * S)
            ark = _write(ark, base_e, m_ext, kind=OUTPUT,
                         pos=gpos_t[:, None],
                         maxs=_gather(ark["maxs"], src_f),
                         left=src_f, right=NULL)
            contrib = jnp.where(m_ext, _ref(base_e, cap), src_f)
            ark, acc = _union_fold(ark, acc, contrib,
                                   cvalid.reshape(B, W * S))
            return (acc, ark), None

        acc0 = jnp.full((B, W * S), NULL, jnp.int32)
        (acc, ar), _ = jax.lax.scan(fold_k, (acc0, ar),
                                    (pk_all, mk_all, vk_all))
        cell = jnp.where(live[:, None, None],
                         acc.reshape(B, W, S), ar["cell"])

        # -- roots at hit positions (Fig. 5(e) merge) ----------------------
        # same-slot final cells share a max-start → gadget fold ...
        def fold_s(carry, xs_s):
            slotacc, ars = carry
            cell_s, fin_s = xs_s                      # (B, W) / (Q,)
            cval = ((cell_s != NULL)[:, :, None] & fin_s[None, None, :]
                    & hit_t[:, None, :])
            contrib = jnp.broadcast_to(cell_s[:, :, None], (B, W, Q))
            ars, sa = _union_fold(ars, slotacc.reshape(B, W * Q),
                                  contrib.reshape(B, W * Q),
                                  cval.reshape(B, W * Q))
            return (sa.reshape(B, W, Q), ars), None

        slot0 = jnp.full((B, W, Q), NULL, jnp.int32)
        (slotacc, ar), _ = jax.lax.scan(
            fold_s, (slot0, ar),
            (jnp.moveaxis(cell, 2, 0), tables.finals_sq))

        # ... then slots chain right-wards in decreasing start order
        def fold_d(carry, d):
            root, ard = carry
            slot_d = (j - d) % W                                # (B,)
            m_node = jnp.take_along_axis(
                slotacc, jnp.broadcast_to(slot_d[:, None, None], (B, 1, Q)),
                axis=1)[:, 0, :]                                # (B, Q)
            vm = (m_node != NULL) & hit_t
            need = (vm & (root != NULL)).astype(jnp.int32)
            ard, base_c = _alloc(ard, need)
            ard = _write(ard, base_c, need > 0, kind=UNION, pos=NULL,
                         maxs=_gather(ard["maxs"], m_node),
                         left=m_node, right=root)
            root = jnp.where(vm, jnp.where(root != NULL,
                                           _ref(base_c, cap), m_node), root)
            return (root, ard), None

        root0 = jnp.full((B, Q), NULL, jnp.int32)
        (root, ar), _ = jax.lax.scan(
            fold_d, (root0, ar),
            jnp.arange(epsilon, -1, -1, dtype=jnp.int32))

        # CONSUME BY ANY: emitted roots keep their nodes; the *cells* of
        # consuming queries drop so no later match extends a consumed run.
        if consume_t is not None:
            cell = jnp.where(consume_t[:, None, :] & live[:, None, None],
                             NULL, cell)

        ar = dict(ar)
        ar["cell"] = cell
        return ar, jnp.where(hit_t, root, NULL)

    ts = jnp.arange(T, dtype=jnp.int32)
    hits = jnp.asarray(hits, bool)
    xs = (ts, class_ids, gpos, hits)
    if expire is not None:
        xs = xs + (jnp.asarray(expire, bool),)
    if consume is not None:
        xs = xs + (jnp.asarray(consume, bool),)
    arena, roots = jax.lax.scan(step, arena, xs)
    return arena, roots


# ---------------------------------------------------------------------------
# block-vectorized arena scan (DESIGN.md §8) — same contract as arena_scan
# ---------------------------------------------------------------------------


def _block_layout(tables: ArenaTables, W: int, epsilon: int, cap: int
                  ) -> "kref.ArenaBlockLayout":
    """Static slot layout for (tables, ring, capacity) — cached on tables."""
    cache = getattr(tables, "_lay_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(tables, "_lay_cache", cache)
    key = (W, epsilon, cap)
    lay = cache.get(key)
    if lay is None:
        lay = kref.arena_block_layout(
            W, tables.num_states, tables.max_indegree, tables.num_queries,
            epsilon, cap, tables.init_states, np.asarray(tables.finals_sq),
            np.asarray(tables.pred_idx), np.asarray(tables.pred_mark),
            np.asarray(tables.pred_valid))
        cache[key] = lay
    return lay


def _ptab(tables: ArenaTables) -> jnp.ndarray:
    """Packed (C, S, K, 3) predecessor tables — cached on tables."""
    pt = getattr(tables, "_ptab_cache", None)
    if pt is None:
        pt = kref.pack_pred_tables(tables.pred_idx, tables.pred_mark,
                                   tables.pred_valid)
        object.__setattr__(tables, "_ptab_cache", pt)
    return pt


#: bytes one chunk's ``(T, lanes, M)`` int32 record array may take; the
#: builder keeps about ten arrays of that size live, so wide batches run
#: in lane groups sized to this budget (:func:`arena_lane_group`)
ARENA_RECORD_BYTES = 1 << 28


def arena_lane_group(T: int, B: int, M: int) -> int:
    """Lanes the block builder processes at once: the largest divisor of
    ``B`` whose ``(T, lanes, M)`` record array fits
    :data:`ARENA_RECORD_BYTES` (at least one lane)."""
    per_lane = 4 * T * M
    best = 1
    for g in range(1, B + 1):
        if B % g == 0 and g * per_lane <= ARENA_RECORD_BYTES:
            best = g
    return best


def arena_scan_block(tables: ArenaTables, arena: dict,
                     class_ids: jnp.ndarray, gpos: jnp.ndarray,
                     start: jnp.ndarray, valid: jnp.ndarray,
                     hits: jnp.ndarray, *, epsilon: int, expire=None,
                     consume=None,
                     n_seg: int = 1) -> Tuple[dict, jnp.ndarray]:
    """Block-vectorized :func:`arena_scan` — same contract, ~1000× less
    per-event write traffic (DESIGN.md §8).

    The per-event fold above runs three traced inner folds and a store
    scatter chain per event; each masked scatter materializes a fresh copy
    of the ``(B, capacity)`` node store inside the scan, which is what made
    arena-on scans ~1000× slower than counting-only ones.  This path
    instead:

    1. runs ONE lean scan carrying only the per-cell attribute table
       (four ``(B, W, S)`` int32 arrays) — per event it folds the
       statically-tabulated predecessor edges through the union gadgets
       (unrolled over the fold depth K, the relevant final states and the
       chain axis — no traced inner scans) and emits fixed-layout node
       *records* on a virtual id space (``kernels.ref.arena_build_ref``,
       one XLA computation on every platform; root folds are skipped at
       runtime on hitless steps);
    2. assigns real node ids with ONE chunk-level exclusive cumsum of the
       record-validity mask (the bump allocator, batched) and translates
       every virtual reference in one vectorized pass — overflowers clamp
       into the sink; and
    3. lands the records with one batched store update per SoA field per
       chunk: node ids are *monotone* in slot order, so each store id
       binary-searches its source slot in the cumsum and gathers its
       record (a scatter would be serial per update on CPU and T·M/cap
       times wider than the ids that can land).  ``kind``/``pos``/
       ``max_start`` are never even emitted: they decode from the static
       slot layout and the closed-form slot-start table.

    ``n_seg > 1`` additionally splits the chunk into overlapping segments
    scanned as a batch (finite-memory replay, see
    :func:`repro.kernels.ref.segment_operands`) — shorter, wider scans;
    measured slower on CPU XLA (the step is bandwidth-bound there), kept
    as a knob for accelerator backends.

    Records are dense (``M`` slots per event and lane, ``M`` growing with
    ring × states), so when a chunk's ``(T, B, M)`` record array would
    pass :data:`ARENA_RECORD_BYTES` the lanes run in groups under
    ``lax.map`` (:func:`arena_lane_group`).

    The slot layout replays the reference fold's allocation order exactly,
    so non-overflowing lanes produce bit-identical node stores — asserted
    by tests/test_arena_block.py.

    ``expire`` (optional, (T, B, W) bool): precomputed time-window
    eviction masks — same contract as :func:`arena_scan` (DESIGN.md §9).
    They are closed-form in the absolute event index, so segmented
    execution consumes them as one more streamed operand.  ``consume`` (optional, (T, B, S) bool): CONSUME BY ANY
    clear masks — same contract as :func:`arena_scan`; clearing allocates
    nothing, so the record layout, the chunk-level cumsum and the decoded
    ``kind``/``pos``/``max_start`` are all untouched.
    """
    T, B = class_ids.shape
    W = arena["cell"].shape[1]
    cap = arena["kind"].shape[1] - 1
    G = arena_lane_group(T, B, _block_layout(tables, W, epsilon, cap).M)
    kw = dict(epsilon=epsilon, n_seg=n_seg)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (B,))
    if G == B:
        return _scan_block_lanes(tables, arena, class_ids, gpos, start,
                                 valid, hits, expire=expire,
                                 consume=consume, **kw)
    # lanes are independent (own cells and node store): run them in groups
    # so the (T, G, M) records stay within budget
    n = B // G

    def lanes_first(x):            # (B, ...) → (n, G, ...)
        return x.reshape((n, G) + x.shape[1:])

    def steps_first(x):            # (T, B, ...) → (n, T, G, ...)
        x = jnp.asarray(x)
        return jnp.moveaxis(x.reshape((T, n, G) + x.shape[2:]), 1, 0)

    xs = ({k: lanes_first(v) for k, v in arena.items()},
          steps_first(class_ids), steps_first(gpos), lanes_first(start),
          lanes_first(valid), steps_first(hits),
          None if expire is None else steps_first(expire),
          None if consume is None else steps_first(consume))

    def group(x):
        ar, cl, gp, st, va, hi, ex, co = x
        return _scan_block_lanes(tables, ar, cl, gp, st, va, hi, expire=ex,
                                 consume=co, **kw)

    arena_g, roots_g = jax.lax.map(group, xs)
    arena = {k: v.reshape((B,) + v.shape[2:]) for k, v in arena_g.items()}
    roots = jnp.moveaxis(roots_g, 0, 1).reshape((T, B) + roots_g.shape[3:])
    return arena, roots


def _scan_block_lanes(tables: ArenaTables, arena: dict, class_ids, gpos,
                      start, valid, hits, *, epsilon: int, expire, consume,
                      n_seg: int) -> Tuple[dict, jnp.ndarray]:
    """:func:`arena_scan_block` over one group of lanes."""
    T, B = class_ids.shape
    W = arena["cell"].shape[1]
    cap = arena["kind"].shape[1] - 1
    lay = _block_layout(tables, W, epsilon, cap)
    ptab = _ptab(tables)
    M = lay.M
    Q = lay.Q
    gpos = jnp.asarray(gpos, jnp.int32)

    # -- chunk-start cell attributes, gathered from the node store ---------
    cid0 = arena["cell"]
    occ = cid0 != NULL
    b3 = jnp.arange(B)[:, None, None]
    safe = jnp.clip(cid0, 0, cap)
    cells0 = (cid0,
              ((arena["kind"][b3, safe] == UNION) & occ).astype(jnp.int32),
              arena["left"][b3, safe], arena["right"][b3, safe])
    sstart0 = jnp.max(jnp.where(occ, arena["maxs"][b3, safe], NULL), axis=2)

    # -- 1+2. builder scan: cell-table recurrence + record emission --------
    cells_T, rec_valid, rec_left, rec_right, roots_v = \
        kref.arena_build_ref(
            cells0, class_ids, hits, start, valid, lay=lay, ptab=ptab,
            finals_sq=tables.finals_sq, n_seg=n_seg, expire=expire,
            consume=consume)

    # -- 3+4 run under one chunk-level allocation gate: a chunk with zero
    # allocations (every step dead — idle fleet engines, service tail
    # chunks) skips the cumsum, the translation and the store update at
    # runtime and returns the arena unchanged.  Any live step allocates at
    # least its bottom record, so the gate only ever skips chunks whose
    # cell table is bit-identically unchanged.
    def _translate(_):
        return _arena_translate_store(arena, lay, cells_T, rec_valid,
                                      rec_left, rec_right, roots_v, gpos,
                                      start, valid, sstart0, hits,
                                      T=T, B=B, W=W, cap=cap,
                                      num_states=tables.num_states)

    def _skip(_):
        return dict(arena), jnp.full((T, B, Q), NULL, jnp.int32)

    return jax.lax.cond(jnp.any(rec_valid > 0), _translate, _skip, None)


def _arena_translate_store(arena, lay, cells_T, rec_valid, rec_left,
                           rec_right, roots_v, gpos, start, valid, sstart0,
                           hits, *, T, B, W, cap, num_states):
    """Steps 3–4 of :func:`arena_scan_block`: bump allocation, virtual-id
    translation and the batched store update (hit-gated by the caller)."""
    M = lay.M
    Q = lay.Q
    # -- 3. bump allocation: one chunk-level cumsum over all T·M slots -----
    N = T * M
    need = jnp.moveaxis(rec_valid, 1, 0).reshape(B, N)
    csum = jnp.cumsum(need, axis=1)
    base = arena["ptr"][:, None] + (csum - need)               # (B, N)
    total = csum[:, -1]
    new_ptr = arena["ptr"] + total
    out = dict(arena)
    out["ovf"] = arena["ovf"] | (new_ptr > cap)
    out["ptr"] = jnp.minimum(new_ptr, cap)

    voff = lay.voffset

    def tr(v):                     # v: (B, n) int32 with virtual references
        g = jnp.take_along_axis(base, jnp.clip(v - voff, 0, N - 1), axis=1)
        return jnp.where(v >= voff, jnp.minimum(g, cap), v)

    def flat(r):                   # (T, B, n) → (B, T·n)
        return jnp.moveaxis(r, 1, 0).reshape(B, -1)

    # -- 4. batched store update: binary-search source slot, gather record -
    ids_rel = (jnp.arange(cap + 1, dtype=jnp.int32)[None, :]
               - arena["ptr"][:, None])                       # (B, cap+1)
    written = (ids_rel >= 0) & (ids_rel < total[:, None])
    src = jax.vmap(
        lambda c, q: jnp.searchsorted(c, q, side="right"))(
            csum, ids_rel).astype(jnp.int32)                  # (B, cap+1)
    src = jnp.clip(src, 0, N - 1)

    def at_src(rec_fl):            # (B, N) records → (B, cap+1) store image
        return jnp.take_along_axis(rec_fl, src, axis=1)

    # kind / pos / max_start decode from the slot layout: kind and the ring
    # slot per layout position are static; slot starts come from the
    # closed-form (T, B, W) table — none of the three is ever emitted.
    slot_m = src % M
    t_of = src // M
    kind_new = jnp.asarray(lay.kind_static())[slot_m]
    gpos_src = jnp.take_along_axis(jnp.moveaxis(gpos, 1, 0), t_of, axis=1)
    pos_new = jnp.where(jnp.asarray(lay.pos_is_event())[slot_m],
                        gpos_src, NULL)
    sstart_tr = kref.arena_slot_starts(sstart0, gpos, start, valid, W=W)
    d_m = jnp.asarray(lay.d_static())[slot_m]
    w_m = jnp.where(d_m >= 0,
                    (start[:, None] + t_of - d_m) % W,        # chain slots
                    jnp.asarray(lay.w_static())[slot_m])
    maxs_new = jnp.take_along_axis(
        jnp.moveaxis(sstart_tr, 1, 0).reshape(B, T * W),
        t_of * W + w_m, axis=1)
    maxs_new = jnp.where(kind_new == BOTTOM, gpos_src, maxs_new)
    for name, val in (("kind", kind_new), ("pos", pos_new),
                      ("maxs", maxs_new),
                      ("left", tr(at_src(flat(rec_left)))),
                      ("right", tr(at_src(flat(rec_right))))):
        out[name] = jnp.where(written, val, arena[name])
    out["cell"] = tr(cells_T[0].reshape(B, -1)).reshape(B, W, num_states)
    roots = jnp.moveaxis(tr(flat(roots_v)).reshape(B, T, Q), 0, 1)
    return out, jnp.where(jnp.asarray(hits, bool), roots, NULL)


# ---------------------------------------------------------------------------
# shared chunk step + one-shot driver
# ---------------------------------------------------------------------------


def window_expire_masks(window: "wkern.DeviceWindow", ts_ring0, event_ts,
                        start, valid) -> jnp.ndarray:
    """(T, B, W) bool time-eviction masks, in closed form (DESIGN.md §9).

    Seeding is position-driven in both window modes, so the per-slot start
    *timestamp* at every step decodes without a recurrence
    (:func:`repro.kernels.ref.arena_slot_starts` fed with timestamps):
    slot ``w`` at step ``t`` carries the timestamp of its last seed (or the
    carried chunk-start ring ``ts_ring0``), and expires when it falls
    below ``τ_t − size``.  The counting kernels carry the same ring in
    VMEM/scan state; both derivations see identical f32 values, so the
    eviction decisions agree bit-for-bit.
    """
    event_ts = jnp.asarray(event_ts, jnp.float32)
    slot_ts = kref.arena_slot_starts(ts_ring0, event_ts, start, valid,
                                     W=window.ring)
    return slot_ts < event_ts[:, :, None] - jnp.float32(window.size)


def run_arena_scan(atables: ArenaTables, arena: dict, trace, gpos, start,
                   valid, hits, *, epsilon: int, expire=None, consume=None,
                   arena_impl: str = "block"):
    """Dispatch one arena chunk to the selected implementation.

    ``arena_impl``: ``"block"`` (vectorized allocation + batched scatters,
    the default) or ``"fold"`` (the per-event reference fold, kept for
    parity testing — DESIGN.md §8).  ``expire``: precomputed time-window
    eviction masks, or None for count windows (DESIGN.md §9).
    ``consume``: precomputed CONSUME BY ANY clear masks ((T, B, S) bool),
    or None for non-consuming queries.
    """
    with jax.named_scope("arena"):
        check_arena_impl(arena_impl)
        if arena_impl == "fold":
            return arena_scan(atables, arena, trace, gpos, start, valid,
                              hits, epsilon=epsilon, expire=expire,
                              consume=consume)
        return arena_scan_block(atables, arena, trace, gpos, start, valid,
                                hits, epsilon=epsilon, expire=expire,
                                consume=consume)


def scan_chunk(atables: ArenaTables, arena: dict, attrs, state, *,
               specs, class_of, class_ind, m_all, finals_q, init_mask,
               window: "wkern.DeviceWindow", start, gbase, route,
               arena_impl: str = "block", event_ts=None, latest_q=None,
               consume_sq=None):
    """One chunk through the fused pipeline + arena at a common offset.

    The whole-batch case: every lane advances by the same T events from
    ring offset ``start``, with global positions ``gbase + t`` (PARTITION
    BY lanes have per-lane offsets and scattered positions — see
    ``PartitionedStreamingEngine._part_step_impl`` instead).  Shared by the
    streaming engine's arena step and the one-shot :func:`run_enumerate`.
    Time windows take the ``event_ts (T, B)`` operand; the same eviction
    masks gate the counting ring and the arena cells (DESIGN.md §9).
    ``latest_q``/``consume_sq`` are the compiled-semantics operands
    (LAST's latest-slot reduction / CONSUME BY ANY's state-clear rows —
    ``repro.core.query.resolve_semantics``): both feed the counting
    kernels, and ``consume_sq`` additionally derives the arena's
    per-step cell-clear masks from the emitted matches, so the node
    store mirrors the count ring's consumption exactly.  ``route`` is the
    caller's recorded scan :class:`~repro.kernels.ops.Route`.
    Returns ``(matches, state', arena', roots)``.
    """
    from ..kernels import ops
    ts_ring0 = state["ts"] if window.is_time else None
    matches, state, trace = ops.cer_pipeline(
        attrs, specs, class_of, class_ind, m_all, finals_q, state,
        init_mask=init_mask, window=window, event_ts=event_ts,
        start_pos=start, route=route, return_trace=True,
        latest_q=latest_q, consume_sq=consume_sq)
    # the arena half: its operands here, the builder in run_arena_scan
    with jax.named_scope("arena"):
        T, B = trace.shape
        gpos = jnp.broadcast_to(
            gbase + jnp.arange(T, dtype=jnp.int32)[:, None], (T, B))
        start_b = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
        valid_b = jnp.full((B,), T, jnp.int32)
        expire = (window_expire_masks(window, ts_ring0, event_ts, start_b,
                                      valid_b)
                  if window.is_time else None)
        # the arena runs on LIVE dims (Q queries, Ŝ states); the pipeline's
        # matches/operands may carry padded tails (fleet buckets pad query
        # slots and packed states) — padding is dead by construction, so
        # slicing is exact
        hits = (matches > 0.5)[..., :atables.num_queries]
        consume = (jnp.einsum(
            "tbq,qs->tbs", hits.astype(jnp.float32),
            jnp.asarray(consume_sq, jnp.float32)[:atables.num_queries,
                                                 :atables.num_states],
            precision=jax.lax.Precision.HIGHEST) > 0.5
            if consume_sq is not None else None)
    arena, roots = run_arena_scan(
        atables, arena, trace, gpos, start_b, valid_b, hits,
        epsilon=window.epsilon, expire=expire, consume=consume,
        arena_impl=arena_impl)
    return matches, state, arena, roots


def resolve_enum_strategy(engine, strategy):
    """Resolve ``run_enumerate``'s strategy arg against the engine's own
    compiled semantics.  Returns the *post-filter* strategy, or ``None``
    for native enumeration (the compiled tables already select).

    * ``None`` → native: strategy-compiled engines keep exactly the
      selected matches in the arena, plain-ALL engines keep everything
      (identical to the legacy ``strategy="ALL"`` default).
    * explicit strategy on a plain-ALL engine → legacy host post-filter.
    * explicit strategy on a natively-compiled engine → must match the
      engine's own (per-query) strategy; anything else would silently
      double-filter, so it raises.
    """
    if strategy is None:
        return None
    if not getattr(engine, "native_semantics", False):
        return strategy
    strats = getattr(engine, "strategies", ())
    if all(s == strategy for s in strats):
        return None                 # already compiled in — nothing to do
    raise ValueError(
        f"engine compiled native semantics {tuple(strats)!r}; cannot "
        f"post-filter its enumeration under {strategy!r} — construct the "
        "engine from a query with that strategy instead")


def take_latest_group(ces) -> List[ComplexEvent]:
    """First (latest-start) group of an arena enumeration, O(group).

    The arena root chains union nodes with strictly decreasing starts, so
    Algorithm 2's DFS yields all complex events of the latest start first,
    contiguously — a LAST query's native matches are exactly that group.
    Useful when the caller has no per-hit count to slice by (streaming
    roots record node ids only).
    """
    it = iter(ces)
    first = next(it, None)
    if first is None:
        return []
    out = [first]
    for ce in it:
        if int(ce.start) != int(first.start):
            break
        out.append(ce)
    return out


def run_enumerate(engine, streams, start_pos: int = 0,
                  arena_capacity: int = 1 << 15, strategy=None):
    """One-shot pipeline + arena + enumeration over pre-batched streams.

    ``engine`` is a constructed VectorEngine or MultiQueryEngine (anything
    with ``tables``/``encoder``/``arena_tables()``/``init_state``).  The
    predicate scan, counting scan and arena maintenance run as ONE jitted
    computation (cached on the engine); the host then fetches the arena and
    walks Algorithm 2 per hit.

    ``strategy=None`` (default) enumerates under each query's COMPILED
    semantics (:func:`resolve_enum_strategy`): the strategy-aware tables
    keep only the selected matches, so the walk is O(matches kept) — for
    LAST queries the DFS yields the latest-start group first and the
    latest-reduced count bounds the take, no host re-filter anywhere.
    Returns ``(counts (T, B, Q) int64, {(t, b, q): [ComplexEvent]})`` —
    single-query callers slice Q = 0.
    """
    from ..core.selection import apply_strategy
    post = resolve_enum_strategy(engine, strategy)
    attrs, event_ts = engine.encode_ts(streams, base_pos=int(start_pos))
    tbl = engine.tables
    finals = tbl.finals
    finals_q = finals if finals.ndim == 2 else finals[None, :]
    atables = engine.arena_tables()
    latest_q = getattr(tbl, "latest_q", None)
    consume_sq = getattr(tbl, "consume_sq", None)

    from .engine import plan_oneshot
    from ..kernels.ops import arena_route
    route = plan_oneshot(engine, attrs.shape, trace=True)
    engine.routes["arena"] = arena_route(
        atables, getattr(engine, "arena_impl", "block"))

    def step(attrs, state, arena, start, ts):
        # one-shot: absolute positions and ring offsets coincide
        matches, _, arena, roots = scan_chunk(
            atables, arena, attrs, state, specs=engine.encoder.specs,
            class_of=tbl.class_of, class_ind=tbl.class_ind,
            m_all=tbl.m_all, finals_q=finals_q, init_mask=tbl.init_mask,
            window=engine.window, start=start, gbase=start, route=route,
            arena_impl=getattr(engine, "arena_impl", "block"),
            event_ts=ts, latest_q=latest_q, consume_sq=consume_sq)
        return matches, arena, roots

    cache = getattr(engine, "_enum_jit", None)
    if cache is None:
        cache = engine._enum_jit = {}
    key = (getattr(engine, "arena_impl", "block"), route)
    jitted = cache.get(key)
    if jitted is None:
        jitted = cache[key] = jax.jit(step)
    T, B = attrs.shape[:2]
    state = engine.init_state(B)
    arena = init_arena(B, arena_capacity, engine.ring, atables.num_states)
    matches_f, arena, roots = jitted(attrs, state, arena,
                                     jnp.asarray(start_pos, jnp.int32),
                                     event_ts)
    counts = np.asarray(matches_f).astype(np.int64)
    roots_np = np.asarray(roots)
    latest_np = (np.asarray(latest_q) > 0.5) if latest_q is not None \
        else None
    snap = ArenaSnapshot(arena)
    tbq = list(zip(*np.nonzero(counts)))
    js = [int(start_pos) + int(t) for t, b, q in tbq]
    # LAST: the root chains starts in decreasing order, so the latest-start
    # group comes first; the latest-reduced count is exactly its size — cap
    # the frontier there (the vectorized islice, O(matches kept)).
    caps = ([int(counts[t, b, q]) if latest_np[q] else _NO_CAP
             for t, b, q in tbq] if latest_np is not None else None)
    batches = snap.enumerate_batch(
        [int(b) for t, b, q in tbq], [int(roots_np[t, b, q])
                                      for t, b, q in tbq],
        js, [j - engine.epsilon for j in js], caps=caps)
    out = {}
    for (t, b, q), ces in zip(tbq, batches):
        if post is not None:
            ces = apply_strategy(post, ces)
        out[(int(t), int(b), int(q))] = ces
    return counts, out


# ---------------------------------------------------------------------------
# host side: fetch + enumerate (Algorithm 2 over the fetched arrays)
# ---------------------------------------------------------------------------


class ArenaOverflow(RuntimeError):
    """A lane's bump pointer passed capacity; its nodes are unreliable."""


class ArenaSnapshot:
    """Host-fetched (numpy) copy of the device arena.

    Node ids are stable across feeds (the arena is append-only between
    resets), so roots recorded at earlier chunks stay enumerable from any
    later snapshot — fetch once, enumerate many.
    """

    def __init__(self, arena: dict):
        self.kind = np.asarray(arena["kind"])
        self.pos = np.asarray(arena["pos"])
        self.maxs = np.asarray(arena["maxs"])
        self.left = np.asarray(arena["left"])
        self.right = np.asarray(arena["right"])
        self.ptr = np.asarray(arena["ptr"])
        self.ovf = np.asarray(arena["ovf"])

    @classmethod
    def from_mirror(cls, bufs: dict, ptr: np.ndarray, ovf: np.ndarray
                    ) -> "ArenaSnapshot":
        """Snapshot over a mirror's persistent buffers (no copy).

        The node store is append-only, so sharing the buffers is safe: a
        later ``sync`` only writes rows at or beyond this snapshot's
        ``ptr`` watermark (or rewrites already-fetched rows with identical
        values) — earlier snapshots keep enumerating correctly.
        """
        snap = cls.__new__(cls)
        snap.kind = bufs["kind"]
        snap.pos = bufs["pos"]
        snap.maxs = bufs["maxs"]
        snap.left = bufs["left"]
        snap.right = bufs["right"]
        snap.ptr = ptr
        snap.ovf = ovf
        return snap

    @property
    def nodes_created(self) -> int:
        return int(self.ptr.sum())

    def enumerate(self, lane: int, root: int, end_pos: int,
                  threshold: Optional[int] = None,
                  steps: Optional[List[int]] = None
                  ) -> Iterator[ComplexEvent]:
        """Enumerate ``⟦root⟧(end_pos)`` with output-linear delay.

        ``threshold`` is the earliest admissible start (``None`` disables
        the prune — every node reachable from a live root is in-window by
        ring-eviction construction).  ``steps`` is an optional 1-element
        work counter incremented per node visit (paper-claims tests).
        """
        if bool(self.ovf[lane]):
            raise ArenaOverflow(
                f"lane {lane} overflowed its arena (capacity "
                f"{self.kind.shape[1] - 1}); raise arena_capacity or reset")
        yield from enumerate_arena(
            self.kind[lane], self.pos[lane], self.maxs[lane],
            self.left[lane], self.right[lane], int(root), int(end_pos),
            threshold, steps)

    def enumerate_batch(self, lanes: Sequence[int], roots: Sequence[int],
                        ends: Sequence[int],
                        thresholds: Optional[Sequence[int]] = None,
                        caps: Optional[Sequence[int]] = None,
                        steps: Optional[List[int]] = None,
                        oracle: bool = False
                        ) -> List[List[ComplexEvent]]:
        """Frontier-vectorized :meth:`enumerate` over many roots at once.

        One entry per root: its arena ``lane``, node id (< 0 = empty), end
        position, window threshold (None entries / omitted = no prune) and
        optional per-root match cap (the compiled-LAST ``islice``).  Returns
        one list per root, bit-identical — order included — to draining the
        per-root DFS (:func:`repro.core.tecs.enumerate_arena_batch`).

        ``oracle=True`` actually drains that per-root Python DFS instead of
        the vectorized walk — the Algorithm-2 reference path, kept for
        parity tests and the ``enum_vectorized_vs_dfs`` benchmark row.
        """
        lanes_a = np.asarray(lanes, dtype=np.int64)
        roots_a = np.asarray(roots, dtype=np.int64)
        live = roots_a >= 0
        if live.any():
            bad = np.unique(lanes_a[live & self.ovf[lanes_a]])
            if bad.size:
                raise ArenaOverflow(
                    f"lane {int(bad[0])} overflowed its arena (capacity "
                    f"{self.kind.shape[1] - 1}); raise arena_capacity or "
                    "reset")
        no_thr = -(1 << 62)
        if thresholds is None:
            thr = np.full(roots_a.shape, no_thr, dtype=np.int64)
        else:
            thr = np.asarray([no_thr if t is None else int(t)
                              for t in thresholds], dtype=np.int64)
        if oracle:
            out: List[List[ComplexEvent]] = []
            for i in range(len(roots_a)):
                if roots_a[i] < 0:
                    out.append([])
                    continue
                it = self.enumerate(
                    int(lanes_a[i]), int(roots_a[i]), int(ends[i]),
                    None if thr[i] == no_thr else int(thr[i]), steps)
                if caps is not None and caps[i] is not None:
                    it = itertools.islice(it, int(caps[i]))
                out.append(list(it))
            return out
        return enumerate_arena_batch(
            self.kind, self.pos, self.maxs, self.left, self.right,
            roots_a, lanes_a, ends, thr, caps=caps, steps=steps)


_NODE_FIELDS = ("kind", "pos", "maxs", "left", "right")


@jax.jit
def _mirror_meta(arena):
    return arena["ptr"], arena["ovf"]


def _mirror_slice(arena, lo, span):
    """Jitted ``[:, lo:lo+span)`` column slice of the five node fields.

    ``span`` is static (one XLA program per power-of-two bucket, ≤
    log2(capacity) of them per geometry); ``lo`` is a traced operand so
    the watermark never recompiles.
    """
    return tuple(jax.lax.dynamic_slice_in_dim(arena[name], lo, span, axis=1)
                 for name in _NODE_FIELDS)


_mirror_slice = jax.jit(_mirror_slice, static_argnums=(2,))


class ArenaMirror:
    """Persistent host mirror of a device arena with *delta* fetch.

    Bump-pointer node ids are monotone and the store is append-only
    between resets, so successive snapshots can only differ in rows
    ``[fetched : ptr)``.  :meth:`sync` pulls just that column span
    (rounded up to a power-of-two bucket so the jitted device slice
    compiles O(log capacity) times, not once per watermark) into
    persistent numpy buffers and returns an :class:`ArenaSnapshot` that
    shares them — the full ``(B, capacity)`` store crosses the device
    boundary exactly once per engine lifetime, however many times the
    host enumerates.

    Old snapshots stay valid across later syncs (append-only: later
    deltas touch rows at or beyond their ``ptr``).  Anything that
    rewrites existing rows — ``reset``, ``restore`` (packing or lane
    migration), regrow — must call :meth:`invalidate`; idle-lane
    eviction only clears *cell* rows, so the node store and the mirror
    stay valid.  Per-lane overflow needs no special casing: the sink
    row is only reachable from overflowed lanes, whose enumeration
    raises :class:`ArenaOverflow` before any node is read.
    """

    def __init__(self):
        self._bufs = None          # name -> (B, cap+1) int32, host
        self._fetched = 0          # columns FINAL in the mirror: min over
        self._shape = None         # lanes — laggards refetch (see sync)

    def invalidate(self) -> None:
        """Drop the watermark — the next sync refetches from row 0."""
        self._fetched = 0

    @property
    def fetched(self) -> int:
        return self._fetched

    def sync(self, arena: dict) -> ArenaSnapshot:
        """Fetch rows ``[fetched : max(ptr))`` and snapshot the mirror.

        The fetch is one column span shared by every lane, but lanes fill
        at different rates: a row between a lagging lane's ptr and the
        global max is UNWRITTEN on device now and may gain a real node
        later, so only rows below ``min(ptr)`` are final for all lanes.
        The watermark therefore advances to the min — the skew span
        ``[min(ptr) : max(ptr))`` is refetched next sync (append-only
        rows below each lane's own ptr rewrite with identical values, so
        earlier snapshots sharing the buffers stay correct).
        """
        # np.array (not asarray): device_get can be zero-copy on CPU and the
        # engine's next step donates the arena buffers out from under a view
        ptr, ovf = (np.array(x) for x in _mirror_meta(arena))
        shape = tuple(arena["kind"].shape)
        if self._bufs is None or self._shape != shape:
            self._bufs = {name: np.full(shape, NULL, np.int32)
                          for name in _NODE_FIELDS}
            self._shape = shape
            self._fetched = 0
        lo, hi = self._fetched, int(ptr.max(initial=0))
        if hi > lo:
            span = 1 << max(0, int(hi - lo - 1)).bit_length()
            span = min(span, shape[1])
            lo_q = max(0, hi - span)          # lo_q ≤ lo, lo_q + span ≥ hi
            cols = _mirror_slice(arena, lo_q, span)
            for name, col in zip(_NODE_FIELDS, cols):
                self._bufs[name][:, lo_q:lo_q + span] = np.asarray(col)
            self._fetched = int(ptr.min(initial=0))
        return ArenaSnapshot.from_mirror(self._bufs, ptr, ovf)


def check_invariants(snap: ArenaSnapshot, lane: int) -> None:
    """Assert the paper's tECS invariants on one lane's node store.

    * ids are topologically ordered (children < parent — bump discipline);
    * unions are time-ordered: ``max(left) ≥ max(right)``, node max =
      ``max(left)``;
    * output-depth ≤ 3 everywhere (3-boundedness, via the safe-node
      gadgets);
    * bottoms/outputs carry positions; unions don't.
    """
    n = int(snap.ptr[lane])
    kind = snap.kind[lane]
    pos, maxs = snap.pos[lane], snap.maxs[lane]
    left, right = snap.left[lane], snap.right[lane]
    odepth = np.zeros(n, np.int64)
    for i in range(n):
        k = kind[i]
        assert k in (BOTTOM, OUTPUT, UNION), (lane, i, k)
        if k == BOTTOM:
            assert left[i] == NULL and right[i] == NULL, (lane, i)
            assert pos[i] == maxs[i] >= 0, (lane, i)
        elif k == OUTPUT:
            assert 0 <= left[i] < i, (lane, i, left[i])
            assert maxs[i] == maxs[left[i]], (lane, i)
            odepth[i] = 0
        else:
            li, ri = int(left[i]), int(right[i])
            assert 0 <= li < i and 0 <= ri < i, (lane, i, li, ri)
            assert pos[i] == NULL, (lane, i)
            assert maxs[li] >= maxs[ri], (lane, i, maxs[li], maxs[ri])
            assert maxs[i] == maxs[li], (lane, i)
            odepth[i] = 1 + odepth[li]
            assert odepth[i] <= 3, (lane, i, odepth[i])
