"""Multi-query packed evaluation (beyond-paper optimization, §Perf #3).

The MXU consumes 128×128 tiles; a small automaton (S ≈ 8–32 det states)
wastes most lanes after padding.  Production CER deployments run *many*
queries over the same stream (the paper benchmarks them one at a time).
We pack q queries into one scan:

* all queries share one AtomRegistry → one bit-vector per event → one
  *combined* symbol-class table (classes = distinct joint behaviour);
* the packed transition matrix is block-diagonal,
  ``M̂[c] = diag(M₁[c], …, M_q[c])`` with Ŝ = Σ S_i ≤ 128 per pack;
* one (B, W, Ŝ)·(Ŝ, Ŝ) scan evaluates every query; per-query match counts
  come from per-query final-state masks.

Runs/counts are exact per query (blocks don't interact).  Speed-up ≈ the
lane-fill ratio: q queries of S=16 in one 128-wide pack ≈ 8× fewer MXU ops
than q padded scans — measured in benchmarks/perf_cer.py.

The packing itself is a first-class :class:`Packing` descriptor
(DESIGN.md §11): per-query state offsets/sizes, the joint-class tables, and
optional *dead padding* of every query-dependent dimension (states, query
slots, classes, predicate bits) up to bucket sizes.  Padded states receive
no transitions, no seeds, and no finals mass — they are provably dead
(:func:`check_packing_invariants`) — so engines built from two packings of
the same bucket geometry share compiled executables.  That is what the
dynamic :class:`repro.runtime.fleet.QueryFleet` builds on: hot add/remove
of queries re-*packs* (host work) without re-*compiling* (device work).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax.numpy as jnp

from ..core.cea import compile_cel
from ..core.predicates import AtomRegistry
from ..core.query import CompiledQuery, compile_query, resolve_semantics
from ..kernels import ops
from ..kernels import window as wkern
from .encoder import EventEncoder
from .symbolic import SymbolicCEA, compile_symbolic

#: a padding target: an explicit size, or a policy mapping the live size to
#: the padded size (the fleet passes power-of-two bucket policies)
PadSpec = Optional[Union[int, Callable[[int], int]]]


@dataclass
class PackedTables:
    m_all: jnp.ndarray          # (C_pad, Ŝ_pad, Ŝ_pad)
    finals: jnp.ndarray         # (Q_pad, Ŝ_pad) one mask row per query slot
    class_of: jnp.ndarray       # (2^k_pad,)
    class_ind: jnp.ndarray      # (≥2^k_pad, C_pad) one-hot (fused path)
    init_mask: jnp.ndarray      # (Ŝ_pad,) 1.0 at each query's initial state
    offsets: List[int]          # block start per query
    sizes: List[int]
    reps: np.ndarray            # (C,) representative bit-vector per class
    # compiled-semantics operands (resolve_semantics): per-query LAST flag
    # and CONSUME BY ANY state-clear rows over the query's own block.
    # None when every packed query is trivial — keeps plain packs'
    # compiled graphs and fingerprints bit-identical to the old format.
    latest_q: Optional[jnp.ndarray] = None    # (Q_pad,) f32 | None
    consume_sq: Optional[jnp.ndarray] = None  # (Q_pad, Ŝ_pad) f32 | None


class PackingInvariantError(ValueError):
    """A packing violates the dead-padding / block-diagonal contract."""


@dataclass
class Packing:
    """First-class descriptor of a packed multi-query automaton.

    Everything an engine (or the fleet's migration path) needs to interpret
    a block-diagonal state space: which query owns which state range
    (``offsets``/``sizes`` — the de-pack map), the joint-class tables, and
    the padded *bucket* dimensions the device arrays were allocated at.
    ``qids`` are caller-chosen stable identifiers — state migration between
    two packings matches queries by qid, not by slot position.
    """
    qids: Tuple[str, ...]
    queries: Tuple[str, ...]             # CEQL text, aligned with qids
    compiled: List[CompiledQuery]
    symbolics: List[SymbolicCEA]
    encoder: EventEncoder
    tables: PackedTables
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    num_states: int                      # live Ŝ = Σ sizes
    padded_states: int
    num_queries: int
    padded_queries: int
    num_classes: int                     # live joint classes C
    padded_classes: int
    num_bits: int                        # k (shared registry width)
    padded_bits: int
    strategies: Tuple[str, ...] = ()     # per-query SELECT strategy
    consumes: Tuple[bool, ...] = ()      # per-query CONSUME BY ANY flag
    _fingerprint: Optional[str] = field(default=None, repr=False)

    # -- de-pack maps ---------------------------------------------------
    def slot_of(self, qid: str) -> int:
        return self.qids.index(qid)

    def state_range(self, slot: int) -> Tuple[int, int]:
        """``[start, end)`` packed-state range owned by query ``slot``."""
        return self.offsets[slot], self.offsets[slot] + self.sizes[slot]

    def query_of_state(self) -> np.ndarray:
        """(Ŝ_pad,) int32 de-pack map: owning query slot, -1 for padding."""
        q = np.full(self.padded_states, -1, np.int32)
        for qi, (off, sz) in enumerate(zip(self.offsets, self.sizes)):
            q[off:off + sz] = qi
        return q

    # -- manifests ------------------------------------------------------
    def spec(self) -> dict:
        """JSON-able packing spec recorded in snapshot manifests; the
        repack-aware restore path migrates state between two specs."""
        return {
            "qids": list(self.qids),
            "offsets": list(map(int, self.offsets)),
            "sizes": list(map(int, self.sizes)),
            "num_states": int(self.num_states),
            "padded_states": int(self.padded_states),
            "num_queries": int(self.num_queries),
            "padded_queries": int(self.padded_queries),
            "strategies": list(self.strategies),
            "consumes": [bool(c) for c in self.consumes],
        }

    def _hash_tables(self, h) -> None:
        enc = self.encoder
        h.update(repr((enc.attrs, enc.specs,
                       sorted((a, sorted(v.items()))
                              for a, v in enc.vocab.items()))).encode())
        t = self.tables
        for arr in (t.m_all, t.finals, t.class_of, t.init_mask):
            a = np.asarray(arr)
            h.update(str((a.shape, str(a.dtype))).encode())
            h.update(a.tobytes())
        # semantic operands: LAST shares MAX's m_all and consuming queries
        # share the non-consuming tables, so the base digest alone cannot
        # tell them apart.  Hash them only when present — trivial packs
        # keep their pre-semantics fingerprints (and compiled-step reuse).
        if t.latest_q is not None or t.consume_sq is not None:
            h.update(b"semantics")
            for arr in (t.latest_q, t.consume_sq):
                if arr is None:
                    h.update(b"none")
                else:
                    a = np.asarray(arr)
                    h.update(str((a.shape, str(a.dtype))).encode())
                    h.update(a.tobytes())

    @property
    def table_fingerprint(self) -> str:
        """Digest of the packed automaton + encoder layout ONLY (no qids).

        Two packings with equal table fingerprints produce bit-identical
        device behaviour regardless of what the queries are *named* — the
        fleet keys arena-step reuse on this, so removing a query and
        re-adding it under a fresh qid still reuses the compiled step.
        """
        h = hashlib.sha256()
        self._hash_tables(h)
        return h.hexdigest()

    @property
    def fingerprint(self) -> str:
        """Deterministic digest of the packed automaton + encoder layout
        + query identities.

        Extends :attr:`table_fingerprint` with ``qids``: equal fingerprints
        mean the packed state is *interchangeable* (same device behaviour
        AND the same membership interpretation) — crash-restore
        verification keys on it.
        """
        if self._fingerprint is None:
            h = hashlib.sha256()
            h.update(repr(self.qids).encode())
            self._hash_tables(h)
            object.__setattr__(self, "_fingerprint", h.hexdigest())
        return self._fingerprint


def _resolve_pad(pad: PadSpec, live: int, what: str) -> int:
    if pad is None:
        return live
    n = pad(live) if callable(pad) else int(pad)
    if n < live:
        raise ValueError(f"pad_{what}={n} is below the live size {live}")
    return n


def build_packing(queries: Sequence[str], *,
                  qids: Optional[Sequence[str]] = None,
                  pad_states: PadSpec = None,
                  pad_queries: PadSpec = None,
                  pad_classes: PadSpec = None,
                  pad_bits: PadSpec = None) -> Packing:
    """Compile ``queries`` against one shared registry into a :class:`Packing`.

    ``pad_*`` grow the corresponding device-array dimension to a bucket
    size (an int, or a policy callable ``live → padded``).  All padding is
    *dead*: padded states get no transitions/seeds/finals, padded query
    slots have all-zero finals rows, padded classes have all-zero
    transition matrices, and padded predicate bits can never be set (the
    engines' padded spec rows evaluate to constant-false) — verified by
    :func:`check_packing_invariants`.
    """
    queries = list(queries)
    if not queries:
        raise ValueError("a packing needs at least one query")
    if qids is None:
        qids = tuple(f"q{i}" for i in range(len(queries)))
    qids = tuple(qids)
    if len(qids) != len(queries) or len(set(qids)) != len(qids):
        raise ValueError("qids must be unique and aligned with queries")

    registry = AtomRegistry()   # SHARED across queries
    compiled = [compile_query(q, registry) for q in queries]
    encoder = EventEncoder.from_registry(registry)
    # resolve every query's strategy + CONSUME clause up front — an
    # unsupported combination raises HERE, before any device table exists,
    # so a pack can never silently evaluate a member under ANY semantics
    sems = [resolve_semantics(c.query) for c in compiled]
    symbolics = [compile_symbolic(c.cea, strategy=s.construction)
                 for c, s in zip(compiled, sems)]

    # NOTE: every symbolic shares num_bits (shared registry), but each
    # computed its own class partition; combine into joint classes.
    k = symbolics[0].num_bits
    n_vec = 1 << k
    joint = np.stack([s.class_of for s in symbolics])        # (Q, 2^k)
    _, class_of = np.unique(joint, axis=1, return_inverse=True)
    n_classes = int(class_of.max()) + 1
    # representative bitvec per joint class
    reps = np.zeros(n_classes, dtype=np.int64)
    for v in range(n_vec - 1, -1, -1):
        reps[class_of[v]] = v

    sizes = [s.num_states for s in symbolics]
    S_hat = sum(sizes)
    offsets = list(np.cumsum([0] + sizes[:-1]))

    kp = _resolve_pad(pad_bits, k, "bits")
    Sp = _resolve_pad(pad_states, S_hat, "states")
    Qp = _resolve_pad(pad_queries, len(sizes), "queries")
    Cp = _resolve_pad(pad_classes, n_classes, "classes")

    class_of_p = np.zeros(1 << kp, np.int32)
    class_of_p[:n_vec] = class_of.astype(np.int32)

    m_all = np.zeros((Cp, Sp, Sp), np.float32)
    finals = np.zeros((Qp, Sp), np.float32)
    init_mask = np.zeros((Sp,), np.float32)
    latest = np.zeros((Qp,), np.float32)
    consume = np.zeros((Qp, Sp), np.float32)
    for qi, sym in enumerate(symbolics):
        off = offsets[qi]
        Mq = sym.transition_matrices()                       # (Cq, S, S)
        for c in range(n_classes):
            cq = sym.class_of[reps[c]]
            m_all[c, off:off + sizes[qi], off:off + sizes[qi]] = Mq[cq]
        finals[qi, off:off + sizes[qi]] = sym.finals.astype(np.float32)
        init_mask[off + sym.initial] = 1.0
        if sems[qi].latest:
            latest[qi] = 1.0
        if sems[qi].consume:
            # clear rows span the query's OWN block only — a consuming
            # query never disturbs its pack-mates' ring states
            consume[qi, off:off + sizes[qi]] = 1.0

    tables = PackedTables(
        m_all=jnp.asarray(m_all), finals=jnp.asarray(finals),
        class_of=jnp.asarray(class_of_p),
        class_ind=ops.class_indicator(class_of_p, Cp),
        init_mask=jnp.asarray(init_mask),
        offsets=[int(o) for o in offsets], sizes=list(sizes), reps=reps,
        latest_q=jnp.asarray(latest) if latest.any() else None,
        consume_sq=jnp.asarray(consume) if consume.any() else None)
    return Packing(
        qids=qids, queries=tuple(queries), compiled=compiled,
        symbolics=symbolics, encoder=encoder, tables=tables,
        offsets=tuple(int(o) for o in offsets), sizes=tuple(sizes),
        num_states=S_hat, padded_states=Sp,
        num_queries=len(sizes), padded_queries=Qp,
        num_classes=n_classes, padded_classes=Cp,
        num_bits=k, padded_bits=kp,
        strategies=tuple(c.query.strategy for c in compiled),
        consumes=tuple(bool(c.query.consume_on_match) for c in compiled))


def check_packing_invariants(packing: Packing) -> None:
    """Verify the dead-padding / block-diagonal contract (DESIGN.md §11).

    Raises :class:`PackingInvariantError` when any of these fail:

    1. **Padded dimensions are dead** — no transitions into/out of states
       beyond ``num_states``, no init seeding there, no finals mass on
       padded states/query slots, all-zero matrices for padded classes,
       and padded ``class_of`` entries map to class 0 (unreachable: padded
       predicate bits are constant-false).
    2. **De-pack maps partition Ŝ** — the per-query ``[offset, offset+size)``
       ranges tile ``[0, num_states)`` exactly, without gaps or overlaps.
    3. **Joint classes are consistent with each query's own classifier** —
       for every bit-vector ``v`` and every query, ``v`` behaves exactly
       like the representative of its joint class, and the block of
       ``m_all`` owned by the query equals that query's own transition
       matrix for the class.

    The fleet runs this on every repack; it is cheap (host numpy over
    small tables) relative to query compilation.
    """
    t = packing.tables
    m = np.asarray(t.m_all)
    fin = np.asarray(t.finals)
    im = np.asarray(t.init_mask)
    cof = np.asarray(t.class_of)
    S, Sp = packing.num_states, packing.padded_states
    Q, Qp = packing.num_queries, packing.padded_queries
    C, Cp = packing.num_classes, packing.padded_classes
    n_vec = 1 << packing.num_bits

    def fail(msg: str):
        raise PackingInvariantError(f"packing invariant violated: {msg}")

    if m.shape != (Cp, Sp, Sp) or fin.shape != (Qp, Sp) or im.shape != (Sp,):
        fail(f"table shapes {m.shape}/{fin.shape}/{im.shape} do not match "
             f"the declared geometry (C_pad={Cp}, S_pad={Sp}, Q_pad={Qp})")
    # 1. dead padding
    if m[:, S:, :].any() or m[:, :, S:].any():
        fail("padded states have transitions (rows/cols beyond Ŝ not zero)")
    if m[C:].any():
        fail("padded classes have non-zero transition matrices")
    if im[S:].any():
        fail("padded states are seeded by init_mask")
    if fin[:, S:].any():
        fail("padded states carry finals mass")
    if fin[Q:].any():
        fail("padded query slots carry finals mass")
    if cof[n_vec:].any():
        fail("padded class_of entries must map to class 0")
    if cof[:n_vec].min() < 0 or cof[:n_vec].max() >= C:
        fail("class_of values outside [0, num_classes)")
    # 2. de-pack maps partition [0, Ŝ)
    cursor = 0
    for qi, (off, sz) in enumerate(zip(packing.offsets, packing.sizes)):
        if off != cursor:
            fail(f"query block {qi} starts at {off}, expected {cursor} — "
                 "offsets must tile Ŝ contiguously")
        if sz != packing.symbolics[qi].num_states:
            fail(f"query block {qi} size {sz} != its automaton's "
                 f"{packing.symbolics[qi].num_states} states")
        cursor += sz
    if cursor != S:
        fail(f"blocks cover {cursor} states, packing declares Ŝ={S}")
    if im[:S].sum() != Q:
        fail("init_mask must seed exactly one state per live query")
    # 3. joint classes consistent with each query's own classifier
    reps = t.reps
    for qi, sym in enumerate(packing.symbolics):
        own = sym.class_of                              # (2^k,) per-query
        if not np.array_equal(own[:n_vec],
                              own[reps[cof[:n_vec].astype(np.int64)]]):
            fail(f"query {qi}: some bit-vector disagrees with its joint "
                 "class representative under the query's own classifier")
        off, sz = packing.offsets[qi], packing.sizes[qi]
        Mq = sym.transition_matrices()
        for c in range(C):
            cq = int(own[reps[c]])
            if not np.array_equal(m[c, off:off + sz, off:off + sz], Mq[cq]):
                fail(f"query {qi}: m_all block for joint class {c} != the "
                     f"query's own matrix for its class {cq}")
        if not np.array_equal(fin[qi, off:off + sz],
                              sym.finals.astype(np.float32)):
            fail(f"query {qi}: finals row disagrees with its automaton")
        if im[off + sym.initial] != 1.0:
            fail(f"query {qi}: initial state not seeded")
    # 4. semantic operands agree with the declared per-query semantics
    strategies = packing.strategies or ("ALL",) * Q
    consumes = packing.consumes or (False,) * Q
    want_latest = [qi for qi in range(Q) if strategies[qi] == "LAST"]
    if t.latest_q is None:
        if want_latest:
            fail(f"LAST queries {want_latest} but no latest_q operand — "
                 "their counts would come out under MAX semantics")
    else:
        la = np.asarray(t.latest_q)
        if la.shape != (Qp,):
            fail(f"latest_q shape {la.shape} != (Q_pad={Qp},)")
        exp = np.zeros(Qp, np.float32)
        exp[want_latest] = 1.0
        if not np.array_equal(la, exp):
            fail("latest_q flags disagree with the per-query strategies")
    want_consume = [qi for qi in range(Q) if consumes[qi]]
    if t.consume_sq is None:
        if want_consume:
            fail(f"CONSUME BY ANY queries {want_consume} but no consume_sq "
                 "operand — their matches would never clear the ring")
    else:
        co = np.asarray(t.consume_sq)
        if co.shape != (Qp, Sp):
            fail(f"consume_sq shape {co.shape} != (Q_pad={Qp}, S_pad={Sp})")
        exp = np.zeros((Qp, Sp), np.float32)
        for qi in want_consume:
            off, sz = packing.offsets[qi], packing.sizes[qi]
            exp[qi, off:off + sz] = 1.0
        if not np.array_equal(co, exp):
            fail("consume_sq rows must cover exactly each consuming "
                 "query's own state block")


def resolve_query_window(spec, *, epsilon: Optional[int] = None,
                         max_window_events: Optional[int] = None
                         ) -> "wkern.DeviceWindow":
    """Resolve one query's window with fleet-style *default* kwargs.

    :func:`repro.kernels.window.resolve_window` treats ``epsilon=`` /
    ``max_window_events=`` as authoritative and raises when they contradict
    the query's own WITHIN clause.  The fleet (and :meth:`MultiQueryEngine.
    from_packing`) instead treats them as defaults: ``epsilon`` applies
    only to clause-free queries, ``max_window_events`` only to time
    windows — each query's own clause always wins.
    """
    import warnings as _w
    kind = getattr(spec, "kind", "none") if spec is not None else "none"
    with _w.catch_warnings():
        # the clause-free shim warns per resolution; a fleet repack would
        # repeat it on every churn op — once per process is plenty
        _w.filterwarnings("ignore",
                          message=".*epsilon= for a query without.*")
        return wkern.resolve_window(
            spec,
            epsilon=epsilon if kind == "none" else None,
            max_window_events=(max_window_events if kind == "time"
                               else None))


class MultiQueryEngine:
    """Evaluate several CEQL queries over the same streams in one scan."""

    def __init__(self, queries: Sequence[str],
                 epsilon: Optional[int] = None,
                 use_pallas: bool = True, b_tile: int = 8,
                 impl: Optional[str] = None, arena_impl: str = "block",
                 max_window_events: Optional[int] = None):
        self._init_from_packing(
            build_packing(queries), epsilon=epsilon, use_pallas=use_pallas,
            b_tile=b_tile, impl=impl, arena_impl=arena_impl,
            max_window_events=max_window_events, strict_windows=True)

    @classmethod
    def from_packing(cls, packing: Packing,
                     epsilon: Optional[int] = None,
                     use_pallas: bool = True, b_tile: int = 8,
                     impl: Optional[str] = None, arena_impl: str = "block",
                     max_window_events: Optional[int] = None
                     ) -> "MultiQueryEngine":
        """Build an engine over a prebuilt (possibly padded) packing.

        Window compatibility is checked on the *resolved*
        :class:`~repro.kernels.window.DeviceWindow` (two syntactically
        different WITHIN clauses that resolve identically may pack) — the
        fleet routes queries into buckets by resolved window, then builds
        each bucket's engine through here.
        """
        self = cls.__new__(cls)
        self._init_from_packing(
            packing, epsilon=epsilon, use_pallas=use_pallas, b_tile=b_tile,
            impl=impl, arena_impl=arena_impl,
            max_window_events=max_window_events, strict_windows=False)
        return self

    def _init_from_packing(self, packing: Packing, *, epsilon, use_pallas,
                           b_tile, impl, arena_impl, max_window_events,
                           strict_windows: bool):
        self.packing = packing
        self.compiled = list(packing.compiled)
        self.encoder = packing.encoder
        self.symbolics = list(packing.symbolics)
        # one scan = one ring = one window: every packed query must declare
        # the same WITHIN clause (or none, falling back to the epsilon shim)
        specs = [c.query.window for c in self.compiled]
        if strict_windows:
            keys = {(w.kind, w.size, w.time_attr) for w in specs}
            if len(keys) > 1:
                raise ValueError(
                    "packed queries share one scan and therefore one "
                    f"window; got {len(keys)} distinct WITHIN clauses: "
                    f"{sorted(keys, key=repr)} — to mix windows, use "
                    "repro.runtime.fleet.QueryFleet, which routes queries "
                    "into per-window buckets instead of one pack")
            self.window = wkern.resolve_window(
                specs[0], epsilon=epsilon,
                max_window_events=max_window_events)
        else:
            windows = {resolve_query_window(
                s, epsilon=epsilon, max_window_events=max_window_events)
                for s in specs}
            if len(windows) > 1:
                raise ValueError(
                    "packed queries share one scan and therefore one "
                    f"window; the packing resolves {len(windows)} distinct "
                    "device windows — route mixed-window queries through "
                    "repro.runtime.fleet.QueryFleet's per-window buckets")
            self.window = windows.pop()
        self.epsilon = self.window.epsilon
        self.ring = self.window.ring
        self.use_pallas = use_pallas
        self.b_tile = b_tile
        self.impl = impl if impl is not None else (
            "fused" if use_pallas else "ref")
        from . import tecs_arena
        self.arena_impl = tecs_arena.check_arena_impl(arena_impl)
        #: stage → :class:`~repro.kernels.ops.Route` of the last call
        self.routes = {}
        self.tables = packing.tables
        sems = [c.semantics for c in self.compiled]
        self.strategies = tuple(c.query.strategy for c in self.compiled)
        self.consumes = tuple(
            bool(c.query.consume_on_match) for c in self.compiled)
        self.native_semantics = any(
            s.construction != "ALL" or s.latest or s.consume for s in sems)

    # ------------------------------------------------------------------
    @property
    def packed_states(self) -> int:
        return int(self.tables.m_all.shape[1])

    def init_state(self, batch: int):
        return wkern.init_state(self.window, batch, self.packed_states)

    def classify(self, attrs: jnp.ndarray) -> jnp.ndarray:
        T, B, A = attrs.shape
        bits = ops.bitvector(attrs.reshape(T * B, A), self.encoder.specs,
                             use_pallas=self.use_pallas)
        return self.tables.class_of[bits].reshape(T, B)

    def scan(self, class_ids: jnp.ndarray, state: jnp.ndarray,
             start_pos: int = 0):
        """→ (matches (T, B, Q), state').

        The packed scan seeds ALL queries' initial states each step (the
        kernel seeds one index; we pass a multi-hot init via state injection:
        cea_scan's init seeding uses a single init_state index, so we run it
        with the joint trick: block-diag M with a virtual shared start is not
        expressible — instead we seed by index per query via the generalized
        path below).  Count windows only; time windows evaluate through
        :meth:`pipeline` (DESIGN.md §9).
        """
        wkern.require_count_scan(self.window)
        if self.tables.latest_q is not None or \
                self.tables.consume_sq is not None:
            raise ValueError(
                "scan() cannot honor LAST / CONSUME BY ANY semantics "
                f"(packed strategies {self.strategies!r}); use pipeline()")
        # generalized multi-hot seeding: fold the per-query inits into the
        # scan by replacing the kernel's one-hot seed with init_mask — the
        # XLA path supports it directly; the Pallas kernel is invoked with
        # init_state=-1 and an extra mask (see kernels/ops.cea_scan_multi).
        return ops.cea_scan_multi(
            class_ids, self.tables.m_all, self.tables.finals,
            state, init_mask=self.tables.init_mask, epsilon=self.epsilon,
            start_pos=start_pos, use_pallas=self.use_pallas,
            b_tile=self.b_tile)

    def pipeline(self, attrs, state, start_pos=0, event_ts=None):
        """Single-dispatch fused path: (T, B, A) → (matches (T, B, Q), st')."""
        from .engine import plan_oneshot
        t = self.tables
        return ops.cer_pipeline(
            attrs, self.encoder.specs, t.class_of, t.class_ind, t.m_all,
            t.finals, state, init_mask=t.init_mask, window=self.window,
            event_ts=event_ts, start_pos=start_pos,
            latest_q=t.latest_q, consume_sq=t.consume_sq,
            route=plan_oneshot(self, attrs.shape, start_pos))

    def encode_ts(self, streams, base_pos: Optional[int] = 0):
        """(attrs, event_ts | None) per the window — see VectorEngine."""
        from .engine import encode_windowed
        return encode_windowed(self.encoder, self.window, streams,
                               base_pos=base_pos)

    def run(self, streams, state=None, start_pos=0):
        from .engine import _fallback_base
        attrs, ts = self.encode_ts(
            streams, base_pos=_fallback_base(self.window, start_pos))
        if state is None:
            state = self.init_state(attrs.shape[1])
        matches, state = self.pipeline(attrs, state, start_pos=start_pos,
                                       event_ts=ts)
        return np.asarray(matches).astype(np.int64), state

    # ------------------------------------------------------------------
    # device tECS arena over the packed automaton (DESIGN.md §7)
    # ------------------------------------------------------------------
    def arena_tables(self):
        """Predecessor tables of the block-diagonal packed det CEA."""
        tbl = getattr(self, "_arena_tables", None)
        if tbl is None:
            from . import tecs_arena
            tbl = tecs_arena.tables_from_packed(
                self.symbolics, self.tables.offsets,
                np.asarray(self.tables.class_of), self.tables.reps)
            self._arena_tables = tbl
        return tbl

    def run_enumerate(self, streams, start_pos: int = 0,
                      arena_capacity: int = 1 << 15,
                      strategy: Optional[str] = None):
        """Packed-query enumeration from the device arena (no event replay).

        ``strategy=None`` (default) enumerates each query under its OWN
        compiled semantics — packs may mix strategies per query; an
        explicit strategy is only accepted on all-trivial packs (legacy
        post-filter) or when it matches every member's strategy.

        Returns ``(counts (T, B, Q) int64, matches)`` with ``matches``
        mapping each hit ``(t, b, q)`` to its complex events — the shared
        driver :func:`repro.vector.tecs_arena.run_enumerate` verbatim.
        """
        from . import tecs_arena
        return tecs_arena.run_enumerate(
            self, streams, start_pos=start_pos,
            arena_capacity=arena_capacity, strategy=strategy)
