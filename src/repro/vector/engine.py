"""Device (TPU-native) CER engine — recognition, counting, and tECS arena.

The vector engine runs Algorithm 1 on device (DESIGN.md §3): per stream
position it computes the exact number of complex events closing there
(``|⟦A⟧ε_j(S)|``) plus a hit bitmap, using the windowed counting-semiring
scan.  :meth:`VectorEngine.run_enumerate` additionally maintains the tECS
*arena* (DESIGN.md §7) in the same compiled computation and enumerates the
actual complex events from the fetched node store with output-linear delay
— no host event replay (deviation D1, narrowed).

Execution is routed through :func:`repro.kernels.ops.cer_pipeline`
(``impl`` ∈ fused / unfused / ref): the default fused path evaluates
predicates, class folding, and the semiring scan in one dispatch.  For true
streaming (fixed-size chunks, donated state, compile-once) use
:class:`repro.vector.streaming.StreamingVectorEngine`.

Batching = partition-by: the B axis carries independent substreams.  For
*pre-partitioned* inputs feed B streams directly; for a raw interleaved
stream, :meth:`VectorEngine.partitioned_streaming` builds the device-native
PARTITION BY runtime (`vector/partitioned.py`) that hash-routes events to
lanes on device and keeps per-lane substream positions.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from ..core.events import ComplexEvent, Event
from ..core.query import CompiledQuery, compile_query
from ..kernels import ops
from ..kernels import window as wkern
from . import tecs_arena
from .encoder import EventEncoder
from .symbolic import SymbolicCEA, compile_symbolic


def encode_windowed(encoder: EventEncoder, window: "wkern.DeviceWindow",
                    streams, base_pos=0):
    """(attrs, event_ts | None) for one pre-batched feed, per the window.

    Shared by :class:`VectorEngine` and
    :class:`~repro.vector.multiquery.MultiQueryEngine`.  Time windows
    encode the ``(T, B) f32`` timestamp operand and audit stream-order
    monotonicity (DESIGN.md §9).  ``base_pos`` anchors the arrival-order
    fallback clock; pass ``None`` when no position-derived clock exists
    (e.g. a traced / per-lane ``start_pos``) — events must then carry
    timestamps.
    """
    if not window.is_time:
        return jnp.asarray(encoder.encode_streams(streams)), None
    attrs, ts = encoder.encode_streams_ts(streams, window.time_attr,
                                          base_pos=base_pos)
    wkern.audit_monotone_ts(ts)
    return jnp.asarray(attrs), jnp.asarray(ts)


def _fallback_base(window: "wkern.DeviceWindow", start_pos):
    """Arrival-order clock anchor for one-shot runs: the scalar start
    position, or None (no fallback clock) when ``start_pos`` is a traced
    scalar or a per-lane vector."""
    if not window.is_time:
        return 0
    if isinstance(start_pos, (int, np.integer)):
        return int(start_pos)
    return None


def plan_oneshot(engine, attrs_shape, start_pos=0, trace: bool = False
                 ) -> "ops.Route":
    """Route of a one-shot pipeline call on ``engine`` (a VectorEngine or
    MultiQueryEngine) for ``(T, B, A)`` attributes, recorded on the engine
    as ``engine.routes["scan"]``."""
    t = engine.tables
    T, B, A = attrs_shape
    nq = t.finals.shape[0] if t.finals.ndim == 2 else 1
    route = ops.plan_pipeline(
        T=T, B=B, A=A, W=engine.ring, S=t.m_all.shape[1],
        NC=t.m_all.shape[0], NQ=nq, V=t.class_ind.shape[0],
        timed=engine.window.is_time,
        per_lane=getattr(start_pos, "ndim", 0) >= 1,
        latest=t.latest_q is not None, consume=t.consume_sq is not None,
        trace=trace, impl=engine.impl, use_pallas=engine.use_pallas,
        b_tile=engine.b_tile)
    engine.routes = {"scan": route}
    return route


@dataclass
class VectorQueryTables:
    """Device-resident tables for one compiled query.

    ``latest_q``/``consume_sq`` are the compiled-semantics operands
    (``repro.core.query.resolve_semantics``): ``latest_q`` is a (Q,) f32
    per-query LAST flag (latest-slot count reduction), ``consume_sq`` a
    (Q, S) f32 CONSUME BY ANY state-clear table (rows of non-consuming
    queries are zero).  Both are ``None`` when trivial, so graphs —
    and packing fingerprints — of plain-ALL queries stay bit-identical
    to the pre-semantics format.
    """

    m_all: jnp.ndarray       # (C, S, S) f32
    finals: jnp.ndarray      # (S,) f32
    class_of: jnp.ndarray    # (2^k,) int32
    class_ind: jnp.ndarray   # (≥2^k, C) f32 one-hot indicator (fused path)
    init_mask: jnp.ndarray   # (S,) f32 one-hot seed at the initial det state
    num_states: int
    num_classes: int
    num_bits: int
    latest_q: Optional[jnp.ndarray] = None    # (Q,) f32 | None
    consume_sq: Optional[jnp.ndarray] = None  # (Q, S) f32 | None


class VectorEngine:
    """End-to-end device evaluation of a windowed CEQL query over B streams.

    The window comes from the compiled query's own ``WITHIN`` clause
    (:class:`repro.kernels.window.DeviceWindow`, DESIGN.md §9) — count
    *and* time windows.  ``epsilon=`` survives only as a deprecation shim:
    it must agree with the query's clause (contradictions raise) and is
    required when the query has no clause at all (with a warning).  For
    time windows, ``max_window_events`` sizes the ring's rate bound (most
    starts simultaneously live; overflow latches per-lane ``ovf``).
    """

    def __init__(self, query: Union[str, CompiledQuery],
                 epsilon: Optional[int] = None,
                 use_pallas: bool = True, b_tile: int = 8,
                 impl: Optional[str] = None, arena_impl: str = "block",
                 max_window_events: Optional[int] = None):
        compiled = compile_query(query) if isinstance(query, str) else query
        self.compiled = compiled
        # Resolve the query's selection strategy + CONSUME clause up front:
        # unsupported semantics raise HERE (mirroring resolve_window), so a
        # device engine can never silently evaluate a query under ANY.
        self.semantics = compiled.semantics
        self.strategies = (compiled.query.strategy,)
        self.consumes = (bool(compiled.query.consume_on_match),)
        self.native_semantics = (self.semantics.construction != "ALL"
                                 or self.semantics.latest
                                 or self.semantics.consume)
        self.symbolic: SymbolicCEA = compile_symbolic(
            compiled.cea, strategy=self.semantics.construction)
        self.encoder = EventEncoder.from_registry(compiled.cea.registry)
        self.window = wkern.resolve_window(
            compiled.query.window, epsilon=epsilon,
            max_window_events=max_window_events)
        self.epsilon = self.window.epsilon
        self.ring = self.window.ring
        self.use_pallas = use_pallas
        self.b_tile = b_tile
        # impl: None → fused when the device path is on, ref otherwise
        self.impl = impl if impl is not None else (
            "fused" if use_pallas else "ref")
        # arena_impl: "block" (vectorized allocation, DESIGN.md §8) or
        # "fold" (per-event reference fold, kept for parity testing)
        self.arena_impl = tecs_arena.check_arena_impl(arena_impl)
        #: stage → :class:`~repro.kernels.ops.Route` of the last call
        self.routes: Dict[str, ops.Route] = {}
        init_mask = np.zeros(self.symbolic.num_states, np.float32)
        init_mask[self.symbolic.initial] = 1.0
        sem = self.semantics
        self.tables = VectorQueryTables(
            m_all=jnp.asarray(self.symbolic.transition_matrices()),
            finals=jnp.asarray(self.symbolic.finals, dtype=jnp.float32),
            class_of=jnp.asarray(self.symbolic.class_of),
            class_ind=ops.class_indicator(self.symbolic.class_of,
                                          self.symbolic.num_classes),
            init_mask=jnp.asarray(init_mask),
            num_states=self.symbolic.num_states,
            num_classes=self.symbolic.num_classes,
            num_bits=self.symbolic.num_bits,
            latest_q=(jnp.ones((1,), jnp.float32) if sem.latest else None),
            consume_sq=(jnp.ones((1, self.symbolic.num_states), jnp.float32)
                        if sem.consume else None),
        )

    # ------------------------------------------------------------------
    def init_state(self, batch: int):
        """Fresh scan state: ``(B, W, S)`` f32 ring for count windows, the
        ``{"C", "ts", "ovf"}`` pytree for time windows (DESIGN.md §9)."""
        return wkern.init_state(self.window, batch,
                                self.tables.num_states)

    def encode(self, streams: Sequence[Sequence[Event]]) -> jnp.ndarray:
        """B streams of T events → (T, B, A) f32 attribute tensor."""
        return jnp.asarray(self.encoder.encode_streams(streams))

    def encode_ts(self, streams: Sequence[Sequence[Event]],
                  base_pos: Optional[int] = 0):
        """→ (attrs (T, B, A), event_ts (T, B) | None) per the window.

        Time windows also audit that timestamps are monotone in stream
        order (the eviction rule's precondition, shared with the host
        engine's binary search).
        """
        return encode_windowed(self.encoder, self.window, streams,
                               base_pos=base_pos)

    # ------------------------------------------------------------------
    def classify(self, attrs: jnp.ndarray) -> jnp.ndarray:
        """(T, B, A) attributes → (T, B) int32 symbol-class ids."""
        T, B, A = attrs.shape
        flat = attrs.reshape(T * B, A)
        bits = ops.bitvector(flat, self.encoder.specs,
                             use_pallas=self.use_pallas)
        return self.tables.class_of[bits].reshape(T, B)

    def scan(self, class_ids: jnp.ndarray, state: jnp.ndarray,
             start_pos: Union[int, jnp.ndarray] = 0
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(T, B) class ids × (B, W, S) state → (matches (T, B), state').

        Legacy count-window entry point (the unfused scan kernels);
        time-window queries evaluate through :meth:`pipeline`.
        """
        wkern.require_count_scan(self.window)
        if self.tables.latest_q is not None or \
                self.tables.consume_sq is not None:
            raise ValueError(
                "scan() cannot honor LAST / CONSUME BY ANY semantics "
                f"(query strategy {self.compiled.query.strategy!r}); "
                "use pipeline()")
        return ops.cea_scan(class_ids, self.tables.m_all, self.tables.finals,
                            state, epsilon=self.epsilon, start_pos=start_pos,
                            use_pallas=self.use_pallas, b_tile=self.b_tile)

    def pipeline(self, attrs: jnp.ndarray, state,
                 start_pos: Union[int, jnp.ndarray] = 0,
                 event_ts: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Single-dispatch path: (T, B, A) attrs → (matches (T, B), state').

        Time windows additionally take the ``event_ts (T, B) f32`` operand
        (:meth:`encode_ts`)."""
        t = self.tables
        matches, state = ops.cer_pipeline(
            attrs, self.encoder.specs, t.class_of, t.class_ind, t.m_all,
            t.finals[None, :], state, init_mask=t.init_mask,
            window=self.window, event_ts=event_ts, start_pos=start_pos,
            latest_q=t.latest_q, consume_sq=t.consume_sq,
            route=plan_oneshot(self, attrs.shape, start_pos))
        return matches[:, :, 0], state

    def run(self, streams: Sequence[Sequence[Event]],
            state=None, start_pos: Union[int, jnp.ndarray] = 0
            ) -> Tuple[np.ndarray, jnp.ndarray]:
        """Convenience host→device→host path.

        Returns (match counts (T, B) int64, final device state).
        """
        attrs, ts = self.encode_ts(
            streams, base_pos=_fallback_base(self.window, start_pos))
        if state is None:
            state = self.init_state(attrs.shape[1])
        matches, state = self.pipeline(attrs, state, start_pos=start_pos,
                                       event_ts=ts)
        return np.asarray(matches).astype(np.int64), state

    def window_overflow(self, state) -> np.ndarray:
        """Per-lane latched rate-bound flags of a returned state (always
        all-False for count windows — they cannot overflow)."""
        return wkern.window_overflow(state)

    # ------------------------------------------------------------------
    # device tECS arena: enumeration without host event replay (DESIGN §7)
    # ------------------------------------------------------------------
    def arena_tables(self) -> tecs_arena.ArenaTables:
        """Static predecessor tables driving the device tECS arena."""
        tbl = getattr(self, "_arena_tables", None)
        if tbl is None:
            tbl = tecs_arena.tables_from_symbolic(self.symbolic)
            self._arena_tables = tbl
        return tbl

    def run_enumerate(self, streams: Sequence[Sequence[Event]],
                      start_pos: int = 0, arena_capacity: int = 1 << 15,
                      strategy: Optional[str] = None
                      ) -> Tuple[np.ndarray,
                                 Dict[Tuple[int, int], List[ComplexEvent]]]:
        """Device-arena evaluation *with enumeration* (narrows deviation D1).

        The whole pipeline — predicates, counting scan, and tECS arena
        maintenance — runs in one jitted device computation
        (:func:`repro.vector.tecs_arena.run_enumerate`); the host only
        fetches the arena arrays and walks Algorithm 2 over them
        (output-linear delay, no event replay).

        ``strategy=None`` (the default) enumerates under the query's OWN
        compiled semantics — the strategy-aware tables already keep
        exactly the selected matches, so the walk touches O(matches kept)
        nodes with no host re-filter.  Passing an explicit strategy is the
        legacy post-filter path and is only accepted on engines whose
        query compiled to plain ALL semantics (a conflicting strategy on
        a natively-compiled engine raises).

        Returns ``(counts (T, B) int64, matches)`` with ``matches`` mapping
        each hit ``(t, b)`` to its complex events.
        """
        counts, res = tecs_arena.run_enumerate(
            self, streams, start_pos=start_pos,
            arena_capacity=arena_capacity, strategy=strategy)
        return counts[:, :, 0], {(t, b): v for (t, b, _q), v in res.items()}

    # ------------------------------------------------------------------
    def partitioned_streaming(self, key_attrs: Sequence[str],
                              chunk_len: int, num_lanes: int, **kw):
        """Device-native PARTITION BY runtime over this query's tables.

        Returns a :class:`repro.vector.partitioned.PartitionedStreamingEngine`
        that hash-routes raw interleaved chunks to ``num_lanes`` substream
        lanes on device (paper §5.4, DESIGN.md §6).
        """
        from .partitioned import PartitionedStreamingEngine
        return PartitionedStreamingEngine(self, key_attrs, chunk_len,
                                          num_lanes, **kw)

    # ------------------------------------------------------------------
    def hit_positions(self, matches: np.ndarray) -> List[Tuple[int, int]]:
        """(t, b) positions with ≥1 match — where enumeration applies
        (:meth:`run_enumerate` / the streaming arena do this on device)."""
        t_idx, b_idx = np.nonzero(matches)
        return list(zip(t_idx.tolist(), b_idx.tolist()))
