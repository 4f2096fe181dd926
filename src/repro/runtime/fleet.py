"""Dynamic query fleet: hot add/remove CEQL queries over a live stream.

CORE's target workload is *many concurrent user-defined patterns* whose
rule set evolves at runtime; :class:`MultiQueryEngine` freezes its query
set at construction, so adding or dropping one pattern would recompile the
world.  :class:`QueryFleet` closes that gap (DESIGN.md §11):

* **Per-window buckets** — queries are routed by their *resolved*
  :class:`~repro.kernels.window.DeviceWindow`; each bucket holds one
  packed engine (the per-pack single-window invariant stays intact, and
  mixed-window query sets no longer raise).
* **Size-bucketed packings** — every query-dependent device dimension is
  padded to a bucket size (packed states and query slots to powers of
  two; joint classes, predicate bits and encoder attributes to multiples
  of four).  Padding is *dead* by construction
  (:func:`repro.vector.multiquery.check_packing_invariants` runs on every
  repack).
* **A compile cache keyed on bucket geometry** — the streaming step takes
  the packed tables as *traced operands* (the data-driven XLA pipeline),
  so two packings with the same padded geometry share one jitted
  executable: ~100 add/removes trigger at most one compile per distinct
  geometry.  tECS-arena steps close over their tables (the block arena's
  static layout is value-dependent), so arena buckets key the cache on
  geometry + table fingerprint (qid-independent) — still a hit for the
  common remove → re-add churn, even under a fresh qid.
* **Live state migration** — a repack snapshots the bucket's engine and
  restores it into the new packing via the repack-aware
  ``restore(migrate_packing=True)`` path: surviving queries keep their
  in-flight runs (bit-identical continuations), removed queries' state is
  dropped, new queries start empty at the current stream position.
* **Per-query cost reports** — states consumed, hits, match counts, live
  arena cells/nodes, and overflow latches per query, the raw material for
  rebalancing hot queries across buckets/shards.

Snapshots carry per-query membership and per-bucket packing fingerprints,
so crash recovery (:class:`~repro.runtime.recovery.
RecoveringStreamRunner`) survives fleet churn: a restored fleet rebuilds
each bucket's packing from the manifest and refuses a fingerprint
mismatch.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.predicates import AtomRegistry
from ..core.query import compile_query
from ..kernels import ops, ref
from ..kernels import window as wkern
from ..vector import tecs_arena
from ..vector.multiquery import (MultiQueryEngine, Packing, build_packing,
                                 check_packing_invariants,
                                 resolve_query_window)
from ..vector.streaming import StreamingVectorEngine

#: kernels/ref.bitvector_ref op-code order: ==, !=, <, <=, >, >=
_OP_LT = 2

#: fleet snapshot layout version
FLEET_SNAPSHOT_FORMAT = 1


def _pow2(n: int, lo: int = 1) -> int:
    p = max(1, int(lo))
    while p < n:
        p <<= 1
    return p


def _mult(n: int, m: int = 4, lo: int = 4) -> int:
    return max(lo, ((int(n) + m - 1) // m) * m)


class CompileCache:
    """Geometry-keyed cache of jitted streaming steps (DESIGN.md §11).

    One entry per distinct bucket geometry ``(padded_states,
    padded_query_slots, padded_classes, padded_bits, attr_slots, window,
    chunk_len, batch, arena)``.  Entries for arena-off buckets take the
    packed tables as traced operands, so every packing of a geometry
    reuses the same executable; arena entries additionally key on the
    packing's table fingerprint (the block arena's layout is table-value
    dependent; qids are not, so renames still hit).  ``compile_count`` counts actual traces — the churn bench
    gates it against ``distinct_keys``.
    """

    def __init__(self):
        self._steps: Dict[tuple, Callable] = {}
        #: keys in trace order, one append per executable actually compiled
        self.traces: List[tuple] = []
        #: cache hits (an add/remove that reused an existing step)
        self.hits = 0

    @property
    def compile_count(self) -> int:
        return len(self.traces)

    @property
    def distinct_keys(self) -> int:
        return len(self._steps)

    def get(self, key: tuple, build: Callable[["CompileCache", tuple],
                                              Callable]) -> Callable:
        fn = self._steps.get(key)
        if fn is None:
            fn = self._steps[key] = build(self, key)
        else:
            self.hits += 1
        return fn

    def _record_trace(self, key: tuple) -> None:
        # called from inside a jitted step body: runs once per trace
        self.traces.append(key)


def _make_data_step(cache: CompileCache, key: tuple,
                    window: "wkern.DeviceWindow") -> Callable:
    """A streaming step with the packed tables as *traced operands*.

    This is the data-driven twin of ``StreamingVectorEngine._step_impl``:
    the same XLA dataflow (``ref.class_trace_ref`` +
    ``ref.cea_scan_multi_ref`` — exactly what ``cer_pipeline``'s XLA route
    lowers to), but predicates arrive as ``idx/ops/thr`` arrays and the
    automaton tables as operands rather than baked constants.  jit's
    signature cache then keys on *shapes only*: every packing of the same
    bucket geometry hits the same executable.  Padding is exact — padded
    states/queries/classes/bits contribute only ``x + 0.0`` terms, so
    counts are bit-identical to the unpadded engine.
    """
    def step(tables, attrs, state, start_pos, event_ts=None):
        cache._record_trace(key)
        class_ids = ref.class_trace_ref(
            attrs, tables["idx"], tables["ops"], tables["thr"],
            tables["class_of"])
        # semantic operands (DESIGN.md D2) ride the traced-tables dict only
        # when non-trivial; their presence is part of the geometry key, so
        # ALL-only packings keep sharing the pre-semantics executable
        c_fin, matches = ref.cea_scan_multi_ref(
            state, tables["m_all"], class_ids, tables["finals_q"],
            tables["init_mask"], window.epsilon, start_pos=start_pos,
            window=window, event_ts=event_ts,
            latest_q=tables.get("latest_q"),
            consume_sq=tables.get("consume_sq"))
        return matches, c_fin

    return jax.jit(step, donate_argnums=(2,))


def _make_arena_step(cache: CompileCache, key: tuple, atables, specs,
                     class_of, class_ind, m_all, finals_q, init_mask,
                     window, route, arena_impl, latest_q=None,
                     consume_sq=None) -> Callable:
    """Counting + tECS-arena step with closed-over tables.

    The block arena's static layout is computed from table *values*
    (DESIGN.md §8), so this step cannot take tables as operands; the cache
    key therefore includes the table fingerprint.  Closures capture only
    packing-derived arrays (never the engine), so a re-added identical
    packing reuses the step across engine instances.
    """
    def step(attrs, state, start_pos, gbase, event_ts=None):
        cache._record_trace(key)
        counts, C, arena, roots = tecs_arena.scan_chunk(
            atables, state["arena"], attrs, state["C"], specs=specs,
            class_of=class_of, class_ind=class_ind, m_all=m_all,
            finals_q=finals_q, init_mask=init_mask, window=window,
            start=start_pos, gbase=gbase, route=route, arena_impl=arena_impl,
            event_ts=event_ts, latest_q=latest_q, consume_sq=consume_sq)
        return counts, {"C": C, "arena": arena}, roots

    return jax.jit(step, donate_argnums=(1,))


class _FleetStreamEngine(StreamingVectorEngine):
    """Bucket-local streaming engine served from the fleet's CompileCache.

    Pads the encoded attribute width to the bucket's ``attr_slots`` on
    every feed (padded predicate rows are constant-false, so padded
    columns are never read) and swaps the per-instance jitted step for the
    fleet-wide cached one.
    """

    def __init__(self, engine: MultiQueryEngine, chunk_len: int, batch: int,
                 *, cache: CompileCache, attr_slots: int,
                 arena_capacity: Optional[int] = None,
                 arena_impl: Optional[str] = None,
                 strict_overflow: bool = False):
        super().__init__(engine, chunk_len, batch, impl="ref",
                         arena_capacity=arena_capacity,
                         arena_impl=arena_impl,
                         strict_overflow=strict_overflow)
        self._cache = cache
        self._attr_slots = int(attr_slots)
        pk = engine.packing
        self.geometry = (
            pk.padded_states, pk.padded_queries, pk.padded_classes,
            pk.padded_bits, self._attr_slots,
            self.window.kind, float(self.window.size),
            self.window.time_attr, int(self.window.ring),
            int(chunk_len), int(batch),
            None if arena_capacity is None else int(arena_capacity),
            # semantic-operand presence flags (DESIGN.md D2): a LAST /
            # CONSUME packing's step has a different traced signature, so
            # it must not share the ALL-only geometry's cache entry
            self._latest_q is not None, self._consume_sq is not None)
        if arena_capacity is None:
            k_pad = pk.padded_bits
            idx = np.zeros(k_pad, np.int32)
            ops_ = np.full(k_pad, _OP_LT, np.int32)
            thr = np.full(k_pad, -np.inf, np.float32)
            for i, (col, op, t) in enumerate(self._specs):
                idx[i], ops_[i], thr[i] = col, op, t
            # device-resident once: feeds must not re-upload tables
            self._operands = {
                "idx": jnp.asarray(idx), "ops": jnp.asarray(ops_),
                "thr": jnp.asarray(thr),
                "class_of": jnp.asarray(self._class_of),
                "m_all": jnp.asarray(self._m_all),
                "finals_q": jnp.asarray(self._finals_q),
                "init_mask": jnp.asarray(self._init_mask)}
            if self._latest_q is not None:
                self._operands["latest_q"] = jnp.asarray(self._latest_q)
            if self._consume_sq is not None:
                self._operands["consume_sq"] = jnp.asarray(self._consume_sq)
            inner = cache.get(
                self.geometry,
                lambda c, k: _make_data_step(c, k, self.window))
            self._step = (lambda attrs, state, start, ts=None:
                          inner(self._operands, attrs, state, start, ts))
        else:
            key = self.geometry + ("arena", pk.table_fingerprint,
                                   self.arena_impl)
            self._step = cache.get(
                key,
                lambda c, k: _make_arena_step(
                    c, k, self._arena_tables, self._specs, self._class_of,
                    self._class_ind, self._m_all, self._finals_q,
                    self._init_mask, self.window, self.routes["scan"],
                    self.arena_impl,
                    latest_q=self._latest_q, consume_sq=self._consume_sq))

    def _plan_routes(self, per_lane: bool = False):
        routes = super()._plan_routes(per_lane)
        routes["scan"] = ops.Route(
            "xla", "fleet steps take the packed tables as traced operands "
                   "so one executable serves every packing of a bucket "
                   "geometry; the fused kernel compiles tables in")
        return routes

    def feed_attrs(self, attrs, event_ts=None):
        a = attrs.shape[-1]
        if a < self._attr_slots:
            attrs = jnp.pad(
                attrs, ((0, 0), (0, 0), (0, self._attr_slots - a)))
        return super().feed_attrs(attrs, event_ts)

    @property
    def compile_count(self) -> int:
        """Fleet-wide compile count — steps are shared, so a per-engine
        number would be meaningless."""
        return self._cache.compile_count


@dataclass
class _Bucket:
    key: tuple                       # (kind, size, time_attr)
    window: "wkern.DeviceWindow"
    qids: List[str] = field(default_factory=list)
    packing: Optional[Packing] = None
    engine: Optional[_FleetStreamEngine] = None


class QueryFleet:
    """A mutable set of compiled queries served over one live stream.

    ::

        fleet = QueryFleet(chunk_len=64, batch=4)
        qid = fleet.add_query("SELECT * FROM S WHERE A;B WITHIN 16 events")
        counts, hits = fleet.feed(streams)      # (T, B, n_live) int64
        fleet.remove_query(qid)

    ``add_query``/``remove_query`` repack only the affected window bucket
    — host work (query compilation + a state migration); the device
    executable is almost always a :class:`CompileCache` hit.  ``feed``
    drives every bucket in lockstep over the same chunk and returns
    de-packed per-query counts, columns ordered by sorted qid
    (:attr:`live_qids`).

    Construction parameters mirror the streaming engines; ``epsilon`` is
    the *default* count window for queries without a WITHIN clause, and
    ``max_window_events`` the default rate bound for time windows.
    """

    def __init__(self, chunk_len: int, batch: int, *,
                 epsilon: Optional[int] = None,
                 arena_capacity: Optional[int] = None,
                 arena_impl: str = "block",
                 max_window_events: Optional[int] = None,
                 strict_overflow: bool = False,
                 min_state_slots: int = 8, min_query_slots: int = 1,
                 check_invariants: bool = True):
        self.chunk_len = int(chunk_len)
        self.batch = int(batch)
        self.epsilon = epsilon
        self.arena_capacity = arena_capacity
        self.arena_impl = arena_impl
        self.max_window_events = max_window_events
        self.strict_overflow = bool(strict_overflow)
        self.min_state_slots = int(min_state_slots)
        self.min_query_slots = int(min_query_slots)
        self.check_invariants = bool(check_invariants)
        self._cache = CompileCache()
        self._queries: Dict[str, str] = {}
        self._buckets: Dict[tuple, _Bucket] = {}
        self._stats: Dict[str, Dict[str, int]] = {}
        self._pos = 0
        self._next_id = 0

    # -- introspection --------------------------------------------------
    @property
    def position(self) -> int:
        """Absolute stream position of the next event to arrive."""
        return self._pos

    @property
    def live_qids(self) -> List[str]:
        """Live query ids in feed-column order (sorted)."""
        return sorted(self._queries)

    @property
    def num_queries(self) -> int:
        return len(self._queries)

    @property
    def num_buckets(self) -> int:
        return len(self._buckets)

    @property
    def compile_count(self) -> int:
        """Executables actually compiled since construction."""
        return self._cache.compile_count

    @property
    def distinct_geometries(self) -> int:
        """Distinct compile-cache keys ever built (the compile ceiling)."""
        return self._cache.distinct_keys

    @property
    def cache_hits(self) -> int:
        return self._cache.hits

    def query_text(self, qid: str) -> str:
        return self._queries[qid]

    def bucket_of(self, qid: str) -> tuple:
        """The (kind, size, time_attr) window key serving ``qid``."""
        return self._find_bucket(qid).key

    # -- membership -----------------------------------------------------
    def _window_of(self, text: str) -> "wkern.DeviceWindow":
        # throwaway compile against a scratch registry: only the parsed
        # WITHIN clause is needed for routing; the bucket's shared-registry
        # compile happens in build_packing
        cq = compile_query(text, AtomRegistry())
        return resolve_query_window(
            cq.query.window, epsilon=self.epsilon,
            max_window_events=self.max_window_events)

    def _find_bucket(self, qid: str) -> _Bucket:
        for b in self._buckets.values():
            if qid in b.qids:
                return b
        raise KeyError(f"no live query {qid!r} in this fleet")

    def add_query(self, text: str, qid: Optional[str] = None) -> str:
        """Compile and start serving ``text``; returns its qid.

        The query joins the bucket of its resolved window at the current
        stream position (it observes events from now on — parity target:
        a fresh engine fed only the post-add suffix).  Only that bucket
        repacks; its surviving queries' live runs migrate bit-identically.
        """
        if qid is None:
            qid = f"q{self._next_id}"
            self._next_id += 1
        if qid in self._queries:
            raise ValueError(f"query id {qid!r} is already live")
        window = self._window_of(text)
        key = (window.kind, float(window.size), window.time_attr)
        self._queries[qid] = text
        bucket = self._buckets.get(key)
        if bucket is None:
            bucket = self._buckets[key] = _Bucket(key=key, window=window)
        bucket.qids = sorted(bucket.qids + [qid])
        self._stats[qid] = {"hits": 0, "matches": 0, "events": 0}
        try:
            self._repack(bucket)
        except Exception:
            # leave the fleet as it was: a bad query must not take down
            # the bucket's healthy residents
            del self._queries[qid]
            del self._stats[qid]
            bucket.qids.remove(qid)
            if not bucket.qids:
                del self._buckets[key]
            else:
                self._repack(bucket)
            raise
        return qid

    def remove_query(self, qid: str) -> None:
        """Stop serving ``qid``; its state is dropped, the bucket repacks.

        Removing the last query of a bucket drops the bucket (and its
        device state) entirely.
        """
        bucket = self._find_bucket(qid)
        del self._queries[qid]
        del self._stats[qid]
        bucket.qids.remove(qid)
        if not bucket.qids:
            del self._buckets[bucket.key]
            return
        self._repack(bucket)

    # -- repack ---------------------------------------------------------
    def _build_packing(self, qids: Sequence[str]) -> Packing:
        return build_packing(
            [self._queries[q] for q in qids], qids=tuple(qids),
            pad_states=lambda n: _pow2(n, self.min_state_slots),
            pad_queries=lambda n: _pow2(n, self.min_query_slots),
            pad_classes=_mult, pad_bits=_mult)

    def _build_engine(self, bucket: _Bucket,
                      packing: Packing) -> _FleetStreamEngine:
        engine = MultiQueryEngine.from_packing(
            packing, epsilon=self.epsilon, arena_impl=self.arena_impl,
            max_window_events=self.max_window_events)
        if (engine.window.kind, float(engine.window.size),
                engine.window.time_attr) != bucket.key:
            raise ValueError(
                f"packing resolved window {engine.window} but was routed "
                f"to bucket {bucket.key} — query text changed meaning?")
        attr_slots = _mult(len(packing.encoder.attrs))
        return _FleetStreamEngine(
            engine, self.chunk_len, self.batch, cache=self._cache,
            attr_slots=attr_slots, arena_capacity=self.arena_capacity,
            arena_impl=self.arena_impl,
            strict_overflow=self.strict_overflow)

    def _repack(self, bucket: _Bucket) -> None:
        packing = self._build_packing(bucket.qids)
        if self.check_invariants:
            check_packing_invariants(packing)
        se = self._build_engine(bucket, packing)
        old = bucket.engine
        if old is not None:
            # live migration: surviving queries keep their in-flight runs
            se.restore(old.snapshot(), migrate_packing=True)
        else:
            se._pos = self._pos     # new bucket joins mid-stream
        bucket.packing = packing
        bucket.engine = se

    # -- feeding --------------------------------------------------------
    def _sorted_buckets(self) -> List[_Bucket]:
        return [self._buckets[k] for k in
                sorted(self._buckets, key=lambda k: (k[0], k[1], k[2] or ""))]

    def feed(self, streams) -> Tuple[np.ndarray, List[Tuple[int, int]]]:
        """Feed one chunk of B streams × chunk_len events to every bucket.

        Returns ``(counts, hits)``: counts is ``(chunk_len, B, n_live)``
        int64 with columns in :attr:`live_qids` order; hits is the sorted
        list of absolute ``(position, stream)`` pairs where *any* live
        query matched.
        """
        per_q: Dict[str, np.ndarray] = {}
        hit_set: set = set()
        for bucket in self._sorted_buckets():
            counts, hits = bucket.engine.feed(streams)
            hit_set.update(hits)
            for slot, qid in enumerate(bucket.qids):
                cq = counts[:, :, slot]
                per_q[qid] = cq
                st = self._stats[qid]
                st["matches"] += int(cq.sum())
                st["hits"] += int((cq > 0).sum())
                st["events"] += cq.size
        self._pos += self.chunk_len
        qids = self.live_qids
        if qids:
            out = np.stack([per_q[q] for q in qids], axis=-1)
        else:
            out = np.zeros((self.chunk_len, self.batch, 0), np.int64)
        return out, sorted(hit_set)

    def counts_by_query(self, counts: np.ndarray) -> Dict[str, np.ndarray]:
        """De-pack a :meth:`feed` counts array into ``{qid: (T, B)}``."""
        return {q: counts[:, :, i] for i, q in enumerate(self.live_qids)}

    # -- enumeration (requires arena_capacity) --------------------------
    def enumerate(self, qid: str, position: int, stream: int = 0,
                  strategy: Optional[str] = None):
        """Complex events of ``qid`` closing at ``position`` on ``stream``
        — walks the bucket's device tECS arena (DESIGN.md §7).

        ``strategy=None`` (default) enumerates under the query's COMPILED
        selection semantics; an explicit strategy is the legacy host
        post-filter, valid only when the bucket carries no native
        semantics (:func:`repro.vector.tecs_arena.resolve_enum_strategy`).
        """
        bucket = self._find_bucket(qid)
        slot = bucket.qids.index(qid)
        return bucket.engine.enumerate(position, stream, query=slot,
                                       strategy=strategy)

    def clear_roots(self, before: Optional[int] = None) -> int:
        """Prune recorded enumeration roots across every bucket engine.

        ``before`` drops only roots at positions ``< before`` (the service
        layer's emission high-water mark); None drops all.  Returns the
        total number of entries dropped (DESIGN.md §13).
        """
        return sum(bucket.engine.clear_roots(before)
                   for bucket in self._buckets.values()
                   if bucket.engine is not None)

    # -- cost reporting -------------------------------------------------
    def cost_report(self) -> Dict[str, Dict[str, Any]]:
        """Per-query serving cost (DESIGN.md §11).

        ``states``: packed states consumed; ``hits``/``matches``: lifetime
        totals while live; ``arena_cells``/``arena_nodes``: live tECS cells
        in the query's state region and the distinct nodes they reference
        (0 with the arena off); ``overflow_lanes``: lanes whose rate-bound
        latch tripped in the query's bucket; plus the bucket key, slot,
        and bucket geometry — the inputs a rebalancer needs.
        """
        report: Dict[str, Dict[str, Any]] = {}
        for bucket in self._sorted_buckets():
            eng, pk = bucket.engine, bucket.packing
            ovf = [int(b) for b in np.nonzero(eng.window_overflow)[0]]
            cell = (np.asarray(eng.state["arena"]["cell"])
                    if self.arena_capacity is not None else None)
            for slot, qid in enumerate(bucket.qids):
                off, sz = pk.offsets[slot], pk.sizes[slot]
                d: Dict[str, Any] = {
                    "states": int(sz),
                    "bucket": bucket.key,
                    "slot": int(slot),
                    "geometry": eng.geometry,
                    "hits": int(self._stats[qid]["hits"]),
                    "matches": int(self._stats[qid]["matches"]),
                    "events": int(self._stats[qid]["events"]),
                    "overflow_lanes": ovf,
                    "arena_cells": 0,
                    "arena_nodes": 0,
                }
                if cell is not None:
                    region = cell[:, :, off:off + sz]
                    live = region[region != tecs_arena.NULL]
                    d["arena_cells"] = int(live.size)
                    d["arena_nodes"] = int(np.unique(live).size)
                report[qid] = d
        return report

    # -- crash-safe snapshots (DESIGN.md §10/§11) -----------------------
    def manifest(self) -> dict:
        """Fleet-level restore manifest: geometry, per-query membership,
        and per-bucket packing fingerprints (all JSON-able)."""
        buckets = []
        for i, bucket in enumerate(self._sorted_buckets()):
            buckets.append({
                "key": list(bucket.key),
                "qids": list(bucket.qids),
                "fingerprint": bucket.packing.fingerprint,
                "manifest": bucket.engine.manifest(),
            })
        return {
            "format": FLEET_SNAPSHOT_FORMAT,
            "engine": type(self).__name__,
            "chunk_len": self.chunk_len,
            "batch": self.batch,
            "epsilon": (None if self.epsilon is None else int(self.epsilon)),
            "arena_capacity": (None if self.arena_capacity is None
                               else int(self.arena_capacity)),
            "pos": int(self._pos),
            "next_id": int(self._next_id),
            "queries": dict(self._queries),
            "stats": {q: dict(s) for q, s in self._stats.items()},
            "buckets": buckets,
        }

    def snapshot(self) -> dict:
        """``{"arrays", "meta"}`` across every bucket — feed to
        ``CheckpointManager.save`` / :class:`RecoveringStreamRunner`."""
        arrays: Dict[str, np.ndarray] = {}
        for i, bucket in enumerate(self._sorted_buckets()):
            sub = bucket.engine.snapshot()
            for name, arr in sub["arrays"].items():
                arrays[f"bucket{i}/{name}"] = arr
        return {"arrays": arrays, "meta": self.manifest()}

    def restore(self, snapshot: dict) -> None:
        """Rebuild membership + buckets from the manifest and restore every
        bucket's engine state.

        The fleet must have been constructed with the same ``chunk_len`` /
        ``batch`` / ``epsilon`` / ``arena_capacity``.  Each bucket's
        packing is rebuilt from the recorded qids and query texts and
        verified against the recorded fingerprint — a mismatch (changed
        query semantics, different code version) refuses to restore rather
        than silently reinterpreting state.
        """
        meta, arrays = snapshot["meta"], snapshot["arrays"]
        if meta.get("engine") != type(self).__name__ or \
                meta.get("format") != FLEET_SNAPSHOT_FORMAT:
            raise ValueError(
                f"snapshot is a {meta.get('engine')!r} format "
                f"{meta.get('format')!r}, not a QueryFleet snapshot")
        for k in ("chunk_len", "batch", "epsilon", "arena_capacity"):
            mine = getattr(self, k)
            mine = None if mine is None else int(mine)
            if meta.get(k) != mine:
                raise ValueError(
                    f"snapshot {k}={meta.get(k)!r} != fleet {mine!r} — "
                    "construct the fleet with matching geometry")
        self._queries = dict(meta["queries"])
        self._stats = {q: {kk: int(vv) for kk, vv in s.items()}
                       for q, s in meta.get("stats", {}).items()}
        self._pos = int(meta["pos"])
        self._next_id = int(meta.get("next_id", 0))
        self._buckets = {}
        for i, bm in enumerate(meta["buckets"]):
            key = (bm["key"][0], float(bm["key"][1]), bm["key"][2])
            qids = list(bm["qids"])
            window = self._window_of(self._queries[qids[0]])
            bucket = _Bucket(key=key, window=window, qids=qids)
            packing = self._build_packing(qids)
            if packing.fingerprint != bm["fingerprint"]:
                raise ValueError(
                    f"bucket {key} repacked to fingerprint "
                    f"{packing.fingerprint[:12]}… but the snapshot recorded "
                    f"{bm['fingerprint'][:12]}… — the query set compiles "
                    "differently now; its state cannot be trusted")
            se = self._build_engine(bucket, packing)
            prefix = f"bucket{i}/"
            sub = {name[len(prefix):]: arr for name, arr in arrays.items()
                   if name.startswith(prefix)}
            se.restore({"arrays": sub, "meta": bm["manifest"]})
            bucket.packing = packing
            bucket.engine = se
            self._buckets[key] = bucket

    # -- maintenance ----------------------------------------------------
    def reset(self) -> None:
        """Drop all live runs (and arena nodes) in every bucket; rewind."""
        self._pos = 0
        for bucket in self._buckets.values():
            bucket.engine.reset()
        for st in self._stats.values():
            st.update(hits=0, matches=0, events=0)
