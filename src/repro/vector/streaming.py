"""Streaming CER runtime: compile-once chunked evaluation (DESIGN.md §5).

CORE's headline property is constant per-event cost on *unbounded* streams;
:class:`StreamingVectorEngine` is the device-side operational mode for that
claim:

* **Shape-stable chunks** — events arrive in fixed-length ``(chunk_len, B)``
  chunks, so the jitted step has exactly one input signature and compiles
  exactly once, no matter how many chunks flow through.
* **Dynamic** ``start_pos`` — the stream offset is a traced int32 operand
  (not a static), carried across chunks by the engine; the ring-buffer
  seed/expire slots are derived from it inside the kernel.
* **Donated state ring** — the ``(B, W, S)`` run-count tensor is donated to
  each step (``jit(..., donate_argnums=...)``), so steady-state streaming
  performs zero fresh allocations for state on backends with donation
  (donation is a no-op on CPU, where XLA ignores it with a warning we
  silence).
* **Device tECS arena** — with ``arena_capacity`` set, the same compiled
  step maintains the paper's enumeration structure on device (DESIGN.md
  §7): :meth:`feed` returns counts + the absolute ``(pos, stream)`` hit
  list, and :meth:`enumerate` walks Algorithm 2 over the fetched arena —
  output-linear delay, no event replay (deviation D1, narrowed).

Works for both the single-query :class:`~repro.vector.engine.VectorEngine`
and the packed :class:`~repro.vector.multiquery.MultiQueryEngine` (pass one
as ``engine``; match counts then carry a trailing query axis).

``feed`` expects B *pre-partitioned* streams; for one raw interleaved
stream with PARTITION BY keys, the subclass
:class:`~repro.vector.partitioned.PartitionedStreamingEngine` hash-routes
events to lanes on device first (DESIGN.md §6).

Time windows (DESIGN.md §9): the engine inherits the query's ``WITHIN``
clause through the wrapped engine's ``DeviceWindow``; feeds thread the
per-event timestamp operand, audit cross-chunk monotonicity, and expose
the latched rate-bound flags as :attr:`window_overflow`.
"""
from __future__ import annotations

import contextlib
import hashlib
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.events import ComplexEvent, Event
from ..core.selection import apply_strategy
from ..kernels import ops
from ..kernels import window as wkern
from . import tecs_arena

_I32_MAX = np.iinfo(np.int32).max

#: snapshot layout version (bumped on incompatible layout changes; restore
#: refuses a snapshot whose format it does not understand)
SNAPSHOT_FORMAT = 1


def _flatten_state(prefix: str, tree, out: Dict[str, np.ndarray]) -> None:
    """Flatten a state pytree of (possibly nested) dicts into host arrays.

    Key order is the dict's sorted keys joined with ``/`` — the same rule
    the checkpoint manager's path flattener applies, so snapshot leaves
    round-trip through :class:`repro.checkpoint.CheckpointManager` files
    under stable names.
    """
    if isinstance(tree, dict):
        for k in sorted(tree):
            _flatten_state(f"{prefix}/{k}", tree[k], out)
    else:
        out[prefix] = np.asarray(tree)


#: snapshot leaves whose LAST axis is the packed state dimension — the
#: block-diagonal count rings of the plain / arena / time-window / lane
#: state layouts.  ``…/arena/cell`` is handled separately (its state axis
#: is the arena's unpadded Ŝ and its fill value is the NULL node id).
_PACKED_STATE_LEAVES = ("state", "state/C", "state/C/C")


def migrate_packed_arrays(arrays: Dict[str, np.ndarray], old: dict,
                          new: dict) -> Dict[str, np.ndarray]:
    """Slice/scatter per-query state regions between two packings.

    ``old``/``new`` are :meth:`repro.vector.multiquery.Packing.spec` dicts.
    Queries are matched by qid: each surviving query's block-diagonal state
    region (count/time ring columns, tECS arena cell columns, enumeration
    root slots) is copied from its old offset to its new offset; regions of
    removed queries are dropped; regions of new queries start empty (zeros
    for rings, NULL for arena cells/roots).  Leaves without a packed state
    axis (timestamp rings, ovf latches, lane tables, arena node stores,
    bump pointers) migrate verbatim — they are per-lane, not per-state.

    Exactness: blocks don't interact in the packed scan, so a surviving
    query's migrated ring continues bit-identically to an engine that
    evaluated only that query from the start (DESIGN.md §11).
    """
    from .tecs_arena import NULL as _ANULL
    o_idx = {q: i for i, q in enumerate(old["qids"])}
    n_idx = {q: i for i, q in enumerate(new["qids"])}
    common = [q for q in new["qids"] if q in o_idx]
    for q in common:
        if old["sizes"][o_idx[q]] != new["sizes"][n_idx[q]]:
            raise ValueError(
                f"query {q!r} changed state count across the repack "
                f"({old['sizes'][o_idx[q]]} → {new['sizes'][n_idx[q]]}) — "
                "its live runs cannot be migrated; remove and re-add it")
        # a surviving query's compiled semantics must be unchanged: its
        # ring columns encode runs *under that strategy/CONSUME clause*
        # (older specs lack these keys; treat them as unchecked)
        for key, what in (("strategies", "selection strategy"),
                          ("consumes", "CONSUME clause")):
            if key in old and key in new and \
                    old[key][o_idx[q]] != new[key][n_idx[q]]:
                raise ValueError(
                    f"query {q!r} changed its {what} across the repack "
                    f"({old[key][o_idx[q]]!r} → {new[key][n_idx[q]]!r}) — "
                    "its live runs cannot be migrated; remove and "
                    "re-add it")
    out: Dict[str, np.ndarray] = {}
    for name, arr in arrays.items():
        if name in _PACKED_STATE_LEAVES:
            if arr.shape[-1] != old["padded_states"]:
                raise ValueError(
                    f"snapshot leaf {name!r} has state axis {arr.shape[-1]},"
                    f" its packing spec declares {old['padded_states']}")
            new_arr = np.zeros(arr.shape[:-1] + (new["padded_states"],),
                               arr.dtype)
        elif name.endswith("/arena/cell"):
            if arr.shape[-1] != old["num_states"]:
                raise ValueError(
                    f"snapshot leaf {name!r} has state axis {arr.shape[-1]},"
                    f" its packing spec declares {old['num_states']}")
            new_arr = np.full(arr.shape[:-1] + (new["num_states"],),
                              _ANULL, arr.dtype)
        elif name == "roots_val":
            new_arr = np.full((arr.shape[0], new["num_queries"]),
                              _ANULL, arr.dtype)
            for q in common:
                new_arr[:, n_idx[q]] = arr[:, o_idx[q]]
            out[name] = new_arr
            continue
        else:
            out[name] = arr
            continue
        for q in common:
            oo = old["offsets"][o_idx[q]]
            no = new["offsets"][n_idx[q]]
            sz = old["sizes"][o_idx[q]]
            new_arr[..., no:no + sz] = arr[..., oo:oo + sz]
        out[name] = new_arr
    return out


#: snapshot leaves whose axis 1 is the window ring — the slice/scatter
#: targets of a ring regrow.  Bare "state" is the count-window layout and
#: never regrows, but is listed for completeness of the addressing rule.
_RING_LEAVES = ("state", "state/C", "state/C/C", "state/ts", "state/C/ts")


def migrate_ring_arrays(arrays: Dict[str, np.ndarray], old_ring: int,
                        new_ring: int, next_pos: np.ndarray
                        ) -> Dict[str, np.ndarray]:
    """Scatter ring-indexed snapshot leaves onto a larger ring (regrow).

    The elastic sibling of :func:`migrate_packed_arrays` for the *ring*
    axis (DESIGN.md §12): count rings, the timestamp ring, and the arena
    cell table move slot ``k → (j mod W1)`` per
    :func:`repro.kernels.window.ring_slot_remap`; surplus W1 slots start
    empty (zeros / ``TS_EMPTY`` / arena ``NULL`` — exactly what a W1
    engine's expiry mask would have left there, so behaviour is identical
    to an engine built wide from the start: any start old enough to live
    only in the wider ring's extra history would have latched the W0
    engine's ``ovf`` flag already).  Leaves without a ring axis (``ovf``
    latches, lane tables, arena node stores, bump pointers, roots) pass
    through verbatim; per-lane position cursors are the caller's to
    rewrite into the new frame.
    """
    if new_ring == old_ring:
        return dict(arrays)
    new_slot, valid = wkern.ring_slot_remap(old_ring, new_ring, next_pos)
    k = np.arange(old_ring)
    out: Dict[str, np.ndarray] = {}
    for name, arr in arrays.items():
        if name in _RING_LEAVES:
            fill = (arr.dtype.type(wkern.TS_EMPTY) if name.endswith("/ts")
                    else arr.dtype.type(0))
        elif name.endswith("/arena/cell"):
            fill = arr.dtype.type(tecs_arena.NULL)
        else:
            out[name] = arr
            continue
        if arr.ndim < 2 or arr.shape[1] != old_ring:
            raise ValueError(
                f"snapshot leaf {name!r} has shape {arr.shape}; ring "
                f"migration expects axis 1 == {old_ring}")
        B = arr.shape[0]
        new = np.full((B, new_ring) + arr.shape[2:], fill, arr.dtype)
        for b in range(B):
            vb = valid[b]
            new[b, new_slot[b, vb]] = arr[b, k[vb]]
        out[name] = new
    return out


def _restore_like(prefix: str, template, arrays: Dict[str, np.ndarray]):
    """Rebuild a device pytree shaped like ``template`` from saved leaves.

    Shape/dtype mismatches raise — a snapshot must never restore onto an
    engine whose compiled shapes differ (silent corruption otherwise).
    """
    if isinstance(template, dict):
        return {k: _restore_like(f"{prefix}/{k}", template[k], arrays)
                for k in template}
    arr = arrays.get(prefix)
    if arr is None:
        raise ValueError(f"snapshot is missing state leaf {prefix!r}")
    tmpl = np.asarray(template)
    if tuple(arr.shape) != tmpl.shape or arr.dtype != tmpl.dtype:
        raise ValueError(
            f"snapshot state leaf {prefix!r} is {arr.shape}/{arr.dtype}, "
            f"this engine expects {tmpl.shape}/{tmpl.dtype} — restore onto "
            "a matching engine (same query, window, capacities)")
    return jnp.asarray(arr)


@contextlib.contextmanager
def _quiet_donation():
    """Silence XLA's per-compile donation nag on CPU.

    XLA has no donation on CPU; semantics are unchanged (callers always
    rebind the returned state), so the warning is noise.
    """
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


class StreamingVectorEngine:
    """Fixed-chunk streaming wrapper around the fused device pipeline."""

    def __init__(self, engine, chunk_len: int, batch: int,
                 impl: Optional[str] = None,
                 arena_capacity: Optional[int] = None,
                 arena_impl: Optional[str] = None,
                 strict_overflow: bool = False):
        """``engine``: a constructed VectorEngine or MultiQueryEngine.

        chunk_len: events per feed() call — fixed for shape-stable compiles.
        batch:     number of parallel substreams (partition-by lanes).
        arena_capacity: when set, the step also maintains the device tECS
                   arena (``arena_capacity`` node slots per lane,
                   DESIGN.md §7) inside the same compiled executable, and
                   hits become *enumerable* via :meth:`enumerate` without
                   any host event replay.
        arena_impl: "block" (vectorized allocation, DESIGN.md §8) or
                   "fold" (the per-event reference fold); default inherits
                   the engine's setting.
        strict_overflow: raise :class:`~repro.kernels.window.
                   WindowOverflowError` (with the latched lane ids) when a
                   time window's per-lane rate-bound ``ovf`` latch trips,
                   instead of silently degrading counts to a lower bound.
                   The raise happens *after* the chunk was applied — the
                   latch is persistent state, surfaced in snapshots.
        """
        if isinstance(engine, str):
            raise TypeError("pass a constructed VectorEngine/MultiQueryEngine"
                            " (a bare query string has no window ε)")
        self.engine = engine
        self.encoder = engine.encoder
        self.epsilon = engine.epsilon
        self.window = engine.window
        self.chunk_len = int(chunk_len)
        self.batch = int(batch)
        self.impl = impl if impl is not None else getattr(
            engine, "impl", "fused")
        t = engine.tables
        # normalize single-query tables to the NQ-generalized pipeline form
        finals = t.finals
        self._finals_q = finals if finals.ndim == 2 else finals[None, :]
        self._init_mask = t.init_mask
        self._class_of = t.class_of
        self._class_ind = t.class_ind
        self._m_all = t.m_all
        self._single_query = finals.ndim == 1
        self._specs = self.encoder.specs
        self._use_pallas = engine.use_pallas
        self._b_tile = engine.b_tile
        # compiled-semantics operands (None when every query is plain ALL —
        # keeps pre-semantics graphs, fingerprints and manifests identical)
        self._latest_q = getattr(t, "latest_q", None)
        self._consume_sq = getattr(t, "consume_sq", None)

        # ring slots depend on the position only mod W, so the kernel gets
        # self._pos % ring — the absolute (unbounded) position stays a host
        # int and the int32 operand can never overflow on long streams.
        # The ARENA path is the exception: node labels are absolute int32
        # positions, so with arena_capacity set feed() refuses past 2^31-1
        # events between resets (the arena's ovf latch fires several orders
        # of magnitude earlier anyway — see DESIGN.md §7).
        self._ring = engine.ring
        self._pos = 0
        self._trace_count = 0  # incremented per trace == per compile
        self.arena_capacity = arena_capacity
        self.arena_impl = tecs_arena.check_arena_impl(
            arena_impl if arena_impl is not None
            else getattr(engine, "arena_impl", "block"))
        self._arena_tables = (engine.arena_tables()
                              if arena_capacity is not None else None)
        self.strict_overflow = bool(strict_overflow)
        self._roots: Dict[Tuple[int, int], np.ndarray] = {}
        # persistent host mirror of the device arena: enumerate() fetches
        # only the appended delta since the last sync (DESIGN.md §13)
        self._arena_mirror = tecs_arena.ArenaMirror()
        # time windows: last timestamp per lane, carried across feeds for
        # the monotonicity audit (stream order must equal time order)
        self._last_ts: Optional[np.ndarray] = None
        self._state = self._init_full_state(batch)
        #: lanes parked by the service layer mid-regrow (DESIGN.md §12) —
        #: informational for the engine itself, but snapshot-carried so a
        #: crash mid-heal resumes the regrow instead of re-raising
        self._quarantined: Tuple[int, ...] = ()
        # state ring donated: steady-state streaming allocates nothing new
        self._step = self._make_step()

    #: events each lane advances per compiled step (the partitioned
    #: subclass scans ``lane_cap`` routed slots instead)
    @property
    def _scan_steps(self) -> int:
        return self.chunk_len

    def _plan_routes(self, per_lane: bool = False) -> Dict[str, "ops.Route"]:
        """Kernel-or-XLA route of each compiled stage, decided from the
        shapes (:func:`repro.kernels.ops.plan_pipeline`) — recorded so no
        switch to XLA or to interpret mode goes unseen."""
        A = len(self.encoder.attrs)
        routes = {"scan": ops.plan_pipeline(
            T=self._scan_steps, B=self.batch, A=A, W=self._ring,
            S=self._m_all.shape[1], NC=self._m_all.shape[0],
            NQ=self._finals_q.shape[0], V=self._class_ind.shape[0],
            timed=self.window.is_time, per_lane=per_lane,
            latest=self._latest_q is not None,
            consume=self._consume_sq is not None,
            trace=self.arena_capacity is not None, impl=self.impl,
            use_pallas=self._use_pallas, b_tile=self._b_tile)}
        if self.arena_capacity is not None:
            routes["arena"] = ops.arena_route(self._arena_tables,
                                              self.arena_impl)
        return routes

    def _make_step(self):
        """(Re)build the jitted step — called at init and after a ring
        regrow invalidates the compiled executable's shapes."""
        self.routes = self._plan_routes()
        return jax.jit(
            self._arena_step_impl if self.arena_capacity is not None
            else self._step_impl, donate_argnums=(1,))

    def _init_full_state(self, batch: int):
        C = self.engine.init_state(batch)
        if self.arena_capacity is None:
            return C
        return {"C": C, "arena": tecs_arena.init_arena(
            batch, self.arena_capacity, self._ring,
            self._arena_tables.num_states)}

    # ------------------------------------------------------------------
    def _step_impl(self, attrs: jnp.ndarray, state,
                   start_pos: jnp.ndarray, event_ts=None):
        self._trace_count += 1  # runs only while tracing (i.e. compiling)
        return ops.cer_pipeline(
            attrs, self._specs, self._class_of, self._class_ind, self._m_all,
            self._finals_q, state, init_mask=self._init_mask,
            window=self.window, event_ts=event_ts,
            start_pos=start_pos, latest_q=self._latest_q,
            consume_sq=self._consume_sq, route=self.routes["scan"])

    def _arena_step_impl(self, attrs: jnp.ndarray, state: dict,
                         start_pos: jnp.ndarray, gbase: jnp.ndarray,
                         event_ts=None):
        """Counting scan + tECS-arena maintenance, one compiled step.

        ``gbase`` is the chunk's absolute stream offset (int32): arena node
        labels are global positions, unlike the mod-ring ``start_pos``.
        """
        self._trace_count += 1  # runs only while tracing (i.e. compiling)
        counts, C, arena, roots = tecs_arena.scan_chunk(
            self._arena_tables, state["arena"], attrs, state["C"],
            specs=self._specs, class_of=self._class_of,
            class_ind=self._class_ind, m_all=self._m_all,
            finals_q=self._finals_q, init_mask=self._init_mask,
            window=self.window, start=start_pos, gbase=gbase,
            route=self.routes["scan"], arena_impl=self.arena_impl,
            event_ts=event_ts, latest_q=self._latest_q,
            consume_sq=self._consume_sq)
        return counts, {"C": C, "arena": arena}, roots

    # ------------------------------------------------------------------
    @property
    def position(self) -> int:
        """Absolute stream position of the next event to arrive."""
        return self._pos

    @property
    def state(self) -> jnp.ndarray:
        """Current (B, W, S) run-count ring (device-resident); with
        ``arena_capacity`` set, a ``{"C", "arena"}`` pytree instead.

        The buffer is *donated* to the next :meth:`feed` — on backends with
        donation (TPU/GPU) a held reference is invalidated by that call.
        Copy (``jnp.array(se.state)``) before feeding if you need a snapshot.
        """
        return self._state

    @property
    def window_overflow(self) -> np.ndarray:
        """Per-lane latched time-window rate-bound flags (DESIGN.md §9).

        All-False for count windows (which cannot overflow).  A latched
        lane saw more than ``max_window_events`` simultaneously-live starts
        — its counts are a lower bound until :meth:`reset`."""
        return wkern.window_overflow(self._state)

    @property
    def quarantined_lanes(self) -> Tuple[int, ...]:
        """Lanes parked by :meth:`quarantine` (empty outside a heal)."""
        return self._quarantined

    def quarantine(self, lanes: Sequence[int]) -> None:
        """Mark lanes as parked mid-overflow-heal (DESIGN.md §12).

        Purely bookkeeping on the engine side — the service layer stops
        routing to these lanes while it regrows the ring; the marks ride
        the snapshot manifest so a crash between quarantine and the
        completed regrow resumes the heal instead of re-raising."""
        self._quarantined = tuple(sorted({int(b) for b in lanes}))

    def clear_quarantine(self) -> None:
        self._quarantined = ()

    @property
    def compile_count(self) -> int:
        """How many distinct executables the step has compiled (goal: 1)."""
        cache_size = getattr(self._step, "_cache_size", None)
        if cache_size is not None:
            try:
                return int(cache_size())
            except Exception:
                pass
        return self._trace_count

    # ------------------------------------------------------------------
    # crash-safe snapshots (DESIGN.md §10)
    # ------------------------------------------------------------------
    _compat_keys = ("format", "engine", "query_fingerprint", "window",
                    "chunk_len", "batch", "num_states", "num_queries",
                    "arena_capacity", "semantics")

    def query_fingerprint(self) -> str:
        """Deterministic digest of the compiled query + encoder.

        Hashes the device tables (transition matrices, finals, class map,
        init mask) and the encoder layout (attribute order, predicate
        specs, string vocabularies) — everything that determines what the
        donated state *means*.  Stable across processes (unlike ``hash()``
        or object reprs), so a checkpoint written by one process refuses to
        restore into an engine compiled from a different query.
        """
        h = hashlib.sha256()
        enc = self.encoder
        h.update(repr((enc.attrs, enc.specs,
                       sorted((a, sorted(v.items()))
                              for a, v in enc.vocab.items()))).encode())
        for arr in (self._m_all, self._finals_q, self._class_of,
                    self._init_mask):
            a = np.asarray(arr)
            h.update(str((a.shape, str(a.dtype))).encode())
            h.update(a.tobytes())
        # compiled-semantics operands, hashed only when present so plain
        # ALL engines keep their pre-semantics fingerprints (matching
        # Packing._hash_tables): LAST shares MAX's transition tables and
        # consuming queries share the non-consuming ones, so the base
        # digest alone cannot tell them apart.
        if self._latest_q is not None or self._consume_sq is not None:
            h.update(b"semantics")
            for arr in (self._latest_q, self._consume_sq):
                if arr is None:
                    h.update(b"none")
                else:
                    a = np.asarray(arr)
                    h.update(str((a.shape, str(a.dtype))).encode())
                    h.update(a.tobytes())
        return h.hexdigest()

    def manifest(self) -> dict:
        """Restore-compatibility manifest (JSON-able, DESIGN.md §10).

        Recorded as the checkpoint's ``extra`` so :meth:`restore` can
        verify the snapshot and the engine agree on query, window, chunk
        geometry, and capacities *before* touching any state.
        """
        w = self.window
        return {
            "format": SNAPSHOT_FORMAT,
            "engine": type(self).__name__,
            "query_fingerprint": self.query_fingerprint(),
            "window": {"kind": w.kind, "size": float(w.size),
                       "time_attr": w.time_attr, "ring": int(w.ring)},
            "chunk_len": int(self.chunk_len),
            "batch": int(self.batch),
            "num_states": int(self._finals_q.shape[-1]),
            "num_queries": int(self._finals_q.shape[0]),
            "arena_capacity": (None if self.arena_capacity is None
                               else int(self.arena_capacity)),
            # compiled selection/consumption semantics (DESIGN.md D2, §10):
            # a snapshot taken under one strategy must not restore into an
            # engine compiled under another — the rings *mean* different
            # run sets (e.g. a consuming engine's ring is cleared on match)
            "semantics": {
                "strategies": [str(s) for s in
                               getattr(self.engine, "strategies", ()) or ()],
                "consume": [bool(c) for c in
                            getattr(self.engine, "consumes", ()) or ()],
            },
            "strict_overflow": bool(self.strict_overflow),
            "window_overflow": [int(b) for b in
                                np.nonzero(self.window_overflow)[0]],
            # not a compat key: lanes parked mid-overflow-heal, so a
            # restore after a crash mid-quarantine resumes the regrow
            "quarantined_lanes": [int(b) for b in self._quarantined],
            "pos": int(self._pos),
            "num_roots": len(self._roots),
            # not a compat key: the repack-aware restore path reads it to
            # migrate state between packings (DESIGN.md §11)
            "packing": (self.engine.packing.spec()
                        if getattr(self.engine, "packing", None) is not None
                        else None),
        }

    def snapshot(self) -> dict:
        """Host-side snapshot: ``{"arrays": {name: np.ndarray}, "meta"}``.

        Round-trips the full donated pytree — counting ring, timestamp
        ring, ``ovf`` latches, and the tECS arena (node store, cell table,
        bump pointers) — plus the stream cursor, the cross-chunk
        monotonicity carry, and the recorded enumeration roots.  Copies
        device buffers to host *before* the next :meth:`feed` donates
        them, reusing the :attr:`state` copy semantics, so snapshotting
        never breaks compile-once streaming.  Feed the parts to
        ``CheckpointManager.save(step, snap["arrays"],
        extra=snap["meta"])`` for an atomic on-disk checkpoint.
        """
        arrays: Dict[str, np.ndarray] = {}
        _flatten_state("state", self._state, arrays)
        if self._last_ts is not None:
            arrays["last_ts"] = np.asarray(self._last_ts, np.float32)
        self._snapshot_roots(arrays)
        return {"arrays": arrays, "meta": self.manifest()}

    def _snapshot_roots(self, arrays: Dict[str, np.ndarray]) -> None:
        keys = sorted(self._roots)
        if keys:
            arrays["roots_key"] = np.asarray(keys, np.int64)      # (N, 2)
            arrays["roots_val"] = np.stack(
                [np.asarray(self._roots[k], np.int32) for k in keys])

    def _restore_roots(self, arrays: Dict[str, np.ndarray]) -> None:
        self._roots.clear()
        if "roots_key" in arrays:
            for k, v in zip(arrays["roots_key"], arrays["roots_val"]):
                self._roots[(int(k[0]), int(k[1]))] = np.asarray(v, np.int32)

    #: compat keys waived by a ``migrate_packing`` restore — the packing
    #: (and therefore the fingerprint and packed dims) is *expected* to
    #: differ; everything else still has to match exactly
    _packing_elastic_keys = ("query_fingerprint", "num_states",
                             "num_queries", "semantics")

    def _check_manifest(self, meta: dict, skip: Sequence[str] = ()) -> None:
        mine = self.manifest()
        bad = [f"{k}: snapshot {meta.get(k)!r} != engine {mine[k]!r}"
               for k in self._compat_keys
               if k not in skip and meta.get(k) != mine[k]]
        if bad:
            raise ValueError(
                "snapshot is incompatible with this engine — restoring "
                "would silently corrupt state:\n  " + "\n  ".join(bad))

    def _migrated_arrays(self, snapshot: dict) -> Dict[str, np.ndarray]:
        """The repack path: remap the snapshot's packed-state leaves onto
        this engine's packing (queries matched by qid)."""
        old = (snapshot["meta"] or {}).get("packing")
        pk = getattr(self.engine, "packing", None)
        if old is None or pk is None:
            raise ValueError(
                "migrate_packing restore needs packing specs on both sides "
                "— the snapshot predates packed manifests or the engine is "
                "not packing-backed")
        return migrate_packed_arrays(snapshot["arrays"], old, pk.spec())

    def _check_window_elastic(self, meta: dict, target_ring: int) -> None:
        """Ring-elastic window compat: kind, size and time_attr must match
        exactly; the snapshot ring may be *smaller* (it migrates onto the
        wider ring) but never larger — a shrink would drop live starts."""
        w = self.window
        sw = meta.get("window") or {}
        mismatch = [k for k, v in (("kind", w.kind), ("size", float(w.size)),
                                   ("time_attr", w.time_attr))
                    if sw.get(k) != v]
        if mismatch:
            raise ValueError(
                f"snapshot window {sw!r} is incompatible with this engine "
                f"(kind={w.kind!r} size={w.size} time_attr={w.time_attr!r})"
                " — only the ring (rate bound) is elastic")
        if int(sw.get("ring", target_ring)) > target_ring:
            raise ValueError(
                f"ring regrow cannot shrink: snapshot ring "
                f"{int(sw['ring'])} > engine ring {target_ring}")

    def _ring_migration_frame(self, meta: dict,
                              arrays: Dict[str, np.ndarray]) -> np.ndarray:
        """Per-lane next-seed positions for the ring slot remap.

        The parent engine seeds slot ``pos mod ring`` for every lane, so
        the frame is the absolute stream cursor broadcast over lanes.
        ``PartitionedStreamingEngine`` overrides this to rewrite its
        per-lane virtual cursors into the new ring's frame (mutating the
        caller's ``arrays`` copy in place)."""
        return np.full(self.batch, int(meta["pos"]), np.int64)

    def _apply_ring(self, new_window: "wkern.DeviceWindow") -> None:
        """Point this engine (and the wrapped compile-time engine, whose
        ``window``/``ring``/``epsilon`` are plain derived attributes) at a
        regrown window.  Invalidates the compiled step: the next feed()
        traces exactly once for the new ring shapes.  The wrapped engine
        is mutated — only regrow an engine you own exclusively."""
        self.engine.window = new_window
        self.engine.ring = new_window.ring
        self.engine.epsilon = new_window.epsilon
        self.window = new_window
        self.epsilon = new_window.epsilon
        self._ring = new_window.ring
        self._trace_count = 0
        self._step = self._make_step()

    def _ring_migrated(self, meta: dict, arrays: Dict[str, np.ndarray],
                       max_window_events: Optional[int],
                       skip: Tuple[str, ...]) -> Dict[str, np.ndarray]:
        """Shared restore plumbing for the ring-regrow path: validate the
        manifest (ring-elastically when rings differ), apply the regrown
        window, and slice/scatter ring leaves onto the wider ring.  All
        validation happens *before* any engine mutation, so a rejected
        snapshot leaves the engine untouched."""
        snap_w = meta.get("window") or {}
        snap_ring = int(snap_w.get("ring", self.window.ring))
        new_w = (self.window.regrow(max_window_events)
                 if max_window_events is not None else self.window)
        if new_w.ring < snap_ring:
            raise ValueError(
                f"restore(max_window_events={int(max_window_events)}) pads "
                f"to ring {new_w.ring} < snapshot ring {snap_ring} — ring "
                "regrow cannot shrink")
        if snap_ring != new_w.ring:
            self._check_window_elastic(meta, target_ring=new_w.ring)
            skip = skip + ("window",)
        self._check_manifest(meta, skip=skip)
        if new_w.ring != self.window.ring:
            self._apply_ring(new_w)
        if snap_ring != self.window.ring:
            frame = self._ring_migration_frame(meta, arrays)
            arrays = migrate_ring_arrays(
                arrays, snap_ring, self.window.ring, frame)
        return arrays

    def restore(self, snapshot: dict, *, migrate_packing: bool = False,
                max_window_events: Optional[int] = None) -> None:
        """Load a :meth:`snapshot` (or a checkpoint read back through
        ``CheckpointManager.load_arrays``) into this engine.

        Validates the manifest first: query fingerprint, window, chunk
        geometry, and capacities must all match, or the call raises without
        touching state.  After a successful restore the engine continues
        bit-identically to the engine the snapshot was taken from —
        replaying the same chunks yields the same counts, hits, and
        enumerable roots.

        ``migrate_packing=True`` is the repack-aware path (DESIGN.md §11),
        mirroring the elastic ``restore(n_lanes=…)`` idiom: the snapshot
        may come from an engine over a *different packing* of overlapping
        queries — surviving queries' state regions are slice/scattered to
        their new offsets (:func:`migrate_packed_arrays`), so a live fleet
        repack loses no in-flight runs.  Window, chunk geometry and arena
        capacity must still match.

        ``max_window_events=…`` is the ring-regrow path (DESIGN.md §12):
        grow a time window's per-lane rate bound while restoring.  The
        engine re-resolves its window at the new bound (recompiling the
        step once), and the snapshot's ring-indexed leaves are
        slice/scattered onto the wider ring via
        :func:`migrate_ring_arrays` — live starts keep their identity
        (start ``j`` moves to slot ``j mod W1``), surplus slots begin
        empty, and subsequent chunks behave exactly like an engine built
        with the wider bound from the start.  A snapshot from a smaller
        ring also restores into an already-regrown engine without the
        kwarg; shrinking is refused either way.
        """
        meta, arrays = snapshot["meta"], dict(snapshot["arrays"])
        skip: Tuple[str, ...] = ()
        if migrate_packing:
            skip = tuple(self._packing_elastic_keys)
            arrays = dict(self._migrated_arrays(snapshot))
        arrays = self._ring_migrated(meta, arrays, max_window_events, skip)
        self._state = _restore_like(
            "state", self._init_full_state(self.batch), arrays)
        # restored (and possibly packing/ring-migrated) node rows replace
        # the store wholesale — the delta mirror must refetch from row 0
        self._arena_mirror.invalidate()
        self._pos = int(meta["pos"])
        self._last_ts = (np.asarray(arrays["last_ts"], np.float32)
                         if "last_ts" in arrays else None)
        self._restore_roots(arrays)
        self._quarantined = tuple(
            int(b) for b in meta.get("quarantined_lanes", ()))

    def regrow(self, max_window_events: int) -> None:
        """Grow this time window's per-lane rate bound in place.

        Implemented as snapshot → ring-migrating :meth:`restore`, so every
        live start keeps its slot identity and the next :meth:`feed`
        recompiles exactly once.  No-op when the target pads to the
        current ring; raises on count windows and on shrink attempts."""
        if self.window.regrow(max_window_events).ring == self.window.ring:
            return
        self.restore(self.snapshot(), max_window_events=max_window_events)

    def _check_overflow(self) -> None:
        """Post-feed strict-mode gate on the latched rate-bound flags."""
        if not self.strict_overflow:
            return
        ovf = self.window_overflow
        if ovf.any():
            raise wkern.WindowOverflowError(np.nonzero(ovf)[0])

    # ------------------------------------------------------------------
    def feed(self, streams: Sequence[Sequence[Event]]
             ) -> Tuple[np.ndarray, List[Tuple[int, int]]]:
        """Feed one chunk of B streams × chunk_len events.

        Returns ``(counts, hits)``: counts is ``(chunk_len, B)`` int64 match
        counts per position (plus a trailing query axis for a multi-query
        engine); hits is the list of absolute ``(position, stream)`` pairs
        with ≥ 1 match, ready for the host tECS enumerator.

        Time windows (DESIGN.md §9): the per-event timestamp operand is
        encoded from the query's ``time_attr`` / event timestamps (arrival
        order as the fallback) and audited for monotonicity across feeds.
        """
        if self.window.is_time:
            attrs, ts = self.encoder.encode_streams_ts(
                streams, self.window.time_attr, base_pos=self._pos)
            return self.feed_attrs(jnp.asarray(attrs), jnp.asarray(ts))
        attrs = jnp.asarray(self.encoder.encode_streams(streams))
        return self.feed_attrs(attrs)

    def feed_attrs(self, attrs: jnp.ndarray, event_ts=None
                   ) -> Tuple[np.ndarray, List[Tuple[int, int]]]:
        """Device-tensor entry point: attrs (chunk_len, B, A) f32.

        Time windows additionally require ``event_ts (chunk_len, B)`` f32
        (monotone in stream order — audited, including across feeds).
        """
        T, B = attrs.shape[0], attrs.shape[1]
        if T != self.chunk_len or B != self.batch:
            raise ValueError(
                f"streaming chunk must be (chunk_len={self.chunk_len}, "
                f"batch={self.batch}, A); got (T={T}, B={B}).  Pad the tail "
                "chunk on the host or build a second engine for remainders — "
                "odd shapes would trigger a recompile per shape.")
        if self.window.is_time:
            if event_ts is None:
                raise ValueError("time-window feeds need the event_ts "
                                 "(chunk_len, B) operand (DESIGN.md §9)")
            self._last_ts = wkern.audit_monotone_ts(
                np.asarray(event_ts), self._last_ts)
        elif event_ts is not None:
            raise ValueError("event_ts was passed but the query window is "
                             "count-based")
        t0 = self._pos
        if self.arena_capacity is not None and self._pos + T > _I32_MAX:
            raise ValueError(
                f"arena node labels are int32 stream positions; position "
                f"{self._pos + T} exceeds {_I32_MAX}.  reset() the engine "
                "(the arena would long since have overflowed its capacity "
                "anyway — see DESIGN.md §7)")
        with _quiet_donation():
            if self.arena_capacity is not None:
                counts_f, self._state, roots = self._step(
                    attrs, self._state,
                    jnp.asarray(self._pos % self._ring, jnp.int32),
                    jnp.asarray(self._pos, jnp.int32), event_ts)
            else:
                counts_f, self._state = self._step(
                    attrs, self._state,
                    jnp.asarray(self._pos % self._ring, jnp.int32),
                    event_ts)
                roots = None
        self._pos += T
        with TraceAnnotation("engine.readback"):
            if self._single_query:
                counts_f = counts_f[:, :, 0]
            counts = np.asarray(counts_f).astype(np.int64)
            hit_dims = np.nonzero(counts.sum(axis=-1) if counts.ndim == 3
                                  else counts)
            hits = [(t0 + int(t), int(b)) for t, b in zip(*hit_dims)]
            if roots is not None:
                roots_np = np.asarray(roots)
                for p, b in hits:
                    self._roots[(p, b)] = roots_np[p - t0, b]
            self._check_overflow()
        return counts, hits

    # ------------------------------------------------------------------
    # tECS-arena enumeration (requires arena_capacity; DESIGN.md §7)
    # ------------------------------------------------------------------
    def arena_snapshot(self) -> "tecs_arena.ArenaSnapshot":
        """Sync the host mirror with the device arena and snapshot it.

        Node ids are stable across feeds, so one snapshot enumerates every
        hit recorded so far; the sync fetches only rows appended since the
        previous snapshot (delta fetch, DESIGN.md §13)."""
        if self.arena_capacity is None:
            raise ValueError("engine built without arena_capacity — "
                             "no tECS arena to snapshot")
        return self._arena_mirror.sync(self._state["arena"])

    def enumerate(self, position: int, stream: int = 0, query: int = 0,
                  strategy: Optional[str] = None,
                  snapshot: Optional["tecs_arena.ArenaSnapshot"] = None
                  ) -> List[ComplexEvent]:
        """Complex events closing at absolute ``position`` on ``stream``.

        Walks Algorithm 2 over the fetched arena (output-linear delay) — no
        host event replay.  Pass a shared ``snapshot`` when enumerating many
        hits to fetch the arena once.

        ``strategy=None`` (default) enumerates under the query's COMPILED
        semantics: strategy-aware tables keep only the selected runs, so
        the walk is O(matches kept) with no host re-filter (a LAST query
        takes the DFS's leading latest-start group).  An explicit strategy
        is the legacy host post-filter, valid only on plain-ALL engines —
        :func:`tecs_arena.resolve_enum_strategy` raises on a conflict.
        """
        snap = snapshot if snapshot is not None else self.arena_snapshot()
        [ces] = self._enumerate_batch(
            [(int(position), int(stream))], query, strategy, snap)
        return ces

    def _enumerate_batch(self, hits, query, strategy, snap,
                         oracle: bool = False
                         ) -> List[List[ComplexEvent]]:
        """Shared frontier-vectorized walk: one list per (position, stream).

        A compiled-LAST query's matches are exactly the latest-start group,
        which Algorithm 2's prune already selects when the threshold is the
        root's own ``max_start`` — so native LAST costs the same vectorized
        walk with a tighter window, no host re-filter (DESIGN.md §13).
        """
        post = tecs_arena.resolve_enum_strategy(self.engine, strategy)
        latest = (self._latest_q is not None
                  and float(np.asarray(self._latest_q)[query]) > 0.5)
        lanes, roots, ends, thrs = [], [], [], []
        for p, b in hits:
            rec = self._roots.get((int(p), int(b)))
            # NULL root slots appear when a repack migration adds a query
            # after this hit was recorded — nothing to enumerate for it
            root = int(rec[query]) if rec is not None else -1
            lanes.append(int(b))
            roots.append(root)
            ends.append(int(p))
            thrs.append(int(snap.maxs[int(b), root])
                        if latest and root >= 0 else None)
        batches = snap.enumerate_batch(lanes, roots, ends, thrs,
                                       oracle=oracle)
        if post is not None:
            batches = [apply_strategy(post, ces) for ces in batches]
        return batches

    def enumerate_hits(self, hits: Sequence[Tuple[int, int]],
                       query: int = 0, strategy: Optional[str] = None,
                       oracle: bool = False
                       ) -> Dict[Tuple[int, int], List[ComplexEvent]]:
        """Enumerate a batch of ``(position, stream)`` hits with ONE delta
        fetch and ONE frontier-vectorized walk over all roots.

        ``oracle=True`` routes through the per-root Python DFS reference
        (Algorithm 2 as written) instead of the vectorized walk — for
        parity tests and the DFS benchmark baseline."""
        snap = self.arena_snapshot()
        batches = self._enumerate_batch(hits, query, strategy, snap,
                                        oracle=oracle)
        return {(int(p), int(b)): ces
                for (p, b), ces in zip(hits, batches)}

    def clear_roots(self, before: Optional[int] = None) -> int:
        """Forget recorded enumeration roots (host-side bookkeeping).

        The roots dict otherwise grows by one entry per hit for the life of
        the stream; prune it once hits have been enumerated (or will never
        be).  ``before`` drops only roots at positions ``< before``; None
        drops all.  Device state is untouched — reclaiming arena *nodes*
        is ``reset()``'s job.  Returns the number of entries dropped.
        """
        if before is None:
            n = len(self._roots)
            self._roots.clear()
            return n
        # keys are (position, stream) here, bare positions in the
        # partitioned subclass — normalize to the position
        drop = [k for k in self._roots
                if (k[0] if isinstance(k, tuple) else k) < before]
        for k in drop:
            del self._roots[k]
        return len(drop)

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Drop all live runs and rewind the stream position."""
        self._state = self._init_full_state(self.batch)
        self._pos = 0
        self._roots.clear()
        self._arena_mirror.invalidate()
        self._last_ts = None
        self._quarantined = ()
