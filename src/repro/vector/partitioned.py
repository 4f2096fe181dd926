"""Device-native PARTITION BY streaming (paper §3/§5.4, DESIGN.md §6).

CORE's PARTITION BY splits the stream into maximal substreams agreeing (and
non-NULL) on the key attributes and runs WHERE-SELECT-WITHIN on each
substream separately.  The host implementation (`core/partition.py`) is a
dict of Python engines — one hash lookup and one Algorithm-1 step per event.
This module is the device-rate equivalent: raw *interleaved* chunks go in,
and one compiled executable per chunk hash-routes every event to a lane,
advances all partitions concurrently, and hands back match counts relabelled
to global stream positions.

Per chunk (all inside one jitted step, state donated):

1. **Lane assignment** — a `lax.scan` over the chunk's key hashes against
   the `(L,)` lane-ownership table: events of a known key go to its lane;
   new keys claim an empty lane, or (policy permitting) **evict** the
   least-recently-used lane that has no events yet this chunk; NULL keys are
   dropped (they join no substream); new keys that find no lane **spill**
   (reported to the host, which may evict + retry or fall back to the host
   engine).
2. **Dense scatter** — events are packed per lane in stream order (the MoE
   bounded-capacity dispatch idiom, cf. `route_by_partition`): lane `b`
   receives a dense prefix of `n_b ≤ lane_cap` events; events beyond
   `lane_cap` spill.
3. **Fused scan** — `ops.cer_pipeline` with *per-lane* `start_pos`
   (substream-local positions, so count-based windows count substream
   events, exactly like the host engine) and per-lane valid counts (padding
   slots are exact no-ops).
4. **Relabelling** — per-slot match counts gather back to the chunk's event
   order; position `base + t` of the global stream gets the count of
   complex events closing at event `t`.  Hit positions are global; with
   ``arena_capacity`` set each lane also maintains its tECS arena in the
   same step (nodes labelled with global positions, DESIGN.md §7) and
   :meth:`enumerate` yields the complex events without event replay.

Key hashing runs in the encoder (`EventEncoder.encode_stream_with_keys`)
with the process-stable 32-bit hash shared with `core/partition.py`; the
engine verifies injectivity on the keys it has seen and raises on a (≈2⁻³²
per pair) hash collision rather than silently merging substreams.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.events import ComplexEvent, Event
from ..core.partition import EMPTY_LANE, NULL_KEY_HASH, partition_key
from ..core.selection import apply_strategy
from ..kernels import ops
from ..kernels import window as wkern
from . import tecs_arena
from .streaming import (StreamingVectorEngine, _flatten_state, _quiet_donation,
                        _restore_like)

_I32_MAX = np.iinfo(np.int32).max

_JSON_KEY_TYPES = (str, int, float, bool)


def _encode_hash_to_key(hash_to_key: Dict[int, tuple]):
    """JSON-able form of the collision-audit table, or None when a key
    carries values JSON cannot round-trip (the audit then restarts fresh
    after restore — safe: it only loses cross-restart collision detection).
    """
    out = []
    for h, key in hash_to_key.items():
        if not all(v is None or isinstance(v, _JSON_KEY_TYPES) for v in key):
            return None
        out.append([int(h), list(key)])
    return out


@dataclass
class PartitionStats:
    """Cumulative routing outcomes across feeds (host-side bookkeeping)."""

    events: int = 0
    routed: int = 0
    dropped_null: int = 0        # NULL partition key → joins no substream
    spilled_table: int = 0       # new key, no free/evictable lane
    spilled_capacity: int = 0    # lane already had lane_cap events this chunk
    evicted_lanes: int = 0       # lanes reassigned to a new key
    overflow_lanes: int = 0      # lanes with the rate-bound ovf latch SET
    #                              (current latch state, not cumulative —
    #                              time windows only, DESIGN.md §9)
    quarantined_lanes: int = 0   # lanes parked mid-overflow-heal (current
    #                              state, mirrors engine.quarantined_lanes —
    #                              snapshot-carried so a crash mid-heal
    #                              resumes the regrow, DESIGN.md §12)


class PartitionedStreamingEngine(StreamingVectorEngine):
    """Compile-once PARTITION BY runtime over the fused device pipeline.

    Unlike the parent (which takes B pre-partitioned streams per feed),
    :meth:`feed` takes ONE interleaved chunk of ``chunk_len`` raw events and
    routes them to ``num_lanes`` partition lanes on device.  Counts/hits come
    back in global stream positions, matching
    ``core.partition.PartitionedEngine`` complex-event-for-complex-event
    (as long as no spill/eviction occurred — both are reported in ``stats``).
    """

    def __init__(self, engine, key_attrs: Sequence[str], chunk_len: int,
                 num_lanes: int, lane_cap: Optional[int] = None,
                 impl: Optional[str] = None, evict: str = "lru",
                 arena_capacity: Optional[int] = None,
                 arena_impl: Optional[str] = None,
                 strict_overflow: bool = False):
        """``engine``: a constructed VectorEngine or MultiQueryEngine.

        key_attrs: PARTITION BY attributes (need not appear in predicates).
        num_lanes: concurrent partitions resident on device (L).
        lane_cap:  per-lane event capacity per chunk; default ``chunk_len``
                   (no capacity spill possible); smaller values trade spill
                   risk for less padded scan work, like MoE capacity factors.
        evict:     "lru" (new keys may evict the least-recently-used lane
                   that is empty this chunk) or "none" (new keys spill when
                   no lane is free).
        arena_capacity: when set, each lane maintains its tECS arena in the
                   same compiled step (nodes labelled with *global* stream
                   positions); hits become enumerable via :meth:`enumerate`
                   without host event replay (DESIGN.md §7).
        """
        # num_lanes before super().__init__: the parent builds the initial
        # state via our _init_full_state override (lane tables + arena in
        # one shot — no throwaway parent-shaped allocation)
        self.num_lanes = int(num_lanes)
        self.lane_cap = int(lane_cap) if lane_cap is not None else chunk_len
        super().__init__(engine, chunk_len, batch=num_lanes, impl=impl,
                         arena_capacity=arena_capacity,
                         arena_impl=arena_impl,
                         strict_overflow=strict_overflow)
        if evict not in ("lru", "none"):
            raise ValueError(f"evict must be 'lru' or 'none', got {evict!r}")
        self.key_attrs = tuple(key_attrs)
        self.evict = evict
        self.stats = PartitionStats()
        self._hash_to_key: Dict[int, tuple] = {}
        # substream-local arrival-order clock (time windows with no
        # time_attr and no event timestamps): events of partition h get
        # timestamp = their post-routing rank in the substream — exactly
        # the host engine's per-partition position clock (DESIGN.md §9)
        self._fallback_clock: Dict[int, int] = {}
        self._chunk_idx = 0
        self._step = self._make_step()

    @property
    def _scan_steps(self) -> int:
        return self.lane_cap

    def _make_step(self):
        self.routes = self._plan_routes(per_lane=True)
        return jax.jit(self._part_step_impl, donate_argnums=(2,))

    # ------------------------------------------------------------------
    def _init_full_state(self, batch: int):
        return self._init_lane_state()

    def _init_lane_state(self):
        st = {
            "C": self.engine.init_state(self.num_lanes),
            "lane_keys": jnp.full((self.num_lanes,), EMPTY_LANE, jnp.uint32),
            "lane_pos": jnp.zeros((self.num_lanes,), jnp.int32),
            "lane_last": jnp.full((self.num_lanes,), -1, jnp.int32),
        }
        if self.arena_capacity is not None:
            st["arena"] = tecs_arena.init_arena(
                self.num_lanes, self.arena_capacity, self._ring,
                self._arena_tables.num_states)
        return st

    # ------------------------------------------------------------------
    def _part_step_impl(self, attrs: jnp.ndarray, keys: jnp.ndarray,
                        state, chunk_idx: jnp.ndarray,
                        positions: jnp.ndarray, event_ts=None):
        self._trace_count += 1  # runs only while tracing (i.e. compiling)
        timed = self.window.is_time
        T, A = attrs.shape
        L, cap = self.num_lanes, self.lane_cap
        lane_ids = jnp.arange(L)

        # --- 1. lane assignment: scan the chunk against the key table -----
        with jax.named_scope("assign"):
            def assign(carry, k):
                lane_keys, touched, lane_last = carry
                # EMPTY_LANE is unreachable from the audited hash path; a raw
                # feed_keyed caller passing it would match every *unowned* lane
                # (lane_keys == k), silently sharing state with whichever
                # partition claims that lane later — treat it as NULL instead
                is_null = (k == jnp.uint32(NULL_KEY_HASH)) | \
                    (k == jnp.uint32(EMPTY_LANE))
                hit = (lane_keys == k) & ~is_null                  # (L,)
                found = hit.any()
                empty = lane_keys == jnp.uint32(EMPTY_LANE)
                has_empty = empty.any()
                idx_empty = jnp.argmax(empty)
                if self.evict == "lru":
                    # evictable: owned lanes with no events yet this chunk
                    evictable = (touched == 0) & ~empty
                    can_evict = evictable.any()
                    lru = jnp.where(evictable, lane_last, _I32_MAX)
                    idx_victim = jnp.argmin(lru)
                else:
                    can_evict = jnp.bool_(False)
                    idx_victim = jnp.int32(0)
                new_lane = jnp.where(has_empty, idx_empty, idx_victim)
                alloc_ok = has_empty | can_evict
                lane = jnp.where(found, jnp.argmax(hit), new_lane).astype(
                    jnp.int32)
                ok = ~is_null & (found | alloc_ok)
                do_alloc = ~is_null & ~found & alloc_ok
                sel = lane_ids == lane
                lane_keys = jnp.where(do_alloc & sel, k, lane_keys)
                touched = touched + (sel & ok).astype(jnp.int32)
                lane_last = jnp.where(sel & ok, chunk_idx, lane_last)
                lane_out = jnp.where(ok, lane, jnp.int32(L))
                return (lane_keys, touched, lane_last), (lane_out, ok, is_null)

            carry0 = (state["lane_keys"], jnp.zeros((L,), jnp.int32),
                      state["lane_last"])
            (lane_keys, _touched, lane_last), (lanes, routed, nulls) = \
                jax.lax.scan(assign, carry0, keys)

            # lanes whose owner changed were evicted: their partition restarts
            # from scratch if its key ever returns (fresh state, local pos 0)
            evicted = (lane_keys != state["lane_keys"]) & \
                (state["lane_keys"] != jnp.uint32(EMPTY_LANE))
            if timed:
                Cst = state["C"]
                C = {"C": jnp.where(evicted[:, None, None], 0.0, Cst["C"]),
                     "ts": jnp.where(evicted[:, None],
                                     jnp.float32(wkern.TS_EMPTY), Cst["ts"]),
                     "ovf": jnp.where(evicted, False, Cst["ovf"])}
            else:
                C = jnp.where(evicted[:, None, None], 0.0, state["C"])
            lane_pos = jnp.where(evicted, 0, state["lane_pos"])

        # --- 2. dense scatter: pack each lane's events in stream order ----
        with jax.named_scope("scatter"):
            onehot = (lanes[:, None] == jnp.arange(L + 1)[None, :]
                      ).astype(jnp.int32)                          # (T, L+1)
            rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0),
                                       lanes[:, None], axis=1)[:, 0] - 1
            keep = routed & (rank < cap)
            spilled = routed & ~keep                            # over capacity
            slot = jnp.where(keep, lanes * cap + rank, L * cap)    # dummy tail
            buf = jnp.zeros((L * cap + 1, A), attrs.dtype).at[slot].set(attrs)
            attrs_lanes = jnp.moveaxis(
                buf[:L * cap].reshape(L, cap, A), 0, 1)           # (cap, L, A)
            n = (onehot[:, :L] * keep[:, None].astype(jnp.int32)).sum(0)
            ts_lanes = None
            if timed:
                # per-lane timestamps ride the same routing scatter as the
                # attributes (DESIGN.md §9); padding rows are dead steps and
                # never consult their (zero) timestamp
                tsbuf = jnp.zeros((L * cap + 1,), jnp.float32).at[slot].set(
                    jnp.asarray(event_ts, jnp.float32))
                ts_lanes = jnp.moveaxis(
                    tsbuf[:L * cap].reshape(L, cap), 0, 1)         # (cap, L)

        # --- 3. fused scan at per-lane substream positions ----------------
        with_arena = self.arena_capacity is not None
        ts_ring0 = C["ts"] if timed else None
        pipe = ops.cer_pipeline(
            attrs_lanes, self._specs, self._class_of, self._class_ind,
            self._m_all, self._finals_q, C, init_mask=self._init_mask,
            window=self.window, event_ts=ts_lanes,
            start_pos=lane_pos, valid_counts=n, return_trace=with_arena,
            latest_q=self._latest_q, consume_sq=self._consume_sq,
            route=self.routes["scan"])                         # (cap, L, Q)
        matches, C = pipe[0], pipe[1]

        # --- 4. relabel: routed-slot counts → chunk event order -----------
        with jax.named_scope("relabel"):
            NQ = matches.shape[-1]
            mm = jnp.concatenate(
                [jnp.moveaxis(matches, 0, 1).reshape(L * cap, NQ),
                 jnp.zeros((1, NQ), matches.dtype)])            # dummy row = 0
            counts_chunk = mm[slot]                                # (T, Q)

            # positions are only consumed mod W (ring slots), so the carried
            # per-lane position wraps mod W — exact, and int32 never
            # overflows however long a substream runs
            new_state = {"C": C, "lane_keys": lane_keys,
                         "lane_pos": (lane_pos + n) % self.engine.ring,
                         "lane_last": lane_last}
            info = {"routed": routed, "nulls": nulls, "spilled": spilled,
                    "evicted": evicted, "lane_fill": n,
                    "lanes": jnp.where(keep, lanes, jnp.int32(L))}

        # --- 5. tECS arena: per-lane node stores, global position labels --
        if with_arena:
            with jax.named_scope("arena"):
                trace = pipe[2]                                    # (cap, L)
                arena = dict(state["arena"])
                # an evicted lane's partition restarts: its cells are garbage
                arena["cell"] = jnp.where(evicted[:, None, None],
                                          tecs_arena.NULL, arena["cell"])
                posbuf = jnp.full((L * cap + 1,), -1, jnp.int32).at[slot].set(
                    jnp.asarray(positions, jnp.int32))
                gpos_lanes = jnp.moveaxis(
                    posbuf[:L * cap].reshape(L, cap), 0, 1)        # (cap, L)
                expire = (tecs_arena.window_expire_masks(
                    self.window, ts_ring0, ts_lanes, lane_pos, n)
                    if timed else None)
                # the arena runs on LIVE dims; padded query/state tails of a
                # fleet-style packing are dead by construction, so slicing the
                # hit mask and consume rows to them is exact (cf. scan_chunk)
                Qa = self._arena_tables.num_queries
                hitsq = (matches > 0.5)[..., :Qa]
                # CONSUME BY ANY rides the routed lanes exactly like the parent
                # (scan_chunk): any matching query clears its own cell-table
                # block after the step's roots are recorded (DESIGN.md D2)
                consume = None
                if self._consume_sq is not None:
                    consume = jnp.einsum(
                        "tbq,qs->tbs", hitsq.astype(jnp.float32),
                        jnp.asarray(self._consume_sq, jnp.float32)
                        [:Qa, :self._arena_tables.num_states],
                        precision=jax.lax.Precision.HIGHEST) > 0.5
            arena, roots = tecs_arena.run_arena_scan(
                self._arena_tables, arena, trace, gpos_lanes,
                lane_pos, n, hitsq, epsilon=self.epsilon,
                expire=expire, consume=consume,
                arena_impl=self.arena_impl)
            with jax.named_scope("relabel"):
                rr = jnp.concatenate(
                    [jnp.moveaxis(roots, 0, 1).reshape(L * cap, Qa),
                     jnp.full((1, Qa), tecs_arena.NULL, jnp.int32)])
                new_state["arena"] = arena
                info["roots"] = rr[slot]                           # (T, Q)
        return counts_chunk, new_state, info

    # ------------------------------------------------------------------
    def feed(self, events: Sequence[Event]
             ) -> Tuple[np.ndarray, List[int]]:
        """Feed one chunk of ``chunk_len`` raw interleaved events.

        Returns ``(counts, hits)``: counts is ``(chunk_len,)`` int64 match
        counts per *global* stream position (trailing query axis for a
        multi-query engine); hits is the sorted list of absolute positions
        with ≥ 1 match, ready for the host tECS enumerator.
        """
        if len(events) != self.chunk_len:
            raise ValueError(
                f"partitioned chunk must have chunk_len={self.chunk_len} "
                f"events; got {len(events)}.  Pad the tail chunk on the host "
                "— odd shapes would trigger a recompile per shape.")
        audit_ts = True
        if self.window.is_time:
            attrs, keys, ts = self.encoder.encode_stream_keyed_ts(
                events, self.key_attrs, self.window.time_attr,
                clock=(self._fallback_clock
                       if self.window.time_attr is None else None))
            if self.window.time_attr is None and any(
                    ev.timestamp is None for ev in events
                    if partition_key(ev, self.key_attrs) is not None):
                # synthesized substream-local clocks are monotone per lane
                # by construction but NOT across the interleaved stream —
                # the global-order audit does not apply (DESIGN.md §9)
                audit_ts = False
        else:
            attrs, keys = self.encoder.encode_stream_with_keys(
                events, self.key_attrs)
            ts = None
        for ev, h in zip(events, keys):       # audit reuses encoder hashes
            key = partition_key(ev, self.key_attrs)
            if key is None:
                continue
            prev = self._hash_to_key.setdefault(int(h), key)
            if prev != key:
                raise ValueError(
                    f"partition hash collision: {prev!r} and {key!r} both "
                    f"hash to {int(h):#x}; routing would merge their "
                    "substreams")
        return self.feed_keyed(jnp.asarray(attrs), jnp.asarray(keys),
                               event_ts=None if ts is None
                               else jnp.asarray(ts), audit_ts=audit_ts)

    def feed_keyed(self, attrs: jnp.ndarray, keys: jnp.ndarray,
                   positions: Optional[np.ndarray] = None,
                   event_ts=None, audit_ts: bool = True
                   ) -> Tuple[np.ndarray, List[int]]:
        """Device-tensor entry point: attrs (chunk_len, A) f32 + uint32 keys.

        Skips the host-side collision audit — callers hashing their own keys
        own that risk.  ``positions`` (optional, (chunk_len,) int) gives the
        global stream position of each fed row — the sharded path feeds the
        rows `route_partitioned_chunk` delivered to this shard, which are a
        non-contiguous slice of the stream; hits are labelled from it.
        ``event_ts`` ((chunk_len,) f32) is required for time windows: each
        event's timestamp rides the routing scatter to its lane
        (DESIGN.md §9).  The interleaved stream must be monotone in time
        (audited across feeds) — which makes every routed substream
        monotone too, the host PartitionedEngine's assumption.
        """
        T = attrs.shape[0]
        if T != self.chunk_len or keys.shape != (T,):
            raise ValueError(f"expected attrs (chunk_len={self.chunk_len}, "
                             f"A) and keys ({self.chunk_len},); got "
                             f"{attrs.shape} / {keys.shape}")
        if self.window.is_time:
            if event_ts is None:
                raise ValueError("time-window partitioned feeds need the "
                                 "event_ts (chunk_len,) operand "
                                 "(DESIGN.md §9)")
            if positions is None and audit_ts:
                # routed (sharded) sub-chunks interleave bucket padding and
                # out-of-order senders — like the collision audit, callers
                # feeding pre-routed rows own the monotonicity guarantee.
                # NULL-key rows join no substream (the host drops them
                # before reading a clock), so they are exempt too — their
                # placeholder timestamps never reach a lane.
                ts_np = np.asarray(event_ts, np.float32)
                keys_np = np.asarray(keys, np.uint32)
                routed_rows = (keys_np != np.uint32(NULL_KEY_HASH)) & \
                    (keys_np != np.uint32(EMPTY_LANE))
                if routed_rows.any():
                    self._last_ts = wkern.audit_monotone_ts(
                        ts_np[routed_rows], self._last_ts)
        elif event_ts is not None:
            raise ValueError("event_ts was passed but the query window is "
                             "count-based")
        base = self._pos
        if positions is None:
            pos_arr = base + np.arange(T, dtype=np.int64)
        else:
            pos_arr = np.asarray(positions, dtype=np.int64)
        if self.arena_capacity is not None and \
                int(pos_arr.max(initial=0)) > _I32_MAX:
            raise ValueError(
                f"arena node labels are int32 stream positions; position "
                f"{int(pos_arr.max())} exceeds {_I32_MAX}.  reset() the "
                "engine (see DESIGN.md §7)")
        pos_arr = pos_arr.astype(np.int32)
        with _quiet_donation():
            counts_f, self._state, info = self._step(
                attrs, keys, self._state,
                jnp.asarray(self._chunk_idx, jnp.int32),
                jnp.asarray(pos_arr), event_ts)
        self._pos += T
        self._chunk_idx += 1

        with TraceAnnotation("engine.readback"):
            st = self.stats
            st.events += T
            st.dropped_null += int(np.asarray(info["nulls"]).sum())
            st.spilled_capacity += int(np.asarray(info["spilled"]).sum())
            st.routed += int(np.asarray(info["lane_fill"]).sum())
            st.spilled_table += T - int(np.asarray(info["routed"]).sum()) \
                - int(np.asarray(info["nulls"]).sum())
            st.evicted_lanes += int(np.asarray(info["evicted"]).sum())
            st.overflow_lanes = int(self.window_overflow.sum())  # latch
            st.quarantined_lanes = len(self._quarantined)

            counts = np.asarray(counts_f).astype(np.int64)     # (T, Q)
            any_q = counts.sum(axis=-1)
            if self._single_query:
                counts = counts[:, 0]
            if self.arena_capacity is not None:
                roots_np = np.asarray(info["roots"])
                lanes_np = np.asarray(info["lanes"])
                for t in np.nonzero(any_q)[0]:
                    self._roots[int(pos_arr[t])] = (int(lanes_np[t]),
                                                    roots_np[t])
            if positions is None:
                hits = [base + int(t) for t in np.nonzero(any_q)[0]]
            else:
                hits = sorted(int(positions[t])
                              for t in np.nonzero(any_q)[0])
            self._check_overflow()
        return counts, hits

    # ------------------------------------------------------------------
    # tECS-arena enumeration at global positions (DESIGN.md §7)
    # ------------------------------------------------------------------
    def enumerate(self, position: int, *, query: int = 0,
                  strategy: Optional[str] = None, snapshot=None
                  ) -> List[ComplexEvent]:
        """Complex events closing at global ``position`` — start/end/data
        are global stream positions, matching the host
        ``PartitionedEngine``'s relabelled output.  No event replay: the
        arena nodes were labelled with global positions as they were built.

        ``strategy=None`` (default) enumerates under the query's COMPILED
        semantics (see the parent class); an explicit strategy is the
        legacy host post-filter, valid only on plain-ALL engines.

        Unlike the parent (B pre-partitioned streams, ``(position,
        stream)``), the partitioned engine has ONE interleaved stream, so
        there is no ``stream`` argument; everything past ``position`` is
        keyword-only to keep parent-style positional calls from silently
        landing in ``query``.
        """
        if not isinstance(position, (int, np.integer)):
            raise TypeError(
                f"position must be a global stream position (int), got "
                f"{position!r} — the partitioned engine has no stream axis")
        snap = snapshot if snapshot is not None else self.arena_snapshot()
        [ces] = self._enumerate_batch([int(position)], query, strategy, snap)
        return ces

    def _enumerate_batch(self, hits, query, strategy, snap,
                         oracle: bool = False
                         ) -> List[List[ComplexEvent]]:
        """Frontier-vectorized walk over global hit positions (the keys of
        ``_roots`` are bare positions here; each record carries its lane)."""
        post = tecs_arena.resolve_enum_strategy(self.engine, strategy)
        latest = (self._latest_q is not None
                  and float(np.asarray(self._latest_q)[query]) > 0.5)
        lanes, roots, ends, thrs = [], [], [], []
        for p in hits:
            rec = self._roots.get(int(p))
            # NULL root slots appear when a repack migration adds a query
            # after this hit was recorded — nothing to enumerate for it
            root = int(rec[1][query]) if rec is not None else -1
            lanes.append(int(rec[0]) if rec is not None else 0)
            roots.append(root)
            ends.append(int(p))
            thrs.append(int(snap.maxs[lanes[-1], root])
                        if latest and root >= 0 else None)
        batches = snap.enumerate_batch(lanes, roots, ends, thrs,
                                       oracle=oracle)
        if post is not None:
            batches = [apply_strategy(post, ces) for ces in batches]
        return batches

    def enumerate_hits(self, hits: Sequence[int], *, query: int = 0,
                       strategy: Optional[str] = None,
                       oracle: bool = False):
        """Enumerate a batch of global hit positions with ONE delta fetch
        and ONE frontier-vectorized walk over all roots."""
        snap = self.arena_snapshot()
        batches = self._enumerate_batch(hits, query, strategy, snap,
                                        oracle=oracle)
        return {int(p): ces for p, ces in zip(hits, batches)}

    # ------------------------------------------------------------------
    def feed_attrs(self, attrs):
        """Unsupported on the partitioned engine (parent-class API).

        The partitioned step needs per-event key hashes alongside the
        attribute rows — use :meth:`feed` (raw events) or
        :meth:`feed_keyed` (pre-encoded attrs + uint32 hashes).
        """
        raise TypeError("PartitionedStreamingEngine routes by key: use "
                        "feed(events) or feed_keyed(attrs, keys) instead of "
                        "feed_attrs")

    @property
    def state(self):
        """Current device state: ``{C (L, W, S), lane_keys (L,), lane_pos
        (L,), lane_last (L,)}``.

        Donated to the next :meth:`feed` — copy leaves before feeding if
        you need a snapshot (see the parent class note on donation).
        """
        return self._state

    @property
    def num_active_lanes(self) -> int:
        """Lanes currently owned by a partition."""
        lk = np.asarray(self._state["lane_keys"])
        return int((lk != np.uint32(EMPTY_LANE)).sum())

    def evict_idle(self, min_idle_chunks: int = 1) -> int:
        """Free lanes whose partition saw no events for ≥ N chunks.

        Cold-path host surgery on the device state (streaming hot path stays
        compile-once).  A lane whose partition appeared in the most recent
        chunk has been idle for 0 chunks.  Evicted partitions restart from
        scratch if their key returns.  Returns the number of lanes freed.
        """
        lk = np.asarray(self._state["lane_keys"])
        ll = np.asarray(self._state["lane_last"])
        ev = (lk != np.uint32(EMPTY_LANE)) & \
            (self._chunk_idx - 1 - ll >= min_idle_chunks)
        n = int(ev.sum())
        if n == 0:
            return 0
        if self.window.is_time:
            Cst = self._state["C"]
            Cr = np.asarray(Cst["C"]).copy()
            tsr = np.asarray(Cst["ts"]).copy()
            ovf = np.asarray(Cst["ovf"]).copy()
            Cr[ev] = 0.0
            tsr[ev] = wkern.TS_EMPTY
            ovf[ev] = False
            C = {"C": jnp.asarray(Cr), "ts": jnp.asarray(tsr),
                 "ovf": jnp.asarray(ovf)}
        else:
            Cr = np.asarray(self._state["C"]).copy()
            Cr[ev] = 0.0
            C = jnp.asarray(Cr)
        lp = np.asarray(self._state["lane_pos"]).copy()
        lp[ev] = 0
        lk = lk.copy()
        ll = ll.copy()
        lk[ev] = np.uint32(EMPTY_LANE)
        ll[ev] = -1
        new_state = {"C": C, "lane_keys": jnp.asarray(lk),
                     "lane_pos": jnp.asarray(lp),
                     "lane_last": jnp.asarray(ll)}
        if self.arena_capacity is not None:
            # evicted partitions restart from scratch: their cell rows are
            # garbage.  Already-built nodes (and recorded roots) stay valid —
            # the bump allocator never recycles ids (DESIGN.md §7).
            arena = dict(self._state["arena"])
            cell = np.asarray(arena["cell"]).copy()
            cell[ev] = tecs_arena.NULL
            arena["cell"] = jnp.asarray(cell)
            new_state["arena"] = arena
        self._state = new_state
        self.stats.evicted_lanes += n
        return n

    # ------------------------------------------------------------------
    # crash-safe snapshots + elastic lane rescale (DESIGN.md §10)
    # ------------------------------------------------------------------
    # "batch"/"num_lanes" are deliberately NOT compatibility keys: the lane
    # count is the *elastic* dimension — restore migrates lane rows instead
    # of rejecting the snapshot.  lane_cap and the PARTITION BY key set are
    # load-bearing (they shape routing), so they are.
    _compat_keys = ("format", "engine", "query_fingerprint", "window",
                    "chunk_len", "lane_cap", "key_attrs", "num_states",
                    "num_queries", "arena_capacity", "semantics")

    def manifest(self) -> dict:
        m = super().manifest()
        m.update({
            "num_lanes": int(self.num_lanes),
            "lane_cap": int(self.lane_cap),
            "evict": self.evict,
            "key_attrs": list(self.key_attrs),
            "chunk_idx": int(self._chunk_idx),
            "stats": asdict(self.stats),
            "hash_to_key": _encode_hash_to_key(self._hash_to_key),
            "fallback_clock": {str(h): int(n)
                               for h, n in self._fallback_clock.items()},
        })
        return m

    def _snapshot_roots(self, arrays: Dict[str, np.ndarray]) -> None:
        # keys are bare global positions here; each value carries the lane
        # the root lives on, which a rescaled restore must remap
        keys = sorted(self._roots)
        if keys:
            arrays["roots_key"] = np.asarray(keys, np.int64)
            arrays["roots_lane"] = np.asarray(
                [self._roots[k][0] for k in keys], np.int32)
            arrays["roots_val"] = np.stack(
                [np.asarray(self._roots[k][1], np.int32) for k in keys])

    def _restore_roots(self, arrays: Dict[str, np.ndarray],
                       lane_map: Optional[Dict[int, int]] = None) -> int:
        self._roots.clear()
        if "roots_key" not in arrays:
            return 0
        dropped = 0
        for p, l, v in zip(arrays["roots_key"], arrays["roots_lane"],
                           arrays["roots_val"]):
            lane = int(l)
            if lane_map is not None:
                lane = lane_map.get(lane, -1)
                if lane < 0:         # root's lane was dropped by the shrink
                    dropped += 1
                    continue
            self._roots[int(p)] = (lane, np.asarray(v, np.int32))
        return dropped

    def _ring_migration_frame(self, meta: dict,
                              arrays: Dict[str, np.ndarray]) -> np.ndarray:
        """Per-lane virtual frame for the ring remap (DESIGN.md §12).

        Lane cursors are carried mod the old ring, so the absolute per-lane
        position is unknown; any representative congruent mod W0 yields the
        same slot↔start pairing, and ``lane_pos + W0`` makes every old slot
        a valid (non-negative) start.  The cursor is rewritten into the new
        ring's frame in place, so post-restore seeding stays consistent
        with the migrated slots — match *sets* are rotation-invariant even
        though the frame is virtual."""
        old_ring = int((meta.get("window") or {}).get("ring",
                                                      self.window.ring))
        lp = np.asarray(arrays["state/lane_pos"], np.int64)
        arrays["state/lane_pos"] = (
            (lp + old_ring) % self.window.ring).astype(np.int32)
        return lp + old_ring

    def quarantine(self, lanes: Sequence[int]) -> None:
        super().quarantine(lanes)
        self.stats.quarantined_lanes = len(self._quarantined)

    def clear_quarantine(self) -> None:
        super().clear_quarantine()
        self.stats.quarantined_lanes = 0

    def restore(self, snapshot: dict, *,
                n_lanes: Optional[int] = None,
                migrate_packing: bool = False,
                max_window_events: Optional[int] = None) -> None:
        """Load a :meth:`snapshot`, optionally rescaling to ``n_lanes``.

        The lane count is the elastic dimension: a snapshot taken at L0
        lanes restores onto L1 ≠ L0 by row-gathering every per-lane state
        leaf (count/timestamp rings, lane table, LRU ages, arena rows) onto
        the new lane axis — see :meth:`_migrate_lanes` for the priority
        order when shrinking.  ``n_lanes`` rebuilds the compiled step for
        the new geometry (a rescale is a restart event: exactly one fresh
        compile, after which ``compile_count == 1`` streaming resumes).
        ``migrate_packing=True`` additionally remaps the packed state axis
        between query packings (repack-aware restore, DESIGN.md §11) — it
        composes with a lane rescale: the state-axis migration runs first
        (it preserves the lane axis), then lanes are gathered.
        ``max_window_events=…`` regrows the time-window rate bound during
        the restore (ring slice/scatter, parent-class docs + DESIGN.md
        §12); it runs after the packing migration and before the lane
        gather, since ring leaves keep the lane axis.  Everything else in
        the manifest must match or the call raises without touching state.
        """
        meta, arrays = snapshot["meta"], dict(snapshot["arrays"])
        if n_lanes is not None and int(n_lanes) != self.num_lanes:
            # lane count is a compiled shape: re-jit for the new geometry
            self.num_lanes = int(n_lanes)
            self.batch = int(n_lanes)
            self._trace_count = 0
            self._step = self._make_step()
        skip: Tuple[str, ...] = ()
        if migrate_packing:
            skip = tuple(self._packing_elastic_keys)
            arrays = dict(self._migrated_arrays(
                {"meta": meta, "arrays": arrays}))
        arrays = self._ring_migrated(meta, arrays, max_window_events, skip)
        lane_map = None
        dropped_owned = 0
        src_lanes = int(meta.get("num_lanes", self.num_lanes))
        if src_lanes != self.num_lanes:
            arrays, lane_map, dropped_owned = self._migrate_lanes(
                arrays, src_lanes)
        self._state = _restore_like("state", self._init_lane_state(), arrays)
        # restored / lane-gathered node rows replace the store wholesale —
        # the delta mirror must refetch from row 0 (DESIGN.md §13)
        self._arena_mirror.invalidate()
        self._pos = int(meta["pos"])
        self._chunk_idx = int(meta["chunk_idx"])
        self._last_ts = (np.asarray(arrays["last_ts"], np.float32)
                         if "last_ts" in arrays else None)
        self.stats = PartitionStats(**meta.get("stats", {}))
        self.stats.evicted_lanes += dropped_owned
        htk = meta.get("hash_to_key")
        self._hash_to_key = ({int(h): tuple(k) for h, k in htk}
                             if htk else {})
        self._fallback_clock = {int(h): int(n) for h, n in
                                meta.get("fallback_clock", {}).items()}
        self._restore_roots(arrays, lane_map)
        q = [int(b) for b in meta.get("quarantined_lanes", ())]
        if lane_map is not None:   # rescale: follow the parked lanes
            q = [lane_map[b] for b in q if b in lane_map]
        self._quarantined = tuple(sorted(q))
        self.stats.quarantined_lanes = len(self._quarantined)

    def _migrate_lanes(self, arrays: Dict[str, np.ndarray], src_lanes: int
                       ) -> Tuple[Dict[str, np.ndarray],
                                  Dict[int, int], int]:
        """Row-gather per-lane snapshot leaves onto this engine's lane axis.

        Every state leaf carries the lane as its leading axis (rings, lane
        table, LRU ages, all arena planes), so a rescale is one gather.
        Candidates to keep: lanes owned by a partition, then unowned lanes
        that still hold arena history (``ptr > 0`` — their nodes back
        already-recorded roots).  When shrinking, owned lanes win by recency
        (``lane_last`` descending); dropped owned lanes count as evictions —
        their partitions restart from scratch if the key returns, and their
        unenumerated roots become unenumerable (DESIGN.md §10).  Kept lanes
        stay in relative order, so the migration is deterministic.
        """
        dst = self.num_lanes
        lk = arrays.get("state/lane_keys")
        ll = arrays.get("state/lane_last")
        if lk is None or ll is None or np.shape(lk) != (src_lanes,):
            raise ValueError(
                f"snapshot lane table does not match its manifest "
                f"num_lanes={src_lanes}")
        owned = np.asarray(lk) != np.uint32(EMPTY_LANE)
        hist = np.zeros(src_lanes, bool)
        ptr = arrays.get("state/arena/ptr")
        if self.arena_capacity is not None and ptr is not None:
            hist = np.asarray(ptr) > 0
        ll = np.asarray(ll)
        order = sorted(np.nonzero(owned | hist)[0],
                       key=lambda i: (0 if owned[i] else 1,
                                      -int(ll[i]), int(i)))
        keep = sorted(int(i) for i in order[:dst])
        dropped_owned = int(sum(1 for i in order[dst:] if owned[i]))
        lane_map = {o: i for i, o in enumerate(keep)}
        tmpl: Dict[str, np.ndarray] = {}
        _flatten_state("state", self._init_lane_state(), tmpl)
        out = {k: v for k, v in arrays.items()
               if not k.startswith("state/")}
        idx = np.asarray(keep, np.int64)
        for key, tv in tmpl.items():
            old = arrays.get(key)
            if old is None:
                raise ValueError(f"snapshot is missing state leaf {key!r}")
            if old.shape[1:] != tv.shape[1:] or old.dtype != tv.dtype:
                raise ValueError(
                    f"snapshot state leaf {key!r} is {old.shape}/"
                    f"{old.dtype}; rescale expects trailing dims "
                    f"{tv.shape[1:]}/{tv.dtype}")
            new = np.array(tv)           # init values on surplus new lanes
            new[:len(idx)] = old[idx]
            out[key] = new
        return out, lane_map, dropped_owned

    def reset(self) -> None:
        """Drop all partitions and rewind the stream position."""
        self._state = self._init_lane_state()
        self._pos = 0
        self._chunk_idx = 0
        self._hash_to_key.clear()
        self._fallback_clock.clear()
        self._roots.clear()
        self._arena_mirror.invalidate()
        self._last_ts = None
        self._quarantined = ()
        self.stats = PartitionStats()
