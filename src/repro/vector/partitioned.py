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
from ..core.partition import (EMPTY_LANE, NULL_KEY_HASH, collision_error,
                              partition_key)
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
    slots_scanned: int = 0       # grid slots the scan ran (lanes x steps)
    # the key pool (KeyPoolEngine) alone:
    keys_live: int = 0           # keys holding a slot after the last chunk
    keys_peak: int = 0           # most keys live after any chunk
    reclaimed: int = 0           # slots freed once their window had passed
    promoted: int = 0            # keys moved from a narrow to a wide ring
    pool_overflow: int = 0       # keys that found no slot in their class


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
                raise collision_error(prev, key, h)
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
            st.slots_scanned += self.num_lanes * self.lane_cap
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


# ----------------------------------------------------------------------
# keyed state pool: thousands of live keys, rings sized per key
# ----------------------------------------------------------------------
class PoolOverflowError(wkern.WindowOverflowError):
    """A key found no free slot in its ring class (``strict_overflow``).

    The chunk was applied without that key's events, so its counts are
    wrong from here on: the service heals it like a ring overflow, by
    restoring the last checkpoint onto a pool whose exhausted class has
    more slots (``restore_kwargs``) and replaying.  ``lanes`` is empty: no
    slot latched, a key had none.
    """

    def __init__(self, classes: Sequence[str], restore_kwargs: dict):
        self.classes = list(classes)
        self.restore_kwargs = dict(restore_kwargs)
        self.lanes = []
        Exception.__init__(
            self, f"key pool exhausted in class(es) {self.classes}: a key "
            "found no free slot and its events were not applied.  Give the "
            f"pool more slots ({self.restore_kwargs}), or drop "
            "strict_overflow to count the loss silently (DESIGN.md §6)")


#: ring classes of the pool, in slot-id order (narrow ids come first)
POOL_CLASSES = ("narrow", "wide")
#: a narrow wave runs this many events of each key, a wide wave this many
#: keys (the rest of each class's grid follows the chunk, KeyPoolEngine)
NARROW_STEPS = 8
WIDE_LANES = 8


def _wave_layout(n_u, member, uid, rank, lanes: int, steps: int):
    """Place one ring class's events on its scan grid.

    A key with ``n`` events this chunk takes ``ceil(n / steps)`` segments,
    segment ``s`` holding its events ``s*steps ...``.  Level ``s`` (every
    key's ``s``-th segment) fills waves of ``lanes`` segments, one key per
    lane, and each level starts a new wave, so a key's segments run in
    order, one wave after another.  Returns each event's wave, lane and
    step, and the number of waves."""
    T = n_u.shape[0]
    levels = jnp.where(member, (n_u + steps - 1) // steps, 0)          # (U,)
    n_lv = -(-T // steps)
    at = levels[:, None] > jnp.arange(n_lv)[None, :]                  # (U, S)
    per_lv = (at.sum(0) + lanes - 1) // lanes                         # waves
    base = jnp.cumsum(per_lv) - per_lv
    rho = jnp.cumsum(at, axis=0) - at                                 # rank
    seg = rank // steps
    r_e = rho[uid, jnp.minimum(seg, n_lv - 1)]
    return (base[jnp.minimum(seg, n_lv - 1)] + r_e // lanes,
            r_e % lanes, rank % steps, per_lv.sum())


class KeyPoolEngine(PartitionedStreamingEngine):
    """PARTITION BY over a pool of key slots in two ring classes.

    State lives in slots, not lanes: ``slots`` narrow slots with a ring of
    ``narrow_ring`` starts and ``wide_slots`` wide slots with the query's
    ring (``max_window_events``).  A key takes a narrow slot when it first
    appears and moves to a wide one, its ring copied in the step, once its
    live window and this chunk's events would outgrow the narrow ring (or
    its events this chunk would take more than two narrow waves).  A slot
    is freed, in the step, once its key's newest event has left the time
    window, so no live partial match is ever dropped (``stats.reclaimed``).
    A key that finds no slot is a counted overflow
    (``stats.pool_overflow``, :class:`PoolOverflowError` under
    ``strict_overflow``), never a silent loss.

    Each chunk scans only the keys it touches: per class, the keys' events
    are laid out on a ``(steps, lanes)`` grid, gathered from the pool,
    scanned and scattered back, with a hot key's events past one row's
    ``steps`` in further waves of the same compiled step
    (``stats.slots_scanned`` counts the grid slots).  The narrow grid is
    ``chunk_len // 4`` keys by ``NARROW_STEPS`` events (a NEXmark chunk of
    512 touches about 130 keys, most with an event or two), the wide one
    ``WIDE_LANES`` keys by ``chunk_len // 8`` events (a few hot keys, one
    with most of the chunk).  Time windows only: reclamation needs the
    stream's clock (DESIGN.md §6).
    """

    _compat_keys = ("format", "engine", "query_fingerprint", "window",
                    "chunk_len", "key_attrs", "num_states", "num_queries",
                    "semantics", "narrow_ring")

    def __init__(self, engine, key_attrs: Sequence[str], chunk_len: int, *,
                 slots: int, wide_slots: int, narrow_ring: int = 128,
                 impl: Optional[str] = None, strict_overflow: bool = False):
        """``engine``: a constructed VectorEngine with a time window; its
        ring (``max_window_events``) is the wide class's.

        slots / wide_slots: keys each class holds at once.
        narrow_ring: the narrow class's ring (a multiple of 8, at most the
                   wide ring).
        """
        if not engine.window.is_time:
            raise ValueError("the key pool reclaims a slot once its key's "
                             "window has passed, so it needs a time window;"
                             " use PartitionedStreamingEngine for count "
                             "windows")
        narrow_ring = int(narrow_ring)
        if narrow_ring % 8 or not 8 <= narrow_ring <= engine.window.ring:
            raise ValueError(f"narrow_ring={narrow_ring} must be a multiple "
                             f"of 8 and at most the wide ring "
                             f"{engine.window.ring}")
        self.slots, self.wide_slots = int(slots), int(wide_slots)
        self.wide_lanes = WIDE_LANES
        self.wide_steps = max(8, int(chunk_len) // 8)
        self._narrow_window = wkern.DeviceWindow(
            "time", engine.window.size, engine.window.time_attr,
            ring=narrow_ring)
        #: hashes the last step freed, dropped from the audit before the
        #: next chunk's (the audit holds live keys only)
        self._reclaimed = np.zeros((0,), np.uint32)
        super().__init__(engine, key_attrs, chunk_len,
                         num_lanes=max(8, int(chunk_len) // 4),
                         lane_cap=NARROW_STEPS, impl=impl, evict="none",
                         strict_overflow=strict_overflow)

    # ------------------------------------------------------------------
    @property
    def narrow_ring(self) -> int:
        return self._narrow_window.ring

    def _plan_routes(self) -> Dict[str, "ops.Route"]:
        """One route per ring class: ``scan`` (narrow) and ``scan_wide``."""
        A = len(self.encoder.attrs)
        common = dict(A=A, S=self._m_all.shape[1], NC=self._m_all.shape[0],
                      NQ=self._finals_q.shape[0],
                      V=self._class_ind.shape[0], timed=True, per_lane=True,
                      latest=self._latest_q is not None,
                      consume=self._consume_sq is not None, impl=self.impl,
                      use_pallas=self._use_pallas, b_tile=self._b_tile)
        return {"scan": ops.plan_pipeline(T=self.lane_cap, B=self.num_lanes,
                                          W=self.narrow_ring, **common),
                "scan_wide": ops.plan_pipeline(
                    T=self.wide_steps, B=self.wide_lanes, W=self._ring,
                    **common)}

    def _make_step(self):
        self.routes = self._plan_routes()
        return jax.jit(self._pool_step_impl, donate_argnums=(2,))

    def _init_lane_state(self):
        S = self._m_all.shape[1]
        P = self.slots + self.wide_slots
        return {
            "C": {"narrow": wkern.init_state(self._narrow_window,
                                             self.slots, S),
                  "wide": wkern.init_state(self.window, self.wide_slots, S)},
            "keys": jnp.full((P,), EMPTY_LANE, jnp.uint32),
            "pos": jnp.zeros((P,), jnp.int32),
            "last": jnp.full((P,), wkern.TS_EMPTY, jnp.float32),
        }

    # ------------------------------------------------------------------
    def _scan_class(self, st, pos, sel, wave, lane, step, slot, n_waves,
                    attrs_pad, ts_pad, counts, *, lanes, steps, window,
                    route):
        """Run one class's waves: gather the wave's slots, scan, scatter
        back.  ``slot`` is each event's slot within the class."""
        T = sel.shape[0]
        Pc = pos.shape[0]
        cells = steps * lanes

        def body(carry):
            w, st, pos, counts = carry
            on = sel & (wave == w)
            cell = jnp.where(on, step * lanes + lane, cells)
            ev = jnp.full((cells + 1,), T, jnp.int32).at[cell].set(
                jnp.arange(T, dtype=jnp.int32))[:cells].reshape(steps, lanes)
            at_lane = jnp.where(on, lane, lanes)
            lane_slot = jnp.full((lanes + 1,), Pc, jnp.int32).at[
                at_lane].set(slot)[:lanes]
            n = jnp.zeros((lanes + 1,), jnp.int32).at[at_lane].add(1)[:lanes]
            g = jnp.minimum(lane_slot, Pc - 1)
            C0 = {k: st[k][g] for k in ("C", "ts", "ovf")}
            p0 = pos[g]
            matches, C1 = ops.cer_pipeline(
                attrs_pad[ev], self._specs, self._class_of, self._class_ind,
                self._m_all, self._finals_q, C0, init_mask=self._init_mask,
                window=window, event_ts=ts_pad[ev], start_pos=p0,
                valid_counts=n, latest_q=self._latest_q,
                consume_sq=self._consume_sq, route=route)
            # a lane with no key holds slot Pc: its writes drop
            st = {k: st[k].at[lane_slot].set(C1[k], mode="drop")
                  for k in st}
            pos = pos.at[lane_slot].set((p0 + n) % window.ring, mode="drop")
            counts = jnp.where(on[:, None], matches[step, lane], counts)
            return w + 1, st, pos, counts

        _, st, pos, counts = jax.lax.while_loop(
            lambda c: c[0] < n_waves, body, (jnp.int32(0), st, pos, counts))
        return st, pos, counts

    def _pool_step_impl(self, attrs: jnp.ndarray, keys: jnp.ndarray, state,
                        event_ts: jnp.ndarray):
        self._trace_count += 1  # runs only while tracing (i.e. compiling)
        T, A = attrs.shape
        Pn, Pw = self.slots, self.wide_slots
        P = Pn + Pw
        Wn, Ww = self.narrow_ring, self.window.ring
        # wide slots a chunk may take: every free one (a chunk's keys may
        # all outgrow their narrow rings at once)
        K = min(Pw, T)
        EMPTY = jnp.uint32(EMPTY_LANE)
        NULL = jnp.uint32(NULL_KEY_HASH)
        ts = jnp.asarray(event_ts, jnp.float32)
        idx = jnp.arange(T, dtype=jnp.int32)

        with jax.named_scope("classify"):
            valid = (keys != NULL) & (keys != EMPTY)
            thr = jnp.where(valid.any(),
                            jnp.min(jnp.where(valid, ts, jnp.inf))
                            - jnp.float32(self.window.size), -jnp.inf)
            # the chunk's distinct keys: a stable sort keeps each key's
            # events in stream order, so rank is the event's place in it
            order = jnp.argsort(jnp.where(valid, keys, NULL), stable=True)
            sk = jnp.where(valid, keys, NULL)[order]
            first = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
            u_sorted = (jnp.cumsum(first) - 1).astype(jnp.int32)
            run0 = jax.lax.cummax(jnp.where(first, idx, 0))
            uid = jnp.zeros((T,), jnp.int32).at[order].set(u_sorted)
            rank = jnp.zeros((T,), jnp.int32).at[order].set(idx - run0)
            ukey = jnp.full((T,), NULL).at[u_sorted].set(sk)
            ukey_ok = ukey != NULL
            n_u = jnp.zeros((T,), jnp.int32).at[uid].add(
                valid.astype(jnp.int32))

        with jax.named_scope("reclaim"):
            # a slot whose key's newest event left the window before this
            # chunk's first event holds no live start: free it
            dead = (state["keys"] != EMPTY) & (state["last"] < thr)
            tk = jnp.where(dead, EMPTY, state["keys"])

        with jax.named_scope("assign"):
            s_keys, s_slot = jax.lax.sort(
                (tk, jnp.arange(P, dtype=jnp.int32)), num_keys=1)
            j = jnp.minimum(jnp.searchsorted(s_keys, ukey), P - 1)
            found = ukey_ok & (s_keys[j] == ukey)
            slot_u = jnp.where(found, s_slot[j], -1)
            narrow_u = found & (slot_u < Pn)
            nar = state["C"]["narrow"]
            live0 = jnp.sum(nar["ts"][jnp.where(narrow_u, slot_u, 0)] >= thr,
                            axis=1)
            wide_u = found & ~narrow_u
            to_wide = ukey_ok & ~wide_u & (
                (jnp.where(narrow_u, live0, 0) + n_u > Wn)
                | (n_u > 2 * self.lane_cap))
            want_n = ukey_ok & ~found & ~to_wide
            free_n = tk[:Pn] == EMPTY
            free_w = tk[Pn:] == EMPTY
            r_n = jnp.cumsum(want_n) - 1
            got_n = want_n & (r_n < free_n.sum())
            slot_n = jnp.nonzero(free_n, size=min(T, Pn), fill_value=Pn)[0][
                jnp.clip(r_n, 0, min(T, Pn) - 1)]
            r_w = jnp.cumsum(to_wide) - 1
            got_w = to_wide & (r_w < jnp.minimum(free_w.sum(), K))
            slot_w = Pn + jnp.nonzero(free_w, size=K, fill_value=Pw)[0][
                jnp.clip(r_w, 0, K - 1)]
            promoted = got_w & narrow_u
            # a key refused a wide slot keeps its narrow one (and latches
            # its ring if it overflows); a new key refused any slot is lost
            slot_f = jnp.where(got_n, slot_n, jnp.where(got_w, slot_w,
                                                        slot_u))
            tk = tk.at[jnp.where(got_n | got_w, slot_f, P)].set(
                ukey, mode="drop")
            tk = tk.at[jnp.where(promoted, slot_u, P)].set(EMPTY,
                                                           mode="drop")

        with jax.named_scope("promote"):
            wide = dict(state["C"]["wide"])
            nar = dict(nar)
            pos = state["pos"]
            last = state["last"]
            # fresh narrow slots start empty
            new_n = jnp.nonzero(got_n, size=min(T, Pn), fill_value=T)[0]
            dst_n = jnp.where(new_n < T, slot_f[jnp.minimum(new_n, T - 1)],
                              Pn)
            nar["C"] = nar["C"].at[dst_n].set(0.0, mode="drop")
            nar["ts"] = nar["ts"].at[dst_n].set(wkern.TS_EMPTY, mode="drop")
            nar["ovf"] = nar["ovf"].at[dst_n].set(False, mode="drop")
            pos = pos.at[jnp.where(dst_n < Pn, dst_n, P)].set(0, mode="drop")
            # wide slots: a promoted key's narrow ring moves start j from
            # slot j mod Wn to j mod Ww in the virtual frame p + Wn (the
            # ring-regrow remap, DESIGN.md §12); a new key starts empty
            new_w = jnp.nonzero(got_w, size=K, fill_value=T)[0]
            ok_w = new_w < T
            u_w = jnp.minimum(new_w, T - 1)
            is_p = ok_w & promoted[u_w]
            src = jnp.where(is_p, slot_u[u_w], 0)
            frame = pos[src] + Wn                                       # (K,)
            k = jnp.arange(Wn)
            start = frame[:, None] - 1 - ((frame[:, None] - 1 - k) % Wn)
            to = start % Ww                                            # (K, Wn)
            rows = jnp.arange(K)[:, None]
            S = nar["C"].shape[-1]
            wC = jnp.zeros((K, Ww, S), jnp.float32).at[rows, to].set(
                nar["C"][src])
            wts = jnp.full((K, Ww), wkern.TS_EMPTY, jnp.float32).at[
                rows, to].set(nar["ts"][src])
            keep = is_p[:, None]
            dst_w = jnp.where(ok_w, slot_f[u_w] - Pn, Pw)
            wide["C"] = wide["C"].at[dst_w].set(
                jnp.where(keep[:, :, None], wC, 0.0), mode="drop")
            wide["ts"] = wide["ts"].at[dst_w].set(
                jnp.where(keep, wts, wkern.TS_EMPTY), mode="drop")
            wide["ovf"] = wide["ovf"].at[dst_w].set(
                is_p & nar["ovf"][src], mode="drop")
            gdst = jnp.where(ok_w, slot_f[u_w], P)
            pos = pos.at[gdst].set(jnp.where(is_p, frame % Ww, 0),
                                   mode="drop")
            last = last.at[gdst].set(
                jnp.where(is_p, last[src], wkern.TS_EMPTY), mode="drop")

        with jax.named_scope("scatter"):
            slot_e = jnp.where(valid, slot_f[uid], -1)
            routed = slot_e >= 0
            is_w = slot_e >= Pn
            attrs_pad = jnp.concatenate([attrs, jnp.zeros((1, A), attrs.dtype)])
            ts_pad = jnp.concatenate([jnp.where(valid, ts, 0.0),
                                      jnp.zeros((1,), jnp.float32)])
            on_n = ukey_ok & (slot_f >= 0) & (slot_f < Pn)
            on_w = ukey_ok & (slot_f >= Pn)
            lay_n = _wave_layout(n_u, on_n, uid, rank, self.num_lanes,
                                 self.lane_cap)
            lay_w = _wave_layout(n_u, on_w, uid, rank, self.wide_lanes,
                                 self.wide_steps)
            last = last.at[jnp.where(routed, slot_e, P)].max(ts, mode="drop")

        NQ = self._finals_q.shape[0]
        counts = jnp.zeros((T, NQ), jnp.float32)
        nar, pos_n, counts = self._scan_class(
            nar, pos[:Pn], routed & ~is_w, *lay_n[:3],
            jnp.minimum(slot_e, Pn - 1), lay_n[3], attrs_pad, ts_pad,
            counts, lanes=self.num_lanes, steps=self.lane_cap,
            window=self._narrow_window, route=self.routes["scan"])
        wide, pos_w, counts = self._scan_class(
            wide, pos[Pn:], is_w, *lay_w[:3], jnp.maximum(slot_e - Pn, 0),
            lay_w[3], attrs_pad, ts_pad, counts, lanes=self.wide_lanes,
            steps=self.wide_steps, window=self.window,
            route=self.routes["scan_wide"])

        with jax.named_scope("relabel"):
            new_state = {"C": {"narrow": nar, "wide": wide}, "keys": tk,
                         "pos": jnp.concatenate([pos_n, pos_w]),
                         "last": last}
            n_slots = (lay_n[3] * (self.num_lanes * self.lane_cap)
                       + lay_w[3] * (self.wide_lanes * self.wide_steps))
            # the counters in one readback: routed, NULL, promoted, lost
            # (narrow, wide), grid slots, live keys, latched slots
            info = jnp.stack([
                routed.sum(), (~valid).sum(), promoted.sum(),
                (want_n & ~got_n).sum(), (to_wide & ~got_w).sum(),
                n_slots, (tk != EMPTY).sum(),
                nar["ovf"].sum() + wide["ovf"].sum()]).astype(jnp.int32)
        return counts, new_state, info, jnp.where(dead, state["keys"], EMPTY)

    # ------------------------------------------------------------------
    def key_values(self, events: Sequence[Event], keys) -> Dict[int, tuple]:
        """The chunk's ``{hash: key}``, raising if two keys of the chunk
        share a hash (the rest of the audit runs in :meth:`feed_keyed`)."""
        out: Dict[int, tuple] = {}
        for ev, h in zip(events, keys):
            key = partition_key(ev, self.key_attrs)
            if key is not None and out.setdefault(int(h), key) != key:
                raise collision_error(out[int(h)], key, h)
        return out

    def _audit(self, key_values: Dict[int, tuple]) -> None:
        """Collision audit among live keys: the table drops a key when its
        slot is reclaimed, so it (and the manifest) holds live keys only."""
        table = self._hash_to_key
        for h in self._reclaimed.tolist():
            table.pop(h, None)
        self._reclaimed = self._reclaimed[:0]
        for h, key in key_values.items():
            prev = table.setdefault(h, key)
            if prev != key:
                raise collision_error(prev, key, h)

    def feed(self, events: Sequence[Event]) -> Tuple[np.ndarray, List[int]]:
        """Feed one chunk of ``chunk_len`` raw interleaved events; see
        :meth:`PartitionedStreamingEngine.feed`.  Events need the query's
        ``time_attr`` or a timestamp: the pool reclaims by the stream's
        clock, so there is no per-key arrival-order fallback."""
        if len(events) != self.chunk_len:
            raise ValueError(f"partitioned chunk must have chunk_len="
                             f"{self.chunk_len} events; got {len(events)}")
        attrs, keys, ts = self.encoder.encode_stream_keyed_ts(
            events, self.key_attrs, self.window.time_attr)
        return self.feed_keyed(attrs, keys, event_ts=ts,
                               key_values=self.key_values(events, keys))

    def feed_keyed(self, attrs: jnp.ndarray, keys: jnp.ndarray,
                   positions: Optional[np.ndarray] = None, event_ts=None,
                   audit_ts: bool = True,
                   key_values: Optional[Dict[int, tuple]] = None
                   ) -> Tuple[np.ndarray, List[int]]:
        """Device-tensor entry point, as the parent's.  ``key_values``
        (the chunk's ``{hash: key}``, :meth:`key_values`) runs the
        collision audit; without it the caller owns that risk."""
        T = attrs.shape[0]
        if T != self.chunk_len or keys.shape != (T,):
            raise ValueError(f"expected attrs (chunk_len={self.chunk_len}, "
                             f"A) and keys ({self.chunk_len},); got "
                             f"{attrs.shape} / {keys.shape}")
        if positions is not None or not audit_ts or event_ts is None:
            raise ValueError("the key pool takes contiguous chunks with the "
                             "stream's own clock (event_ts, monotone): no "
                             "routed positions, no per-key fallback clock")
        with TraceAnnotation("partition.keys"):
            ts_np = np.asarray(event_ts, np.float32)
            keys_np = np.asarray(keys, np.uint32)
            rows = (keys_np != np.uint32(NULL_KEY_HASH)) & \
                (keys_np != np.uint32(EMPTY_LANE))
            if rows.any():
                self._last_ts = wkern.audit_monotone_ts(ts_np[rows],
                                                        self._last_ts)
            if key_values is not None:
                self._audit(key_values)
        base = self._pos
        with _quiet_donation():
            counts_f, self._state, info, rec = self._step(
                attrs, keys, self._state, event_ts)
        self._pos += T
        self._chunk_idx += 1
        with TraceAnnotation("engine.readback"):
            routed, nulls, promoted, lost_n, lost_w, slots, live, ovf = (
                int(x) for x in np.asarray(info))
            rec = np.asarray(rec)
            st = self.stats
            st.reclaimed += int((rec != np.uint32(EMPTY_LANE)).sum())
            # a reclaimed key back in this chunk holds a fresh slot: it
            # stays in the audit
            self._reclaimed = np.asarray(
                [h for h in rec[rec != np.uint32(EMPTY_LANE)].tolist()
                 if key_values is None or h not in key_values], np.uint32)
            st.events += T
            st.routed += routed
            st.dropped_null += nulls
            st.spilled_table += T - routed - nulls
            st.promoted += promoted
            st.pool_overflow += lost_n + lost_w
            st.slots_scanned += slots
            st.keys_live = live
            st.keys_peak = max(st.keys_peak, live)
            st.overflow_lanes = ovf
            st.quarantined_lanes = len(self._quarantined)
            counts = np.asarray(counts_f).astype(np.int64)
            any_q = counts.sum(axis=-1)
            if self._single_query:
                counts = counts[:, 0]
            hits = [base + int(t) for t in np.nonzero(any_q)[0]]
            if self.strict_overflow and (lost_n or lost_w):
                grow = {}
                if lost_n:
                    grow["slots"] = 2 * self.slots
                if lost_w:
                    grow["wide_slots"] = 2 * self.wide_slots
                raise PoolOverflowError(sorted(grow), grow)
            if ovf:
                self._check_overflow()
        return counts, hits

    # ------------------------------------------------------------------
    @property
    def window_overflow(self) -> np.ndarray:
        """Per-slot latched rate-bound flags, narrow slots first."""
        return np.concatenate([np.asarray(self._state["C"][c]["ovf"])
                               for c in POOL_CLASSES])

    @property
    def num_active_lanes(self) -> int:
        """Keys that hold a slot."""
        return int((np.asarray(self._state["keys"])
                    != np.uint32(EMPTY_LANE)).sum())

    def evict_idle(self, min_idle_chunks: int = 1) -> int:
        raise TypeError("the key pool frees a slot in the step, once its "
                        "key's window has passed; there is nothing to evict")

    def manifest(self) -> dict:
        self._audit({})
        m = StreamingVectorEngine.manifest(self)
        m.update({"narrow_ring": self.narrow_ring,
                  "pool": {"slots": self.slots,
                           "wide_slots": self.wide_slots},
                  "key_attrs": list(self.key_attrs),
                  "chunk_idx": int(self._chunk_idx),
                  "stats": asdict(self.stats),
                  "hash_to_key": _encode_hash_to_key(self._hash_to_key)})
        return m

    def snapshot(self) -> dict:
        """Host snapshot of the live pool: per class, the slots that hold a
        key, and of their rings only the starts still live for any event
        to come (older ones would expire at the next event anyway), so a
        checkpoint's bytes follow live state, not the pool's size."""
        st = {}
        _flatten_state("state", self._state, st)
        keys = st["state/keys"]
        arrays: Dict[str, np.ndarray] = {}
        thr = (np.float32(self._last_ts[0]) - np.float32(self.window.size)
               if self._last_ts is not None else np.float32(-np.inf))
        lo = 0
        for c, n in zip(POOL_CLASSES, (self.slots, self.wide_slots)):
            live = np.nonzero(keys[lo:lo + n] != np.uint32(EMPTY_LANE))[0]
            ts = st[f"state/C/{c}/ts"][live]
            row, ring = np.nonzero(ts >= thr)
            arrays.update({
                f"pool/{c}/slot": live.astype(np.int32),
                f"pool/{c}/key": keys[lo + live],
                f"pool/{c}/pos": st["state/pos"][lo + live],
                f"pool/{c}/last": st["state/last"][lo + live],
                f"pool/{c}/ovf": st[f"state/C/{c}/ovf"][live],
                f"pool/{c}/row": row.astype(np.int32),
                f"pool/{c}/ring": ring.astype(np.int32),
                f"pool/{c}/ts": ts[row, ring],
                f"pool/{c}/C": st[f"state/C/{c}/C"][live][row, ring]})
            lo += n
        if self._last_ts is not None:
            arrays["last_ts"] = np.asarray(self._last_ts, np.float32)
        return {"arrays": arrays, "meta": self.manifest()}

    def restore(self, snapshot: dict, *,
                max_window_events: Optional[int] = None,
                slots: Optional[int] = None,
                wide_slots: Optional[int] = None) -> None:
        """Load a :meth:`snapshot`, onto a larger pool (``slots``,
        ``wide_slots``) or a wider wide ring (``max_window_events``) where
        asked: the overflow heal grows only the class that overflowed.
        A smaller pool than the snapshot's live keys is refused."""
        meta, arrays = snapshot["meta"], snapshot["arrays"]
        snap_ring = int((meta.get("window") or {}).get("ring",
                                                       self.window.ring))
        new_w = (self.window.regrow(max_window_events)
                 if max_window_events is not None else self.window)
        skip: Tuple[str, ...] = ()
        if snap_ring != new_w.ring:
            self._check_window_elastic(meta, target_ring=new_w.ring)
            skip = ("window",)
        self._check_manifest(meta, skip=skip)
        # never smaller than the pool the snapshot was taken from (a heal
        # may have grown it before a restart)
        had = meta.get("pool") or {}
        size = {"narrow": max(int(slots or self.slots),
                              int(had.get("slots", 0))),
                "wide": max(int(wide_slots or self.wide_slots),
                            int(had.get("wide_slots", 0)))}
        for c in POOL_CLASSES:
            have = arrays[f"pool/{c}/slot"]
            if len(have) and int(have.max()) >= size[c]:
                raise ValueError(f"snapshot holds {c} slot {int(have.max())}"
                                 f"; the pool has {size[c]}")
        if (size["narrow"], size["wide"]) != (self.slots, self.wide_slots) \
                or new_w.ring != self.window.ring:
            self.slots, self.wide_slots = size["narrow"], size["wide"]
            if new_w.ring != self.window.ring:
                self._apply_ring(new_w)
            else:
                self._trace_count = 0
                self._step = self._make_step()
        st = {}
        _flatten_state("state", self._init_lane_state(), st)
        st = {k: np.array(v) for k, v in st.items()}
        rings = {"narrow": (self.narrow_ring, self.narrow_ring),
                 "wide": (snap_ring, self.window.ring)}
        for c in POOL_CLASSES:
            g = arrays[f"pool/{c}/slot"].astype(np.int64)
            off = 0 if c == "narrow" else self.slots
            pos = arrays[f"pool/{c}/pos"].astype(np.int64)
            row = arrays[f"pool/{c}/row"]
            ring = arrays[f"pool/{c}/ring"].astype(np.int64)
            w0, w1 = rings[c]
            if w1 != w0:
                # the regrow remap in the virtual frame pos + w0 (§12)
                frame = pos + w0
                f = frame[row]
                ring = (f - 1 - ((f - 1 - ring) % w0)) % w1
                pos = frame % w1
            st["state/keys"][off + g] = arrays[f"pool/{c}/key"]
            st["state/pos"][off + g] = pos
            st["state/last"][off + g] = arrays[f"pool/{c}/last"]
            st[f"state/C/{c}/ovf"][g] = arrays[f"pool/{c}/ovf"]
            st[f"state/C/{c}/ts"][g[row], ring] = arrays[f"pool/{c}/ts"]
            st[f"state/C/{c}/C"][g[row], ring] = arrays[f"pool/{c}/C"]
        self._state = _restore_like("state", self._init_lane_state(), st)
        self._pos = int(meta["pos"])
        self._chunk_idx = int(meta["chunk_idx"])
        self._last_ts = (np.asarray(arrays["last_ts"], np.float32)
                         if "last_ts" in arrays else None)
        self.stats = PartitionStats(**meta.get("stats", {}))
        htk = meta.get("hash_to_key")
        self._hash_to_key = ({int(h): tuple(k) for h, k in htk}
                             if htk else {})
        self._reclaimed = self._reclaimed[:0]
        self._quarantined = tuple(
            int(b) for b in meta.get("quarantined_lanes", ()))
        self.stats.quarantined_lanes = len(self._quarantined)

    def regrow(self, max_window_events: Optional[int] = None, *,
               slots: Optional[int] = None,
               wide_slots: Optional[int] = None) -> None:
        """Grow the wide ring and/or a class's slots in place (snapshot →
        :meth:`restore`); the next feed compiles once for the new shapes."""
        self.restore(self.snapshot(), max_window_events=max_window_events,
                     slots=slots, wide_slots=wide_slots)

    def reset(self) -> None:
        super().reset()
        self._reclaimed = self._reclaimed[:0]
