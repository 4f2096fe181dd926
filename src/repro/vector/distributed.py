"""Distributed CER: partition-by sharded across the device mesh.

The paper leaves parallel/distributed execution as future work (§7); this
module provides it.  Three pieces:

* :func:`sharded_cea_scan` — the windowed counting scan with the stream/batch
  axis sharded over every mesh axis (partitions are independent, so the scan
  itself needs **no** collectives — the ideal scaling case the partition-by
  operator exposes).
* :func:`sharded_cer_pipeline` — the fused single-pass pipeline
  (attrs → bits → class → scan, :func:`repro.kernels.ops.cer_pipeline`)
  sharded the same way: tables replicated, streams sharded, still zero
  collectives, and ``start_pos`` stays a dynamic operand so chunked /
  streaming callers reuse one executable per mesh.
* :func:`route_by_partition` — the event router: incoming event blocks carry a
  partition hash; an ``all_to_all`` moves each event to the shard that owns
  its partition.  This is the one collective of the distributed engine and is
  exercised by the multi-pod dry-run.
"""
from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..kernels import ops


def stream_axes(mesh: Mesh) -> Tuple[str, ...]:
    """All mesh axes — CER shards streams over the full device grid."""
    return tuple(mesh.axis_names)


def sharded_cea_scan(mesh: Mesh, class_ids, m_all, finals, c0, *,
                     epsilon: int, start_pos: Union[int, jnp.ndarray] = 0,
                     use_pallas: bool = False):
    """Shard the B axis of the scan over every mesh axis via shard_map.

    class_ids (T, B) | m_all, finals replicated | c0 (B, W, S) sharded on B.
    ``start_pos`` is a replicated dynamic operand (chunk offset).
    """
    axes = stream_axes(mesh)

    def local_scan(ids, m, f, c, sp):
        return ops.cea_scan(ids, m, f, c, epsilon=epsilon,
                            start_pos=sp[0], use_pallas=use_pallas)

    return jax.shard_map(
        local_scan, mesh=mesh,
        in_specs=(P(None, axes), P(), P(), P(axes), P()),
        out_specs=(P(None, axes), P(axes)), check_vma=False,
    )(class_ids, m_all, finals, c0, ops._start_arr(start_pos))


def sharded_cer_pipeline(mesh: Mesh, attrs, specs, class_of, class_ind,
                         m_all, finals_q, c0, *, init_mask, epsilon: int,
                         start_pos: Union[int, jnp.ndarray] = 0,
                         impl: str = "fused", use_pallas: bool = False,
                         b_tile: int = 8):
    """Fused single-pass pipeline with streams sharded over the mesh.

    attrs (T, B, A) sharded on B | tables replicated | c0 (B, W, S) sharded.
    Returns (matches (T, B, Q), c_final) with the same shardings.  Zero
    collectives: every shard runs the fused pipeline on its own substreams.
    """
    axes = stream_axes(mesh)
    specs = tuple(specs)

    def local_pipeline(a, co, ci, m, fq, c, im, sp):
        T, B, A = a.shape
        route = ops.plan_pipeline(
            T=T, B=B, A=A, W=c.shape[1], S=m.shape[1], NC=m.shape[0],
            NQ=fq.shape[0], V=ci.shape[0], impl=impl, use_pallas=use_pallas,
            b_tile=b_tile)
        return ops.cer_pipeline(a, specs, co, ci, m, fq, c, init_mask=im,
                                epsilon=epsilon, start_pos=sp[0],
                                route=route)

    return jax.shard_map(
        local_pipeline, mesh=mesh,
        in_specs=(P(None, axes, None), P(), P(), P(), P(), P(axes), P(),
                  P()),
        out_specs=(P(None, axes, None), P(axes)), check_vma=False,
    )(attrs, class_of, class_ind, m_all, finals_q, c0, init_mask,
      ops._start_arr(start_pos))


def route_by_partition(mesh: Mesh, events: jnp.ndarray, keys: jnp.ndarray,
                       payload: jnp.ndarray = None,
                       drop: jnp.ndarray = None):
    """Route event rows to the shard owning their partition (hash routing).

    events:  (N, A) f32 event block, N % num_shards == 0
    keys:    (N,)  int32 partition hashes, already in [0, num_shards) or
             non-negative (ownership = ``keys % num_shards``)
    payload: optional (N, P) int32 per-event columns (e.g. key hashes +
             global stream positions) routed through the identical
             permutation, so each shard receives its events' metadata.
    drop:    optional (N,) bool — events excluded sender-side (e.g. NULL
             partition keys): they enter no bucket, consume no capacity,
             and come back ``keep=False``.
    Returns (N, A) events re-ordered so that shard s holds the events with
    ``hash % num_shards == s`` (padded round-robin within shards), plus the
    routed payload when one was given, plus the keep mask:
    ``(routed, keep)`` or ``(routed, routed_payload, keep)``.

    The dense formulation: each shard bucket-sorts its local events by
    destination shard, then a single ``all_to_all`` exchanges equal-size
    buckets of ``N / num_shards²`` rows.  Overflowing buckets spill to a
    host retry queue (returned mask) — the classic bounded-capacity routing
    used by MoE dispatch, reused here for CER partition routing.
    """
    axes = stream_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    with_payload = payload is not None
    if drop is None:
        drop = jnp.zeros((events.shape[0],), bool)
    extra = (payload,) if with_payload else ()

    def local_route(ev, ks, dr, *pls):
        # ev: (n_local, A), ks: (n_local,), dr: (n_local,), pls: (n_local, P)
        n_local, A = ev.shape
        cap = n_local // n_shards
        dest = (ks % n_shards).astype(jnp.int32)              # (n_local,)
        # position of each (non-dropped) event within its destination bucket
        onehot = jax.nn.one_hot(dest, n_shards, dtype=jnp.int32) \
            * (~dr)[:, None].astype(jnp.int32)
        rank = jnp.cumsum(onehot, axis=0) - 1                 # (n_local, S)
        my_rank = jnp.take_along_axis(rank, dest[:, None], axis=1)[:, 0]
        keep = ~dr & (my_rank < cap)                          # capacity mask
        flat_idx = dest * cap + jnp.clip(my_rank, 0, cap - 1)

        def exchange(x):
            # scatter into (n_shards, cap, ...) buckets, then all_to_all
            buckets = jnp.zeros((n_shards * cap, x.shape[1]), x.dtype)
            buckets = buckets.at[flat_idx].add(
                x * keep[:, None].astype(x.dtype))
            buckets = buckets.reshape(n_shards, cap, x.shape[1])
            routed = jax.lax.all_to_all(buckets, axes, split_axis=0,
                                        concat_axis=0, tiled=False)
            return routed.reshape(n_shards * cap, x.shape[1])

        return tuple(exchange(x) for x in (ev, *pls)) + (keep,)

    # returns (routed, keep) or (routed, routed_payload, keep)
    return jax.shard_map(
        local_route, mesh=mesh,
        in_specs=(P(axes),) * (3 + len(extra)),
        out_specs=(P(axes),) * (2 + len(extra)), check_vma=False,
    )(events, keys, drop, *extra)


def route_partitioned_chunk(mesh: Mesh, attrs: jnp.ndarray,
                            keys: jnp.ndarray, positions: jnp.ndarray,
                            event_ts: "jnp.ndarray" = None):
    """One chunk of an interleaved stream → shard-owned sub-chunks.

    The sharded PARTITION BY layout (DESIGN.md §6): the global lane table is
    split over the mesh (shard s owns the partitions with
    ``hash % num_shards == s``), so the only collective in the whole
    partitioned pipeline is this router — each shard then runs the *local*
    assignment-scan + fused-scan step (`vector/partitioned.py`) on its
    sub-chunk with zero scan collectives.

    attrs (N, A) f32 | keys (N,) uint32 partition hashes | positions (N,)
    int32 global stream positions | event_ts (N,) f32 per-event timestamps
    (time windows only, DESIGN.md §9 — shipped as one more bitcast payload
    column).  Returns ``(attrs', keys', positions', valid, keep)`` — plus
    ``ts'`` before ``valid`` when ``event_ts`` was given — where row i of
    every output belongs to the same event and shard s holds the events it
    owns.  ``valid`` flags the received rows that carry a real event —
    bucket padding comes back with the NULL key sentinel, so the local
    lane router drops it either way.  ``keep`` (sender-side) flags events
    that arrived at their owner: NULL-keyed events are dropped before the
    exchange (they join no substream and must not consume router
    capacity), and events past the per-bucket capacity spill and retry on
    the host, as in MoE dispatch.
    """
    from ..core.partition import NULL_KEY_HASH

    axes = stream_axes(mesh)
    n_shards = np.prod([mesh.shape[a] for a in axes]).astype(np.uint32)
    is_null = keys == jnp.uint32(NULL_KEY_HASH)
    # ownership is hash % num_shards in *uint32*: reduce before the int32
    # bitcast so hashes ≥ 2³¹ land on their documented owner
    dest_keys = _bitcast_i32(keys % n_shards)
    ones = jnp.ones_like(positions, dtype=jnp.int32)
    cols = [_bitcast_i32(keys), positions.astype(jnp.int32), ones]
    if event_ts is not None:
        cols.append(_bitcast_i32(jnp.asarray(event_ts, jnp.float32)))
    payload = jnp.stack(cols, axis=1)
    routed, routed_pl, keep = route_by_partition(
        mesh, attrs, dest_keys, payload=payload, drop=is_null)
    valid = routed_pl[:, 2] > 0
    keys_out = jnp.where(valid, _bitcast_u32(routed_pl[:, 0]),
                         jnp.uint32(NULL_KEY_HASH))
    out = (routed, keys_out, routed_pl[:, 1])
    if event_ts is not None:
        ts_out = jax.lax.bitcast_convert_type(routed_pl[:, 3], jnp.float32)
        out = out + (ts_out,)
    return out + (valid, keep)


def _bitcast_i32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _bitcast_u32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)
