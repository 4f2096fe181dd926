#!/usr/bin/env python3
"""Run the served CER path once on one TPU and check what comes out.

    python3 chip_smoke.py

One process holds the chip throughout.  Phases:

(a) device  — print platform, kind and count; anything but a TPU fails.
(b) kernel  — the quickstart's count-window query over 1,024 substreams in
              512-event chunks with the tECS arena on.  The compiled step
              must contain the fused Pallas kernel (``tpu_custom_call``);
              counts and enumerated matches must equal the same engine with
              ``impl="ref"`` and, on sampled lanes, the host engine.  The
              kernel's time-window and LAST + CONSUME BY ANY variants must
              match ``impl="ref"`` too.
(c) served  — the paper's stock query Q3 (``PARTITION BY [volume]``,
              ``WITHIN 30000 [stock_time]``, ``CONSUME BY ANY``) through
              ``StreamService`` → ``PartitionedStreamingEngine`` with the
              tECS arena on, then delta fetch and Algorithm 2, over
              300,000 ``stock_stream`` events at 1/128 of the published
              4,803 ev/s (:data:`SERVED`, a 512-slot ring; the cut,
              :data:`CUT`, is printed first).  Per-position counts
              for every event, and enumerations of sampled hits, must
              equal the host ``core.partition.PartitionedEngine``; the ring
              is sized so that nothing overflows, and the step compiles
              once.

Any failed phase exits non-zero.  The last stdout line is the JSON result
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

#: the quickstart's count-window query (examples/quickstart.py)
KERNEL_QUERY = ("SELECT * FROM S WHERE SELL AS a ; BUY AS b "
                "FILTER a[price > 25.0] AND b[price < 10.0] "
                "WITHIN 100 events")

#: the paper's stock query Q3 (benchmarks/cer_paper.py STOCK_QUERIES)
Q3 = """SELECT * FROM S
    WHERE SELL AS msft ; BUY AS oracle ; BUY AS csco ; SELL AS amat
    FILTER msft[name = 'MSFT'] AND oracle[name = 'ORCL'] AND
    csco[name = 'CSCO'] AND amat[name = 'AMAT']
    PARTITION BY [volume]
    WITHIN 30000 [stock_time]
    CONSUME BY ANY"""


def log(msg: str) -> None:
    print(msg, flush=True)


def ceset(ces):
    """Complex events in comparable form: (start, end, positions)."""
    return {(int(c.start), int(c.end), tuple(map(int, c.data)))
            for c in ces}


def print_routes(name: str, engine) -> None:
    for stage, route in engine.routes.items():
        log(f"[{name}] route {stage}: {route.describe()}")


def state_bytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# (b) kernel phase
# ---------------------------------------------------------------------------

def kernel_phase(lanes: int = 1024, chunk: int = 512, chunks: int = 2,
                 arena_capacity: int = 8192, host_lanes: int = 8,
                 enum_sample: int = 256, seed: int = 0) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Event, compile_query
    from repro.core.engine import Engine
    from repro.vector import StreamingVectorEngine, VectorEngine

    # 8,192 arena nodes per lane hold the 5,537 the busiest lane allocates
    ve = VectorEngine(KERNEL_QUERY)
    se = StreamingVectorEngine(ve, chunk_len=chunk, batch=lanes,
                               arena_capacity=arena_capacity)
    se_ref = StreamingVectorEngine(VectorEngine(KERNEL_QUERY, impl="ref"),
                                   chunk_len=chunk, batch=lanes,
                                   arena_capacity=arena_capacity)
    print_routes("kernel", se)
    print_routes("kernel/ref", se_ref)
    r = se.routes["scan"]
    assert r.path == "pallas" and not r.interpret, r

    # stock-shaped events made in bulk: BUY/SELL and prices U(5, 50)
    rng = np.random.default_rng(seed)
    T = chunk * chunks
    types = rng.integers(0, 2, (T, lanes))
    prices = np.round(rng.uniform(5.0, 50.0, (T, lanes)), 2)
    enc = ve.encoder
    codes = enc.vocab["type"]
    names = {v: k for k, v in codes.items()}
    attrs = np.zeros((T, lanes, len(enc.attrs)), np.float32)
    attrs[:, :, enc.attr_index["type"]] = types
    attrs[:, :, enc.attr_index["price"]] = prices

    t0 = time.perf_counter()
    first = jnp.asarray(attrs[:chunk])
    hlo = se._step.lower(first, se.state, jnp.asarray(0, jnp.int32),
                         jnp.asarray(0, jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in hlo, "compiled step holds no tpu_custom_call"
    log("[kernel] compiled step holds tpu_custom_call")

    counts, hits, counts_ref, hits_ref = [], [], [], []
    for c in range(chunks):
        a = jnp.asarray(attrs[c * chunk:(c + 1) * chunk])
        k, h = se.feed_attrs(a)
        kr, hr = se_ref.feed_attrs(a)
        counts.append(k)
        hits += h
        counts_ref.append(kr)
        hits_ref += hr
    counts = np.concatenate(counts)
    counts_ref = np.concatenate(counts_ref)
    log(f"[kernel] {lanes} lanes x {T} events: {int(counts.sum())} matches "
        f"at {len(hits)} hit positions ({time.perf_counter() - t0:.3f} s "
        "host clock, compiles included)")
    assert np.array_equal(counts, counts_ref), "kernel counts != impl=ref"
    assert hits == hits_ref, "kernel hits != impl=ref"
    assert se.compile_count == 1, se.compile_count
    assert not np.asarray(se.state["arena"]["ovf"]).any(), "arena overflow"

    pick = rng.choice(len(hits), min(enum_sample, len(hits)), replace=False)
    sample = [hits[i] for i in sorted(pick)]
    got = se.enumerate_hits(sample)
    want = se_ref.enumerate_hits(sample)
    for h in sample:
        assert ceset(got[h]) == ceset(want[h]), ("enum != impl=ref", h)
        assert len(got[h]) == counts[h[0], h[1]], ("enum size", h)
    log(f"[kernel] counts and {len(sample)} sampled enumerations equal "
        "impl='ref'")

    # host engine (Algorithm 1 + 2) on sampled lanes
    cq = compile_query(KERNEL_QUERY)
    lane_pick = sorted(rng.choice(lanes, min(host_lanes, lanes),
                                  replace=False).tolist())
    lane_hits = [(p, b) for p, b in hits if b in lane_pick]
    got = se.enumerate_hits(lane_hits)
    n_ce = 0
    for b in lane_pick:
        eng = Engine(cq.cea, window=cq.query.window)
        for t in range(T):
            ev = Event(names[float(types[t, b])],
                       {"price": float(prices[t, b])})
            ces = eng.process(ev)
            assert len(ces) == counts[t, b], ("host count", t, b)
            if ces:
                assert ceset(ces) == ceset(got[(t, b)]), ("host enum", t, b)
                n_ce += len(ces)
    log(f"[kernel] host engine agrees on lanes {lane_pick}: every count, "
        f"{n_ce} complex events")


#: the fused kernel's other static variants, checked against impl="ref"
VARIANT_QUERIES = {
    "time": ("SELECT * FROM S WHERE SELL AS a ; BUY AS b "
             "FILTER a[price > 25.0] AND b[price < 10.0] WITHIN 30 seconds",
             256),
    "last+consume": ("SELECT LAST * FROM S WHERE A ; B+ ; C WITHIN 7 [ts] "
                     "CONSUME BY ANY", 64),
}


def variant_events(name: str, rng, n: int):
    from repro.core import Event
    if name == "time":      # 4 events per second: 120 live in 30 s
        return [Event(("SELL", "BUY")[int(rng.integers(2))],
                      {"price": round(float(rng.uniform(5.0, 50.0)), 2)},
                      timestamp=0.25 * (i + 1)) for i in range(n)]
    return [Event("ABC"[int(rng.integers(3))], {"ts": float(i)})
            for i in range(n)]


def kernel_variants_phase(lanes: int = 64, chunk: int = 256,
                          chunks: int = 2, seed: int = 1) -> None:
    import numpy as np

    from repro.vector import StreamingVectorEngine, VectorEngine

    rng = np.random.default_rng(seed)
    for name, (query, mwe) in VARIANT_QUERIES.items():
        se = StreamingVectorEngine(
            VectorEngine(query, max_window_events=mwe), chunk_len=chunk,
            batch=lanes, strict_overflow=True)
        ref = StreamingVectorEngine(
            VectorEngine(query, max_window_events=mwe, impl="ref"),
            chunk_len=chunk, batch=lanes, strict_overflow=True)
        route = se.routes["scan"]
        log(f"[kernel/{name}] route scan: {route.describe()}")
        assert route.path == "pallas" and not route.interpret, route
        streams = [variant_events(name, rng, chunk * chunks)
                   for _ in range(lanes)]
        total = 0
        for c in range(chunks):
            part = [s_[c * chunk:(c + 1) * chunk] for s_ in streams]
            k, _ = se.feed(part)
            kr, _ = ref.feed(part)
            assert np.array_equal(k, kr), f"{name}: kernel != impl=ref"
            total += int(k.sum())
        assert se.compile_count == 1, se.compile_count
        log(f"[kernel/{name}] {lanes} lanes x {chunk * chunks} events: "
            f"{total} matches, equal to impl='ref'")


# ---------------------------------------------------------------------------
# (c) served phase
# ---------------------------------------------------------------------------

def ring_for(events, key: str, time_attr: str, size: float,
             align: int = 512) -> int:
    """Rate bound that keeps every partition's live window in the ring:
    the most events any partition holds inside one window, rounded up."""
    import numpy as np
    ts = np.asarray([e.get(time_attr) for e in events], np.float64)
    keys = np.asarray([e.get(key) for e in events])
    most = 0
    for k in np.unique(keys):
        t = ts[keys == k]
        first = np.searchsorted(t, t - size, side="left")
        most = max(most, int((np.arange(len(t)) - first).max()) + 1)
    return -(-most // align) * align


def lane_fill(events, key: str, chunk: int) -> int:
    """Most events one partition receives in any chunk (lane_cap bound)."""
    import numpy as np
    keys = np.asarray([e.get(key) for e in events])
    return max(int(np.unique(keys[c:c + chunk], return_counts=True)[1].max())
               for c in range(0, len(keys), chunk))


#: the served phase's run of Q3 over 300,000 stock events (seed 0).
#: ``ring`` and ``lane_cap`` are what the data needs (:func:`ring_for`,
#: :func:`lane_fill`), fixed here so tests/test_tpu_compile.py compiles the
#: same widths; ``arena_capacity`` is the tECS node store per lane.  The
#: arrival rate is cut 128x from the generator's published 4,803 ev/s (see
#: :data:`CUT`).
SERVED = dict(rate=4803.0 / 128, ring=512, chunk=512, lane_cap=168,
              arena_capacity=1 << 18)

#: why the served run does not use the published rate, printed before it
CUT = ("arrival rate 37.52 ev/s, 128x below the published 4,803 ev/s (ring "
       "512 slots instead of 36,864): at the published ring the arena "
       "step did not get through 50,000 events in the 1,080 s a 1,300 s "
       "run on a TPU v5 lite had left for it; the block builder's dense "
       "records grow with the ring (958,465 per event and lane at 36,864)")


def served_phase(n_events: int = 300_000, enum_sample: int = 512,
                 checkpoint_every: int = 16, seed: int = 0) -> None:
    import numpy as np

    from repro.core import compile_query
    from repro.core.engine import Engine
    from repro.core.partition import PartitionedEngine
    from repro.data.streams import stock_stream
    from repro.runtime import StreamService
    from repro.runtime.recovery import cumulative_matches
    from repro.vector import PartitionedStreamingEngine, VectorEngine

    cfg = SERVED
    chunk, lane_cap = cfg["chunk"], cfg["lane_cap"]
    arena = cfg["arena_capacity"]
    tag = "served"
    t0 = time.perf_counter()
    events = stock_stream(n_events, seed=seed, events_per_sec=cfg["rate"])
    cq = compile_query(Q3)
    keys = tuple(cq.query.partition_by)
    win = cq.query.window
    n_keys = len({e.get(keys[0]) for e in events})
    need_ring = ring_for(events, keys[0], win.time_attr, win.size)
    need_cap = lane_fill(events, keys[0], chunk)
    assert need_ring <= cfg["ring"] and need_cap <= lane_cap, \
        (need_ring, need_cap, cfg)
    span = events[-1].get(win.time_attr) - events[0].get(win.time_attr)
    log(f"[{tag}] {n_events} events at {cfg['rate']:.2f} ev/s over "
        f"{span / 1000:.1f} s of stock_time ({span / win.size:.2f} "
        f"windows), {n_keys} partitions, ring {cfg['ring']} slots (data "
        f"needs {need_ring}), chunk {chunk}, lane_cap {lane_cap} (data "
        f"needs {need_cap}), arena on, {arena} nodes per lane")
    log(f"[{tag}] CUT: {CUT}")

    # host reference: one Algorithm-1 engine per partition
    host = PartitionedEngine(
        lambda: Engine(cq.cea, window=win,
                       consume_on_match=cq.query.consume_on_match), keys)
    want_counts = np.zeros(n_events, np.int64)
    want_sets = {}
    for j, ev in enumerate(events):
        ces = host.process(ev)
        if ces:
            want_counts[j] = len(ces)
            want_sets[j] = ceset(ces)
    log(f"[{tag}] host engine: {int(want_counts.sum())} matches at "
        f"{len(want_sets)} positions ({time.perf_counter() - t0:.3f} s "
        "host clock, generation included)")

    ve = VectorEngine(cq, max_window_events=cfg["ring"])
    pse = PartitionedStreamingEngine(
        ve, keys, chunk_len=chunk, num_lanes=n_keys, lane_cap=lane_cap,
        arena_capacity=arena, strict_overflow=True)
    print_routes(tag, pse)
    st = pse.state
    parts = [f"ring {state_bytes(st['C'])}",
             f"lane tables {state_bytes([st[k] for k in st if k.startswith('lane_')])}",
             f"arena cells {state_bytes(st['arena']['cell'])}",
             "node store " + str(state_bytes(
                 {k: v for k, v in st['arena'].items() if k != 'cell'}))]
    log(f"[{tag}] device state bytes: {', '.join(parts)}")

    alerts = []
    raws = [{"type": e.type, **e.attrs} for e in events]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        svc = StreamService(pse, d, sinks=[lambda c, h: alerts.extend(h)],
                            checkpoint_every=checkpoint_every,
                            prune_roots=False,
                            max_window_events_cap=cfg["ring"])
        t1 = time.perf_counter()
        for j, raw in enumerate(raws, 1):
            r = svc.submit(raw, block=True, timeout=1200.0)
            assert r.accepted, r
            if j % 50_000 == 0:
                log(f"[{tag}] {j} events submitted, {svc.metrics.chunks} "
                    f"chunks done, {time.perf_counter() - t1:.3f} s host "
                    "clock")
        svc.drain(pad=True, timeout=1200.0)
        dt = time.perf_counter() - t1
        m = svc.metrics
        log(f"[{tag}] StreamService: {m.chunks} chunks, "
            f"{m.events_processed} events in {dt:.3f} s host clock "
            f"({m.events_processed / dt:.0f} ev/s, compile included), "
            f"overflows {m.overflows}, regrows {m.regrows}")
        assert m.overflows == 0 and not pse.window_overflow.any(), \
            "time-window ring overflowed"
        s = pse.stats
        assert s.spilled_capacity == 0 and s.spilled_table == 0 and \
            s.evicted_lanes == 0, s
        got_counts = np.zeros(n_events, np.int64)
        for (c, i), v in cumulative_matches(d)["counts"].items():
            if c * chunk + i < n_events:
                got_counts[c * chunk + i] = v
        bad = np.nonzero(got_counts != want_counts)[0]
        assert len(bad) == 0, \
            f"{len(bad)} positions differ from the host, first {bad[:5]}"
        assert sorted(alerts) == sorted(want_sets), "alert positions differ"
        log(f"[{tag}] per-position counts equal the host engine at all "
            f"{n_events} positions ({int(got_counts.sum())} matches)")
        assert pse.compile_count == 1, pse.compile_count
        log(f"[{tag}] compile_count {pse.compile_count}")

        rng = np.random.default_rng(seed)
        hit_pos = sorted(want_sets)
        pick = rng.choice(len(hit_pos), min(enum_sample, len(hit_pos)),
                          replace=False)
        sample = [hit_pos[i] for i in sorted(pick.tolist())]
        t2 = time.perf_counter()
        got = pse.enumerate_hits(sample)
        log(f"[{tag}] delta fetch + Algorithm 2 over {len(sample)} "
            f"hits: {time.perf_counter() - t2:.3f} s host clock")
        for p in sample:
            assert ceset(got[p]) == want_sets[p], ("enumeration", p)
        n = sum(len(want_sets[p]) for p in sample)
        log(f"[{tag}] {len(sample)} sampled hits enumerate the host's "
            f"{n} complex events exactly")
        assert not np.asarray(pse.state["arena"]["ovf"]).any(), \
            "arena overflow"
        ptr = np.asarray(pse.state["arena"]["ptr"])
        log(f"[{tag}] arena nodes per lane: {ptr.tolist()} of {arena}")
        svc.close()


def main() -> int:
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing: {e}",
              file=sys.stderr)
        return 1
    log(f"[device] compile cache: {enable_compile_cache(HERE)}")
    failed = []
    phases = (("kernel", kernel_phase),
              ("kernel/variants", kernel_variants_phase),
              ("served", served_phase))
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
            log(f"[{name}] passed in {time.perf_counter() - t0:.1f} s")
        except Exception:
            traceback.print_exc()
            log(f"[{name}] FAILED")
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
