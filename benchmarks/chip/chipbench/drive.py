"""Run one cell: build the program's engine and service, warm, measure, check.

With the engine builders (``engines/<kind>.py``), the only module of the
harness that imports the program (``repro``).  The run goes:

1. set-up: generate the stream from the seed, build the engine and
   ``StreamService`` the configuration names, warm the shapes the window
   uses (one chunk through the service, plus the arena mirror's
   delta-fetch slices), drain;
2. the window: ``seconds`` of traffic as the mix says (closed or open
   loop), with a compile counter armed, and with ``trace`` a profiler
   trace;
3. after the window: drain, read the device's peak memory, free the
   service, then compare what the window produced with the plain reference.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import reference, spec, streams, tracing


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclass
class Run:
    """Everything a run measured; per-layer readers take it as ``run``."""

    seconds: float
    t_open: float = 0.0
    t_close: float = 0.0
    #: per window chunk: (formed, completed, events)
    chunks: List[Tuple[float, float, int]] = field(default_factory=list)
    cpu_s: float = 0.0               # process CPU seconds over the window
    enum_calls: List[Tuple[float, float, int]] = field(default_factory=list)
    hit_latency_s: List[float] = field(default_factory=list)
    trace: Optional[tracing.Trace] = None
    kernel_shapes: Dict[str, Dict[str, int]] = field(default_factory=dict)
    peaks: Optional[dict] = None
    root: str = spec.CHIP_DIR
    #: open loop: the rate, the first window event's generator index and
    #: the time it was due
    rate: Optional[float] = None
    i0: int = 0
    due0: float = 0.0

    def completed(self) -> List[Tuple[float, float, int]]:
        """Window chunks whose alerts reached the sink before it closed."""
        return [c for c in self.chunks if c[1] <= self.t_close]

    def measured(self) -> Tuple[List[Tuple[float, float, int]], float]:
        """The chunks the rate counts and the end of its span: the span
        runs from the opening to the first completion at or after the
        close (to the close where none comes), and counts every chunk
        completed by then.  A stall at the end of the window lengthens the
        span; a chunk of several seconds is not cut in two."""
        after = [c[1] for c in self.chunks if c[1] >= self.t_close]
        end = min(after) if after else self.t_close
        return [c for c in self.chunks if c[1] <= end], end


# ---------------------------------------------------------------------------
# end-to-end arithmetic
# ---------------------------------------------------------------------------

def events_per_s(run: Run) -> Optional[float]:
    """Events of the chunks :meth:`Run.measured` counts over its span."""
    done, end = run.measured()
    if not done:
        return None
    return sum(c[2] for c in done) / (end - run.t_open)


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile (0–100) with linear interpolation."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    x = (len(v) - 1) * q / 100.0
    lo = int(math.floor(x))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (x - lo)


# ---------------------------------------------------------------------------
# compile counter
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts traces, compiles and persistent-cache loads while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.count = 0
        self.names: List[str] = []
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **kw):
        self._on_event(name, **kw)

    def _on_event(self, name, **kw):
        if self.armed and name in self.EVENTS:
            self.count += 1
            self.names.append(f"{name} {kw.get('fun_name', '')}".strip())


class HostStalls:
    """What held the host up while armed: a ticker thread that wakes every
    20 ms and keeps its longest oversleeps, with the process's CPU time in
    them, and the garbage collector's pauses.  A long gap with CPU time
    spent is the process at work (the collector, a call that holds the
    interpreter's lock); one with almost none is the machine standing
    still.  Printed on an earlier line; no metric reads it."""

    TICK = 0.02

    def __init__(self):
        self.t0 = 0.0
        self.gaps: List[Tuple[float, float, float]] = []  # (gap, at, cpu)
        self.gc: List[Tuple[int, float]] = []              # (gen, pause)
        self._gc_start: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gc.append((info["generation"],
                            time.perf_counter() - self._gc_start))
            self._gc_start = None

    def _tick(self):
        last, cpu = time.perf_counter(), time.process_time()
        while not self._stop.wait(self.TICK):
            now, c = time.perf_counter(), time.process_time()
            if now - last > 2 * self.TICK:
                self.gaps.append((now - last - self.TICK, last - self.t0,
                                  c - cpu))
            last, cpu = now, c

    def start(self) -> None:
        self.t0 = time.perf_counter()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._tick,
                                        name="bench-ticker", daemon=True)
        self._thread.start()

    def stop(self) -> str:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        top = sorted(self.gaps, reverse=True)[:3]
        gaps = ", ".join(f"{1e3 * g:.1f} ms at {a:.2f} s ({1e3 * c:.1f} "
                         "CPU ms)" for g, a, c in top) or "none"
        full = [p for g, p in self.gc if g == 2]
        return (f"longest ticker gaps {gaps}; gc {len(self.gc)} "
                f"collections, {len(full)} full, longest "
                f"{1e3 * max((p for _, p in self.gc), default=0.0):.1f} ms"
                f", {1e3 * sum(p for _, p in self.gc):.1f} ms in all")


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build_engine(cfg: dict, root: str = spec.CHIP_DIR):
    """The engine the configuration's ``engine`` group states, built by
    ``engines/<kind>.py``."""
    return spec.load_engine(cfg["engine"]["kind"], root).build(cfg)


def ingress_chunks(cfg: dict, mix: dict) -> int:
    """The service's ingress buffer, in chunks: the traffic mix's
    ``queue_chunks`` where it sizes one for its sources, else the
    configuration's.  Open-loop sources keep their schedule through a host
    stall, and the buffer holds what they send meanwhile: a stall then
    shows in the latency tail, where a buffer too small for it sheds."""
    return int(mix.get("queue_chunks", cfg["service"]["queue_chunks"]))


def tree_bytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def fused_scan_shapes(engine) -> Dict[str, int]:
    """The shapes of one ``cer_fused_scan`` call of this engine's step."""
    t = engine.engine.tables
    return {"T": engine._scan_steps, "B": engine.batch,
            "A": len(engine.encoder.attrs), "W": engine.window.ring,
            "S": int(t.m_all.shape[1]), "NC": int(t.m_all.shape[0]),
            "V": int(t.class_ind.shape[0]),
            "NQ": int(np.asarray(engine._finals_q).shape[0]),
            "timed": int(engine.window.is_time),
            "trace": int(engine.arena_capacity is not None)}


def describe_engine(engine, cfg: dict) -> None:
    e = cfg["engine"]
    for stage, route in engine.routes.items():
        log(f"[engine] route {stage}: {route.describe()}")
    st = engine.state
    arena = st.get("arena") if isinstance(st, dict) else None
    ring = st["C"] if isinstance(st, dict) else st
    log(f"[engine] ring {engine.window.ring} slots, chunk {engine.chunk_len}, "
        f"lanes {engine.batch}, lane_cap {e.get('lane_cap', engine.chunk_len)}"
        f", arena capacity {engine.arena_capacity}")
    log(f"[engine] state bytes: ring {tree_bytes(ring)}, arena "
        f"{tree_bytes(arena) if arena is not None else 0}, total "
        f"{tree_bytes(st)}")


def warm_mirror(engine) -> int:
    """Load (or compile) the arena mirror's delta-fetch slice for every span
    a sync can ask for: the powers of two up to the capacity.  The span a
    sync needs is the growth since the last hit chunk plus the skew between
    lanes, which grow with the run's length and so with the program's
    speed: only the capacity bounds them for every program, and a span
    left cold would compile inside the window of a faster one."""
    if engine.arena_capacity is None:
        return 0
    import jax
    from repro.vector import tecs_arena
    arena = engine.state["arena"]
    cap = int(arena["kind"].shape[1])
    spans = sorted({min(1 << k, cap) for k in range(cap.bit_length() + 1)})
    for span in spans:
        jax.block_until_ready(tecs_arena._mirror_slice(arena, 0, span))
    return len(spans)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def execute(bench: dict, cell: dict, seed: int, seconds: float,
            trace: bool, t_start: float, root: str = spec.CHIP_DIR,
            peaks: Optional[dict] = None,
            faults: Optional[Dict[str, Any]] = None) -> Tuple[dict, int]:
    """One run of ``cell``.  Returns the result line's object and the exit
    code.  ``peaks`` are the chip's (None off the chip: no roofline);
    ``faults`` plants a defect for the harness's own tests."""
    import jax
    cfg = spec.load_config(cell["config"], root)
    mix = spec.load_mix(cell["traffic"], root)
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    log(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    counter = CompileCounter()

    L = int(cfg["engine"]["chunk_len"])
    queue_chunks = ingress_chunks(cfg, mix)
    stream = streams.Stream(cfg["generator"], seed, root)
    engine = build_engine(cfg, root)
    describe_engine(engine, cfg)
    log(f"[setup] engine built at {time.perf_counter() - t_start:.3f} s")
    run = Run(seconds=seconds, root=root, peaks=peaks)
    if engine.routes["scan"].path == "pallas":
        run.kernel_shapes["cer_fused_scan"] = fused_scan_shapes(engine)
    if faults:
        faults["plant"](engine)

    from repro.runtime import StreamService
    enumerate_all = bool(cfg["sink"]["enumerate"])
    delivered: Dict[int, float] = {}
    enumerated: Dict[int, set] = {}
    window_state = {"open": False}

    def sink(chunk: int, hits) -> None:
        t = time.perf_counter()
        pos = [h[0] if isinstance(h, tuple) else int(h) for h in hits]
        for p in pos:
            delivered.setdefault(p, t)
        if enumerate_all:
            with jax.profiler.TraceAnnotation("bench.sink"):
                t0 = time.perf_counter()
                out = engine.enumerate_hits(list(hits))
                t1 = time.perf_counter()
            n_ce = 0
            for h, ces in out.items():
                p = h[0] if isinstance(h, tuple) else int(h)
                enumerated[p] = {tuple(int(x) for x in c.data) for c in ces}
                n_ce += len(ces)
            if window_state["open"]:
                run.enum_calls.append((t1, t1 - t0, n_ce))

    workdir = tempfile.mkdtemp(prefix="chipbench-")
    svc = None
    stalls = HostStalls()
    try:
        svc = StreamService(engine, workdir, sinks=[sink],
                            checkpoint_every=cfg["service"]["checkpoint_every"],
                            queue_chunks=queue_chunks,
                            max_window_events_cap=max(engine.window.ring,
                                                      1 << 16))
        if faults and "plant_service" in faults:
            faults["plant_service"](svc)
        accepted: List[int] = []   # generator index of each accepted event
        # --- warm-up: one chunk through every stage, then the mirror -------
        t_warm = time.perf_counter()
        for i in range(L):
            if svc.submit(stream.raw(i), block=True, timeout=1200.0).accepted:
                accepted.append(i)
        svc.drain(timeout=1200.0)
        t_slices = time.perf_counter()
        n_slices = warm_mirror(engine)
        t_slices = time.perf_counter() - t_slices
        if enumerate_all:
            engine.enumerate_hits([])
        n_warm = len(svc.metrics.chunk_latency_s)
        log(f"[setup] warm-up: {n_warm} chunk(s), {n_slices} mirror slices "
            f"in {t_slices:.3f} s, compile_count {engine.compile_count}, "
            f"{time.perf_counter() - t_warm:.3f} s; set-up so far "
            f"{time.perf_counter() - t_start:.3f} s")
        gc.collect()

        tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(
                tdir, profiler_options=tracing.capture_options())
        counter.armed = True
        stalls.start()
        if mix["loop"] == "closed":
            forms, gen_lag, offered = closed_loop(
                svc, stream, run, accepted, L, queue_chunks, window_state)
        elif mix["loop"] == "open":
            forms, gen_lag, offered = open_loop(
                svc, stream, run, accepted, L, float(mix["rate"]),
                window_state)
        else:
            raise ValueError(f"unknown loop {mix['loop']!r}")
        setup_s = run.t_open - t_start
        t_drain = time.perf_counter()
        if trace:
            # stopped at the end of the span the rate measures (the first
            # completion after the close): the steps that started in the
            # window run to their end in the trace, the rest of the drain
            # stays out of it
            wait_past_close(svc.metrics, n_warm, len(forms), timeout=300.0)
            jax.profiler.stop_trace()
        svc.drain(timeout=300.0)
        counter.armed = False
        log(f"[host] in the window and drain: {stalls.stop()}")
        log(f"[window] {run.seconds} s; drain after it "
            f"{time.perf_counter() - t_drain:.3f} s")

        lat = svc.metrics.chunk_latency_s[n_warm:]
        run.chunks = [(f, f + l, L) for f, l in zip(forms, lat)]
        m = svc.metrics
        peak = peak_memory(dev)
        stats = getattr(engine, "stats", None)
        guards = {
            "compiles": counter.count,
            "spilled": (stats.spilled_capacity + stats.spilled_table
                        if stats is not None else 0),
            "evicted": stats.evicted_lanes if stats is not None else 0,
            "ring_overflow": int(m.overflows) + int(np.asarray(
                engine.window_overflow).any()),
            "arena_overflow": (int(np.asarray(engine.state["arena"]["ovf"]
                                              ).any())
                               if engine.arena_capacity is not None else 0),
        }
        if counter.names:
            log(f"[window] compiles inside the window: {counter.names[:8]}")
        if engine.arena_capacity is not None:
            ptr = np.asarray(engine.state["arena"]["ptr"]).tolist()
            log(f"[arena] nodes per lane {ptr} of {engine.arena_capacity} "
                f"({100.0 * max(ptr) / engine.arena_capacity:.3f}% of the "
                f"fullest lane), {sum(ptr) / max(1, m.events_processed):.3f} "
                "nodes per event")
        log(f"[service] accepted {m.accepted}, rejected {m.rejected}, shed "
            f"{m.shed_rate + m.shed_backpressure}, timeouts "
            f"{m.block_timeouts}, chunks {m.chunks}, queue peak "
            f"{m.queue_peak} of {queue_chunks * L}, overflows {m.overflows}, "
            f"regrows {m.regrows}, checkpoint every {cfg['service']['checkpoint_every']} chunks")
        log(f"[window] compile_count {engine.compile_count}, compiles in "
            f"window {counter.count}, generator lag {gen_lag}")
        n_processed = int(m.events_processed)
        log(f"[disk] bytes this process has written: {written_bytes()}")
        svc.close(checkpoint=False)
        svc = None
        log_records = read_match_log(os.path.join(workdir, "matches.log"))
    finally:
        stalls.stop()
        if svc is not None:
            svc.close(checkpoint=False)
        shutil.rmtree(workdir, ignore_errors=True)

    trace_obj = None
    if trace:
        try:
            trace_obj = tracing.load(tdir)
            log(f"[trace] device ops {sum(map(len, trace_obj.ops.values()))}"
                f", programs {sum(map(len, trace_obj.modules.values()))}, "
                f"host spans {len(trace_obj.host)}")
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    run.trace = trace_obj
    del sink, engine          # free the program's device state before
    gc.collect()              # the reference runs

    # --- correctness against the plain reference ---------------------------
    idx = np.asarray(accepted, np.int64)
    cols = stream.select(idx)
    t0 = time.perf_counter()
    want, want_ces = reference.evaluate(cfg["reference"], cols,
                                        stream.type_names, len(idx),
                                        enumerate_all=enumerate_all)
    log(f"[reference] {len(idx)} events, {int((want > 0).sum())} hits, "
        f"{int(want.sum())} complex events, {time.perf_counter() - t0:.3f} s")
    host_baseline(cfg, stream, accepted)
    checks = compare(want, want_ces, log_records, delivered, enumerated,
                     n_processed, len(idx), L, guards)

    # --- metrics ------------------------------------------------------------
    due = run_due_times(run, accepted)
    for p, t_recv in delivered.items():
        d = due.get(p)
        if d is not None and run.t_open <= d < run.t_close:
            run.hit_latency_s.append(t_recv - d)
    log(f"[window] {len(run.completed())} chunks completed in the window, "
        f"{len(run.hit_latency_s)} hits due in it")
    metrics: Dict[str, dict] = {}
    names = spec.cell_metrics(bench, cell["name"], trace)
    values = (per_layer_values(bench, cell, run) if trace
              else end_to_end_values(run, setup_s))
    for mdef in names:
        v = values.get(mdef["name"])
        if v is not None:
            metrics[mdef["name"]] = {"value": v, "unit": mdef["unit"]}
    missing = [m_["name"] for m_ in names if m_["name"] not in metrics]
    if missing:
        log(f"[metrics] nothing to read for {missing}")

    correct = all(v <= lim for v, lim in checks.values())
    out = {"correct": correct,
           "attempted": offered,
           "failed": (offered - len(accepted))
           + (len(accepted) - n_processed) + checks["count_diff"][0],
           "metrics": metrics,
           "device": dict(device, memory_peak_bytes=peak)}
    if trace and trace_obj is not None:
        out["device"]["busy_s"] = tracing.busy_seconds(trace_obj)
        out["device"]["window_s"] = tracing.window_seconds(trace_obj)
        out["breakdown"] = {"device_ops": [list(x) for x in
                                           tracing.top_ops(trace_obj)],
                            "idle_gaps": [list(x) for x in
                                          tracing.idle_gaps(trace_obj)]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    return out, (0 if not missing or trace else 3)


def closed_loop(svc, stream, run: Run, accepted: List[int], L: int,
                queue_chunks: int, window_state
                ) -> Tuple[List[float], str, int]:
    """Blocking submits as fast as the service takes them, a whole chunk at
    a time.  A chunk starts only while the window is open and the service
    has room for all of it.  Returns each chunk's formation time, the
    generator's lag and the number of events offered in the whole run."""
    import jax
    cap = queue_chunks * L
    forms: List[float] = []
    m = svc.metrics
    i = L
    cpu0 = time.process_time()
    run.t_open = time.perf_counter()
    run.t_close = run.t_open + run.seconds
    window_state["open"] = True
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        while True:
            while m.accepted - len(m.chunk_latency_s) * L > cap - L and \
                    time.perf_counter() < run.t_close:
                time.sleep(0.0002)
            if time.perf_counter() >= run.t_close:
                break
            with jax.profiler.TraceAnnotation("bench.submit"):
                for _ in range(L):
                    if svc.submit(stream.raw(i), block=True,
                                  timeout=600.0).accepted:
                        accepted.append(i)
                    i += 1
            forms.append(time.perf_counter())
        # the window closes once every chunk it formed has completed or
        # its time is up, whichever is later: run.t_close stays the cut
    run.cpu_s = time.process_time() - cpu0
    window_state["open"] = False
    return forms, "none (closed loop)", i


def open_loop(svc, stream, run: Run, accepted: List[int], L: int,
              rate: float, window_state) -> Tuple[List[float], str, int]:
    """Events due at a fixed rate whether or not the service keeps up
    (non-blocking submits: a full buffer sheds).  Events due after the
    window keep the schedule until the last chunk is whole: the accepted
    events fill whole chunks, so nothing waits in a partial one."""
    import jax
    i0 = L
    n_win = int(math.ceil(run.seconds * rate))
    i_end = i0 + int(math.ceil(n_win / L)) * L
    forms: List[float] = []
    lags: List[float] = []
    cpu0 = time.process_time()
    t_open = time.perf_counter()
    run.t_open, run.t_close = t_open, t_open + run.seconds
    run.due0, run.rate, run.i0 = t_open, rate, i0
    window_state["open"] = True
    i = i0
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        while i < i_end or len(accepted) % L:
            now = time.perf_counter()
            target = i0 + int((now - t_open) * rate) + 1
            if i >= i_end:        # past the window: only fill the last chunk
                target = min(target, i + L - len(accepted) % L)
            else:
                target = min(target, i_end)
            if target > i:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    while i < target:
                        if svc.submit(stream.raw(i), block=False).accepted:
                            accepted.append(i)
                            if len(accepted) % L == 0:
                                forms.append(time.perf_counter())
                        lags.append(time.perf_counter()
                                    - (t_open + (i - i0) / rate))
                        i += 1
            else:
                time.sleep(max(0.0, t_open + (i - i0) / rate
                               - time.perf_counter()))
    run.cpu_s = time.process_time() - cpu0
    window_state["open"] = False
    worst = int(np.argmax(lags))
    lag = (f"mean {1e3 * statistics.fmean(lags):.3f} ms, p99 "
           f"{1e3 * percentile(lags, 99):.3f} ms, max "
           f"{1e3 * lags[worst]:.3f} ms (event due at {worst / rate:.2f} s)"
           f" over {len(lags)} events")
    return forms, lag, i


def wait_past_close(metrics, n_warm: int, n_formed: int,
                    timeout: float) -> None:
    """Wait until one more window chunk completes than had by now (the
    window has closed), or every chunk it formed has."""
    done = len(metrics.chunk_latency_s)
    until = time.perf_counter() + timeout
    while len(metrics.chunk_latency_s) == done < n_warm + n_formed:
        if time.perf_counter() > until:
            raise TimeoutError("no chunk completed after the window closed")
        time.sleep(0.001)


def run_due_times(run: Run, accepted: List[int]) -> Dict[int, float]:
    """Stream position → the time its event was due (open loop only)."""
    if getattr(run, "rate", None) is None:
        return {}
    return {p: run.due0 + (g - run.i0) / run.rate
            for p, g in enumerate(accepted) if g >= run.i0}


def written_bytes() -> str:
    """What this process has written so far, by Linux's ``/proc/self/io``:
    bytes passed to write calls (``wchar``) and bytes sent to storage
    (``write_bytes``; a memory-backed file system sends none)."""
    try:
        with open("/proc/self/io") as f:
            io = dict(line.split(":") for line in f)
    except OSError:
        return "not known"
    return (f"{int(io['wchar'])} to write calls, "
            f"{int(io['write_bytes'])} to storage")


def peak_memory(dev) -> int:
    peaks = []
    for d in dev:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def read_match_log(path: str) -> List[dict]:
    """The service's emission record: one JSON object per line."""
    out = []
    with open(path) as f:
        for line in f:
            if line.endswith("\n"):
                out.append(json.loads(line))
    return out


def compare(want: np.ndarray, want_ces, records: List[dict],
            delivered: Dict[int, float], enumerated: Dict[int, set],
            n_processed: int, n_accepted: int, L: int,
            guards: Dict[str, int]) -> Dict[str, Tuple[int, int]]:
    """Each number compared, beside its limit (all exact, so 0)."""
    n_chunks = n_accepted // L
    per_chunk: Dict[int, int] = {}
    got = np.zeros(n_accepted, np.int64)
    logged = set()
    for r in records:
        c = int(r["chunk"])
        per_chunk[c] = per_chunk.get(c, 0) + 1
        for idx, v in r["counts"]:
            p = c * L + int(idx[0])
            if p < n_accepted:
                got[p] = int(v)
        for h in r["hits"]:
            logged.add(int(h[0]) if isinstance(h, list) else int(h))
    want_hits = set(np.nonzero(want)[0].tolist())
    checks = {
        "unprocessed": (n_accepted - n_processed, 0),
        "log_chunks": (sum(1 for c in range(n_chunks)
                           if per_chunk.get(c, 0) != 1)
                       + sum(1 for c in per_chunk if c >= n_chunks), 0),
        "count_diff": (int((got != want).sum()), 0),
        "alert_diff": (len(want_hits.symmetric_difference(delivered)), 0),
        "unlogged": (len(logged.symmetric_difference(delivered)), 0),
    }
    if want_ces is not None:
        checks["ce_diff"] = (sum(1 for p, s in want_ces.items()
                                 if enumerated.get(p) != s)
                             + sum(1 for p in enumerated if p not in want_ces),
                             0)
    for k, v in guards.items():
        checks[k] = (int(v), 0)
    return checks


def host_baseline(cfg: dict, stream, accepted: List[int],
                  budget_events: int = 20000) -> None:
    """The program's single-threaded host engine on the run's first events
    (creating each ``Event`` included): the baseline the device path is
    measured against."""
    from repro.core import Event, compile_query
    from repro.core.engine import Engine
    from repro.core.partition import PartitionedEngine
    cq = compile_query(cfg["query"])
    q = cq.query
    cap = None if cfg["sink"]["enumerate"] else 0

    def make():
        return Engine(cq.cea, window=q.window,
                      consume_on_match=q.consume_on_match, max_enumerate=cap)
    eng = PartitionedEngine(make, q.partition_by) if q.partition_by else make()
    n = min(len(accepted), budget_events)
    raws = [stream.raw(g) for g in accepted[:n]]
    t0 = time.perf_counter()
    for r in raws:
        eng.process(Event(r["type"], {k: v for k, v in r.items()
                                      if k != "type"}))
    dt = time.perf_counter() - t0
    log(f"[host] host engine (core.engine, one thread, max_enumerate={cap}):"
        f" {n} events in {dt:.3f} s = {n / dt:.1f} events/s")


def end_to_end_values(run: Run, setup_s: float) -> Dict[str, float]:
    out = {"setup_s": setup_s}
    eps = events_per_s(run)
    if eps is not None:
        out["events_per_s"] = eps
    if run.hit_latency_s:
        out["detect_p50_ms"] = 1e3 * percentile(run.hit_latency_s, 50)
        out["detect_p95_ms"] = 1e3 * percentile(run.hit_latency_s, 95)
    return out


def per_layer_values(bench: dict, cell: dict, run: Run) -> Dict[str, float]:
    out = {}
    for name, read in spec.metric_readers(bench, cell["name"],
                                          run.root).items():
        v = read(run)
        if v is not None:
            out[name] = float(v)
    return out
