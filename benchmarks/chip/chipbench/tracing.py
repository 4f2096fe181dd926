"""Profiler trace of the measured window, and its reduction to numbers.

The harness traces with :func:`capture_options` (``jax.profiler`` with the
Python tracer off, so tracing costs the host little); :func:`load` reads the ``.xplane.pb`` it wrote into a
:class:`Trace`: device operation intervals, the compiled programs (XLA
modules) that ran, and host spans, all on one nanosecond clock.  The
reductions below take a :class:`Trace` and nothing else, so they are tested
on a small recorded trace (``tests/data``) without a chip.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: the host span the harness puts around the measured window
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"

Span = Tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclass
class Trace:
    """What the reductions need from one trace.

    ``ops[d]`` and ``modules[d]`` are device ``d``'s operation and program
    spans; ``host`` holds host spans with their thread (line) name.
    """

    ops: Dict[int, List[Span]] = field(default_factory=dict)
    modules: Dict[int, List[Span]] = field(default_factory=dict)
    host: List[Tuple[str, str, int, int]] = field(default_factory=list)

    def window(self) -> Tuple[int, int]:
        spans = [(s, e) for name, _, s, e in self.host if name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        return min(s for s, _ in spans), max(e for _, e in spans)

    @staticmethod
    def from_json(d: dict) -> "Trace":
        return Trace(
            ops={int(k): [tuple(x) for x in v] for k, v in d["ops"].items()},
            modules={int(k): [tuple(x) for x in v]
                     for k, v in d["modules"].items()},
            host=[tuple(x) for x in d["host"]])


def capture_options():
    """Profiler options: runtime and annotation spans, no Python tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one xplane.pb under {log_dir}, found "
                         f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    tr = Trace()
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(2))
                if line.name == _OPS_LINE:
                    tr.ops.setdefault(dev, []).extend(
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events)
                elif line.name == _MODULES_LINE:
                    tr.modules.setdefault(dev, []).extend(
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events)
            elif plane.name.startswith("/host:"):
                tr.host.extend((e.name, line.name, int(e.start_ns),
                                int(e.end_ns)) for e in line.events)
    return tr


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def clip(spans: List[Span], lo: int, hi: int) -> List[Span]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in spans
            if e > lo and s < hi]


def union(spans: List[Span]) -> List[Tuple[int, int]]:
    """Merged busy intervals of possibly nested or overlapping spans."""
    out: List[List[int]] = []
    for _, s, e in sorted(spans, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(tr: Trace, device: int) -> int:
    lo, hi = tr.window()
    return sum(e - s for s, e in union(clip(tr.ops.get(device, []), lo, hi)))


def devices(tr: Trace) -> List[int]:
    return sorted(d for d, ops in tr.ops.items() if ops)


def busy_seconds(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices that
    ran any."""
    devs = devices(tr)
    if not devs:
        return 0.0
    return sum(busy_ns(tr, d) for d in devs) / len(devs) / 1e9


def window_seconds(tr: Trace) -> float:
    lo, hi = tr.window()
    return (hi - lo) / 1e9


def idle_share(tr: Trace) -> Optional[float]:
    """1 − busy / window, in percent; None where no device op ran."""
    if not devices(tr):
        return None
    return 100.0 * (1.0 - busy_seconds(tr) / window_seconds(tr))


def module_time(tr: Trace, pattern: str, device: int = 0
                ) -> Tuple[float, int]:
    """Seconds and count of the runs of programs whose name matches
    ``pattern`` that started inside the window.  The harness stops the
    trace only once a chunk has completed after the window closed, and the
    service steps one chunk at a time, so each such run is whole."""
    lo, hi = tr.window()
    rx = re.compile(pattern)
    runs = [(s, e) for n, s, e in tr.modules.get(device, [])
            if rx.search(n) and lo <= s < hi]
    return sum(e - s for s, e in runs) / 1e9, len(runs)


def op_time(tr: Trace, pattern: str, device: int = 0) -> Tuple[float, int]:
    """Seconds and count of device operations matching ``pattern`` that
    started inside the window (a kernel is one operation per call)."""
    lo, hi = tr.window()
    rx = re.compile(pattern)
    runs = [(s, e) for n, s, e in tr.ops.get(device, [])
            if rx.search(n) and lo <= s < hi]
    return sum(e - s for s, e in runs) / 1e9, len(runs)


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")[:120]


def self_times(spans: List[Span]) -> List[Tuple[str, int]]:
    """Each span's duration less the spans nested directly inside it (a
    loop or a conditional holds the operations it runs)."""
    order = sorted(spans, key=lambda x: (x[1], -x[2]))
    own = [e - s for _, s, e in order]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(order[i][0], own[i]) for i in range(len(order))]


def top_ops(tr: Trace, k: int = 10, device: int = 0
            ) -> List[Tuple[str, float]]:
    """The operations that took the most device time of their own in the
    window."""
    lo, hi = tr.window()
    tot: Dict[str, int] = {}
    for n, t in self_times(clip(tr.ops.get(device, []), lo, hi)):
        n = short_name(n)
        tot[n] = tot.get(n, 0) + t
    return [(n, t / 1e9) for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10, device: int = 0
              ) -> List[Tuple[str, float]]:
    """The longest idle gaps in the window, each named by the host span
    that overlaps it most (the host's activity while the device waited)."""
    lo, hi = tr.window()
    busy = union(clip(tr.ops.get(device, []), lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for gs, ge in gaps:
        best, label = 0, "no host span"
        for name, line, s, e in tr.host:
            if name == WINDOW_SPAN:
                continue
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, label = ov, f"{name} [{line}]"
        out.append((label[:120], (ge - gs) / 1e9))
    return out


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
