"""The program's own marks in the profiler trace (DESIGN.md §12).

The served path records one host span per chunk and stage, named
``<layer>.<stage>`` and keyed by the chunk index, and the compiled steps
carry ``jax.named_scope`` stages.  These tests run the service on the CPU
under the profiler, read its spans back with the benchmark's trace loader
(``benchmarks/chip/chipbench/tracing.py``) and their keywords with
``jax.profiler.ProfileData``, and lower the steps to find every scope in
their op-name metadata.
"""
import collections
import gc
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.runtime import StreamService
from repro.vector import (PartitionedStreamingEngine, StreamingVectorEngine,
                          VectorEngine)

CHIP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "chip")
sys.path.insert(0, CHIP)
from chipbench import tracing  # noqa: E402

QUERY = "SELECT * FROM S WHERE A ; B+ ; C WITHIN 50 events"
CHUNK, CHUNKS = 16, 6
#: the named scopes of the partitioned step, stage by stage
SCOPES = ("assign", "scatter", "scan", "relabel", "arena")


def raws(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"type": "ABC"[int(rng.integers(0, 3))], "v": 1.0,
             "uid": int(rng.integers(0, 4))} for _ in range(n)]


def part_engine():
    ve = VectorEngine(QUERY, use_pallas=False)
    return PartitionedStreamingEngine(ve, ("uid",), chunk_len=CHUNK,
                                      num_lanes=4, arena_capacity=1024)


def keywords(log_dir):
    """``(name, keywords)`` of each of the program's host spans."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("service.", "engine.", "host."))]


def test_service_spans_per_chunk(tmp_path):
    svc = StreamService(part_engine(), str(tmp_path / "svc"),
                        checkpoint_every=2)
    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir,
                             profiler_options=tracing.capture_options())
    try:
        for r in raws(CHUNK * CHUNKS):
            assert svc.submit(r, block=True, timeout=60.0).accepted
        svc.drain(timeout=120.0)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    metrics = svc.metrics
    svc.close(checkpoint=False)

    tr = tracing.load(log_dir)
    spans = collections.defaultdict(list)
    for name, _, s, e in tr.host:
        spans[name].append((s, e))
    steps = sorted(spans["service.step"])
    assert len(steps) == CHUNKS
    assert len(spans["service.encode"]) == CHUNKS
    assert len(spans["service.log"]) == CHUNKS
    assert len(spans["service.deliver"]) == CHUNKS
    # one readback inside each step
    for s, e in steps:
        assert sum(s <= a and b <= e for a, b in spans["engine.readback"]) \
            == 1
    # the device thread waits for input before every chunk; the first wait
    # began before the trace did, so the trace holds the other five
    assert len(spans["service.wait_input"]) == CHUNKS - 1

    kw = keywords(log_dir)
    by_chunk = collections.defaultdict(collections.Counter)
    for name, k in kw:
        if "chunk" in k:
            by_chunk[int(k["chunk"])][name] += 1
    assert sorted(int(k["step_num"]) for n, k in kw
                  if n == "service.step") == list(range(CHUNKS))
    for c in range(CHUNKS):
        assert by_chunk[c]["service.encode"] == 1
        assert by_chunk[c]["service.log"] == 1
        assert by_chunk[c]["service.deliver"] == 1

    ckpts = [k for n, k in kw if n == "service.checkpoint"]
    assert len(ckpts) == CHUNKS // 2 == metrics.checkpoints
    assert [int(k["chunk"]) for k in ckpts] == [1, 3, 5]
    assert sum(int(k["bytes"]) for k in ckpts) == metrics.checkpoint_bytes
    assert metrics.checkpoint_bytes > 0

    assert any(int(k["gen"]) == 2 for n, k in kw if n == "host.gc")


def test_gc_hook_lives_while_a_service_is_open(tmp_path):
    from repro.runtime import service
    before = list(gc.callbacks)
    a = StreamService(part_engine(), str(tmp_path / "a"))
    b = StreamService(part_engine(), str(tmp_path / "b"))
    assert gc.callbacks.count(service._GC_SPANS) == 1
    a.close()
    assert service._GC_SPANS in gc.callbacks
    b.close()
    assert gc.callbacks == before


@pytest.mark.parametrize("kind", ["partitioned", "streaming_arena"])
def test_compiled_step_scopes(kind):
    """Every stage's scope reaches the lowered step's op-name metadata."""
    if kind == "partitioned":
        eng = part_engine()
        A = len(eng.encoder.attrs)
        lowered = eng._step.lower(
            jnp.zeros((CHUNK, A), jnp.float32),
            jnp.zeros((CHUNK,), jnp.uint32), eng.state, jnp.int32(0),
            jnp.arange(CHUNK, dtype=jnp.int32))
        want = SCOPES
    else:
        eng = StreamingVectorEngine(VectorEngine(QUERY, use_pallas=False),
                                    chunk_len=CHUNK, batch=2,
                                    arena_capacity=1024)
        A = len(eng.encoder.attrs)
        lowered = eng._step.lower(
            jnp.zeros((CHUNK, 2, A), jnp.float32), eng.state,
            jnp.int32(0), jnp.int32(0))
        want = ("scan", "arena")
    text = lowered.as_text(debug_info=True)
    for scope in want:
        assert f"/{scope}/" in text, scope
