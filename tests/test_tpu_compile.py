"""Compile the served path for a described TPU v5e — no chip needed.

Interpret mode hides the TPU compiler's tiling and VMEM refusals; these
tests lower the fused Pallas kernel (every static variant) and the jitted
streaming and partitioned steps for one chip of a described ``v5e:2x2``
and check that the kernel is in the program and that it fits the chip's
memory.  The topology is described inside a fixture, never at import: only
the process that runs these tests may load the TPU compiler.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compile_query
from repro.kernels import ops
from repro.vector import (PartitionedStreamingEngine, StreamingVectorEngine,
                          VectorEngine)

V5E_HBM_BYTES = 16 * 1000 ** 3

#: the quickstart's count-window query at the chip smoke's kernel widths
KERNEL_QUERY = ("SELECT * FROM S WHERE SELL AS a ; BUY AS b "
                "FILTER a[price > 25.0] AND b[price < 10.0] "
                "WITHIN 100 events")
LANES, CHUNK = 1024, 512

VARIANTS = {
    "count": (KERNEL_QUERY, {}, False),
    "time": ("SELECT * FROM S WHERE SELL AS a ; BUY AS b FILTER "
             "a[price > 25.0] AND b[price < 10.0] WITHIN 30 seconds",
             {"max_window_events": 256}, False),
    "trace": (KERNEL_QUERY, {}, True),
    "latest": ("SELECT LAST * FROM S WHERE A ; B+ ; C WITHIN 6", {}, False),
    "consume": ("SELECT * FROM S WHERE A ; B+ ; C WITHIN 7 [ts] "
                "CONSUME BY ANY", {"max_window_events": 64}, False),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def like(tree, sharding):
    return jax.tree.map(lambda x: spec(np.shape(x), x.dtype, sharding),
                        tree)


def compiled_fits(compiled):
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return text


def load_chip_smoke():
    """The repo-root ``chip_smoke.py`` script as a module (its widths)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec_ = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


def on_chip(engine):
    """Steer a CPU-planned engine onto the compiled (non-interpret) kernel
    route, as it plans on a TPU."""
    engine.routes = {k: dataclasses.replace(r, interpret=False)
                     for k, r in engine.routes.items()}
    return engine


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_fused_kernel_compiles(one_chip, variant):
    query, kw, trace = VARIANTS[variant]
    ve = VectorEngine(query, **kw)
    t = ve.tables
    A = len(ve.encoder.attrs)

    route = ops.plan_pipeline(
        T=CHUNK, B=LANES, A=A, W=ve.ring, S=t.num_states,
        NC=t.num_classes, NQ=1, V=t.class_ind.shape[0],
        timed=ve.window.is_time, latest=t.latest_q is not None,
        consume=t.consume_sq is not None, trace=trace, interpret=False)
    assert route.path == "pallas" and not route.interpret, route

    def run(attrs, state, start, ts):
        return ops.cer_pipeline(
            attrs, ve.encoder.specs, t.class_of, t.class_ind, t.m_all,
            t.finals[None, :], state, init_mask=t.init_mask,
            window=ve.window, start_pos=start,
            event_ts=ts if ve.window.is_time else None, route=route,
            return_trace=trace, latest_q=t.latest_q,
            consume_sq=t.consume_sq)
    args = (spec((CHUNK, LANES, A), jnp.float32, one_chip),
            like(jax.eval_shape(lambda: ve.init_state(LANES)), one_chip),
            spec((), jnp.int32, one_chip),
            spec((CHUNK, LANES), jnp.float32, one_chip))
    text = compiled_fits(jax.jit(run).lower(*args).compile())
    assert "tpu_custom_call" in text


def test_streaming_arena_step_compiles(one_chip):
    """The quickstart's streaming step with the arena on: the kernel for
    the scan, the block builder (lane groups) in XLA, within HBM."""
    se = on_chip(StreamingVectorEngine(VectorEngine(KERNEL_QUERY),
                                       chunk_len=CHUNK, batch=LANES,
                                       arena_capacity=8192))
    assert se.routes["scan"].path == "pallas"
    assert se.routes["arena"] == ops.arena_route(se._arena_tables)
    assert "no state-axis gather" in se.routes["arena"].reason
    A = len(se.encoder.attrs)
    args = (spec((CHUNK, LANES, A), jnp.float32, one_chip),
            like(se.state, one_chip), spec((), jnp.int32, one_chip),
            spec((), jnp.int32, one_chip))
    text = compiled_fits(se._step.lower(*args).compile())
    assert "tpu_custom_call" in text


def test_partitioned_served_step_compiles(one_chip):
    """The chip smoke's served step: stock Q3 over 4 partitions, arena on.
    Its time-window ring is too wide for the kernel's VMEM tile, so the
    recorded scan route is XLA; the block builder's dense records
    (lane_cap x M per lane) must still fit the chip's HBM."""
    cs = load_chip_smoke()
    cfg = cs.SERVED
    pse = PartitionedStreamingEngine(
        VectorEngine(compile_query(cs.Q3), max_window_events=cfg["ring"]),
        ("volume",), chunk_len=cfg["chunk"], num_lanes=4,
        lane_cap=cfg["lane_cap"], arena_capacity=cfg["arena_capacity"],
        strict_overflow=True)
    route = pse.routes["scan"]
    assert route.path == "xla" and "VMEM" in route.reason, route
    T, A = pse.chunk_len, len(pse.encoder.attrs)
    args = (spec((T, A), jnp.float32, one_chip),
            spec((T,), jnp.uint32, one_chip), like(pse.state, one_chip),
            spec((), jnp.int32, one_chip), spec((T,), jnp.int32, one_chip),
            spec((T,), jnp.float32, one_chip))
    compiled_fits(pse._step.lower(*args).compile())


def test_served_widths_cover_the_stream():
    """The served step compiled above is the one the chip smoke needs: its
    ring holds every partition's live 30 s window of the stream at the
    smoke's rate, and lane_cap the most events one partition gets in a
    chunk."""
    from repro.data.streams import stock_stream
    cs = load_chip_smoke()
    cfg = cs.SERVED
    events = stock_stream(300_000, seed=0, events_per_sec=cfg["rate"])
    win = compile_query(cs.Q3).query.window
    assert cs.ring_for(events, "volume", win.time_attr, win.size) \
        <= cfg["ring"]
    assert cs.lane_fill(events, "volume", cfg["chunk"]) <= cfg["lane_cap"]
