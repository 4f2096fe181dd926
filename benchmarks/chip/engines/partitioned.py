"""``PartitionedStreamingEngine``: one interleaved stream routed by key to
``num_lanes`` lanes, each with its own window ring (and tECS arena)."""


def build(cfg):
    from repro.core import compile_query
    from repro.vector import PartitionedStreamingEngine, VectorEngine
    e = cfg["engine"]
    ve = VectorEngine(compile_query(cfg["query"]),
                      max_window_events=e["ring"])
    return PartitionedStreamingEngine(
        ve, tuple(e["partition_by"]), chunk_len=e["chunk_len"],
        num_lanes=e["num_lanes"], lane_cap=e["lane_cap"],
        arena_capacity=e.get("arena_capacity"),
        strict_overflow=e["strict_overflow"])
