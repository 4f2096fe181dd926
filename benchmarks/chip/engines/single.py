"""``StreamingVectorEngine`` over one stream (batch 1), the ring sized by
the query's window unless the configuration states one."""


def build(cfg):
    from repro.core import compile_query
    from repro.vector import StreamingVectorEngine, VectorEngine
    e = cfg["engine"]
    ve = VectorEngine(compile_query(cfg["query"]),
                      **({"max_window_events": e["ring"]} if e.get("ring")
                         else {}))
    return StreamingVectorEngine(
        ve, chunk_len=e["chunk_len"], batch=1,
        arena_capacity=e.get("arena_capacity"),
        strict_overflow=e["strict_overflow"])
