"""``KeyPoolEngine``: one interleaved stream whose keys hold slots of a
pool in two ring classes (narrow and wide), freed once a key's window has
passed (DESIGN.md §6).  Every ``REPORT`` chunks the run's log gets the
pool's counters so far (``PartitionStats``) and the largest count of one
position so far: the counts are float32 on the device, exact below
2^24."""

REPORT = 128


def build(cfg):
    from repro.core import compile_query
    from repro.vector import KeyPoolEngine, VectorEngine
    e = cfg["engine"]
    ve = VectorEngine(compile_query(cfg["query"]),
                      max_window_events=e["ring"])
    engine = KeyPoolEngine(
        ve, tuple(e["partition_by"]), chunk_len=e["chunk_len"],
        slots=e["slots"], wide_slots=e["wide_slots"],
        narrow_ring=e["narrow_ring"], strict_overflow=e["strict_overflow"])
    feed = engine.feed_keyed
    largest = [0]

    def feed_keyed(*args, **kwargs):
        out = feed(*args, **kwargs)
        largest[0] = max(largest[0], int(out[0].max()))
        st = engine.stats
        chunks = st.events // engine.chunk_len
        if chunks % REPORT == 0:
            print(f"[pool] {chunks} chunks: slots_scanned per chunk "
                  f"{st.slots_scanned / chunks:.1f}, keys_live "
                  f"{st.keys_live}, keys_peak {st.keys_peak}, reclaimed "
                  f"{st.reclaimed}, promoted {st.promoted}, pool_overflow "
                  f"{st.pool_overflow}, spilled {st.spilled_table}, largest "
                  f"count {largest[0]}",
                  flush=True)
        return out

    engine.feed_keyed = feed_keyed
    return engine
