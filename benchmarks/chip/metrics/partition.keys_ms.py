"""partition.keys_ms: the mean duration of the program's ``partition.keys``
host spans (the partition layer's per-chunk host work on the device
thread: the collision audit of live keys and the key table's upkeep) that
started in the traced window."""
SPAN = "partition.keys"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = tr.window()
    spans = [e - s for n, _, s, e in tr.host if n == SPAN and lo <= s < hi]
    return 1e-6 * sum(spans) / len(spans) if spans else None
