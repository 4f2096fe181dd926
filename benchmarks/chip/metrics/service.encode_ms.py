"""service.encode_ms: the mean duration of the program's ``service.encode``
host spans (one chunk's raw events to device operands, on the service's
encoder thread) that started in the traced window."""
SPAN = "service.encode"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = tr.window()
    spans = [e - s for n, _, s, e in tr.host if n == SPAN and lo <= s < hi]
    return 1e-6 * sum(spans) / len(spans) if spans else None
