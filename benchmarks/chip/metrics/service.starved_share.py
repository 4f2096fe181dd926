"""service.starved_share: the union of the program's ``service.wait_input``
host spans (its device thread waiting for an encoded chunk) over the traced
window, in percent; nothing where the program records no such span."""
from chipbench import tracing

SPAN = "service.wait_input"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    spans = [(n, s, e) for n, _, s, e in tr.host if n == SPAN]
    if not spans:
        return None
    lo, hi = tr.window()
    waits = tracing.union(tracing.clip(spans, lo, hi))
    return 100.0 * sum(e - s for s, e in waits) / (hi - lo)
