"""host.gc_share: the union of the program's ``host.gc`` host spans (each
garbage collection, every generation, on the thread that ran it) over the
traced window, in percent; nothing where the program records no span of
its own (a window without a collection reads 0)."""
from chipbench import tracing

SPAN = "host.gc"


def read(run):
    tr = run.trace
    if tr is None or not any(n.startswith("service.") for n, *_ in tr.host):
        return None
    lo, hi = tr.window()
    gcs = tracing.union(tracing.clip(
        [(n, s, e) for n, _, s, e in tr.host if n == SPAN], lo, hi))
    return 100.0 * sum(e - s for s, e in gcs) / (hi - lo)
