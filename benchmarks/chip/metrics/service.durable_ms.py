"""service.durable_ms: the time the device thread spent in the match-log
write (``service.log``) and in checkpoints (``service.checkpoint``: the
snapshot, its copy to the host and the hand-off to the writer) within the
traced window, per chunk whose ``service.step`` ended in it."""
from chipbench import tracing

DURABLE = ("service.log", "service.checkpoint")


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = tr.window()
    chunks = sum(1 for n, _, s, e in tr.host
                 if n == "service.step" and lo <= e <= hi)
    if not chunks:
        return None
    spans = tracing.clip([(n, s, e) for n, _, s, e in tr.host
                          if n in DURABLE], lo, hi)
    return 1e-6 * sum(e - s for _, s, e in spans) / chunks
