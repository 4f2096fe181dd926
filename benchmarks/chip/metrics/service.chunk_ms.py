"""service.chunk_ms: the 95th percentile of ``ServiceMetrics.chunk_latency_s``
(chunk formed to its sinks done) over the chunks completed in the window."""
from chipbench.drive import percentile


def read(run):
    lat = [done - formed for formed, done, _ in run.completed()]
    return 1e3 * percentile(lat, 95) if lat else None
