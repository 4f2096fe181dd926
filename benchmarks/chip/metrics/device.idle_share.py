"""device.idle_share: 1 - (union of device operation intervals / traced
window), in percent, from the profiler trace of the window."""
from chipbench import tracing


def read(run):
    if run.trace is None:
        return None
    return tracing.idle_share(run.trace)
