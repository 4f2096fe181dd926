"""step.device_ms: device time of the engine's compiled step per run of it,
from the trace's XLA module spans.  The step is the jitted ``_step_impl``
(single stream), ``_arena_step_impl`` or ``_part_step_impl`` (partitioned):
modules named ``jit__..._step_impl``."""
from chipbench import tracing

MODULE = r"_step_impl"


def read(run):
    if run.trace is None:
        return None
    secs, n = tracing.module_time(run.trace, MODULE)
    return 1e3 * secs / n if n else None
