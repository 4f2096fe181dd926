"""cer_fused_scan_roofline: the least time the chip could take for the
window's calls of the fused Pallas kernel (the larger of operations over
the bf16 peak and bytes over the HBM peak, from shapes by
``roofline/cer_fused_scan.py``) over the summed device time of its trace
events, in percent."""
from chipbench import spec, tracing

KERNEL = "cer_fused_scan"


def read(run):
    shapes = run.kernel_shapes.get(KERNEL)
    if run.trace is None or shapes is None or run.peaks is None:
        return None
    secs, n = tracing.op_time(run.trace, KERNEL)
    if not n or secs <= 0:
        return None
    cost = spec.load_roofline(KERNEL, run.root).cost(shapes)
    least = max(cost["flops"] / run.peaks["bf16_flops_per_s"],
                cost["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / secs
