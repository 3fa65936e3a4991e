"""Device time of the fused sampler kernel per step, summed over the
sampling levels, in ms, from the trace: the device ops whose name holds
``KERNEL``."""
import kernels

KERNEL = "fused_sample"


def read(ctx):
    seconds = kernels.seconds(ctx.trace, KERNEL)
    if not seconds:
        return None
    return 1e3 * seconds / ctx.steps
