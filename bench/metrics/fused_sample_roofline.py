"""The fused sampler kernel's share of its roofline, in %: the least time
the bytes it must move take at the chip's HBM bandwidth (the bound:
the kernel does no arithmetic to speak of), over the kernel's device time
in the trace.  Bytes per step are ``flops.fused_sample_bytes``; the
kernel's events are the device ops whose name holds ``KERNEL``."""
import flops
import kernels

KERNEL = "fused_sample"


def read(ctx):
    seconds = kernels.seconds(ctx.trace, KERNEL)
    if not seconds:
        return None
    bw = ctx.peak["hbm_bytes_per_s"]
    need = flops.fused_sample_bytes(ctx.batch, ctx.fanouts) * ctx.steps / bw
    return 100.0 * need / seconds
