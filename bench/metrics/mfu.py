"""The whole step's share of the chip's bf16 peak, in %: GraphSAGE
forward-plus-backward FLOPs per step at the nominal block sizes
(``flops.sage_step_flops``), times steps per second of the window, over
chips times the ``peaks.json`` bf16 peak."""
import flops


def read(ctx):
    peak = ctx.peak["bf16_flops_per_s"]
    work = flops.sage_step_flops(ctx.batch, ctx.fanouts, ctx.dims)
    return 100.0 * work * ctx.steps / ctx.window_s / (ctx.chips * peak)
