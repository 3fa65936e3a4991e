"""The whole step's share of the chip's bf16 peak, in %: the model's
forward-plus-backward FLOPs per step at the nominal block sizes (its
module's ``step_flops``, ``ctx.step_flops``), times steps per second of
the window, over chips times the ``peaks.json`` bf16 peak."""


def read(ctx):
    peak = ctx.peak["bf16_flops_per_s"]
    return 100.0 * ctx.step_flops * ctx.steps / ctx.window_s / (
        ctx.chips * peak)
