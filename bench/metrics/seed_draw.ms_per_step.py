"""Host time of the driver's seed draw per step, in ms: the seconds of
the program's ``driver/seeds`` spans (``repro.obs.trace.span``) that
closed while the traced window's profiler session collected, over the
window's steps."""
import stages

SPAN = "driver/seeds"


def read(ctx):
    rec = stages.record()
    span = None if rec is None else rec.spans.get(SPAN)
    if not span:
        return None
    return 1e3 * span[0] / ctx.steps
