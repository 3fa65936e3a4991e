"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of the device's op intervals) / window."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
