"""Programs compiled or loaded from the compilation cache inside the
measured window (``jax.monitoring`` backend-compile events); there
should be none."""


def read(ctx):
    return float(ctx.window_compiles)
