"""Seconds of set-up spent tracing, lowering and compiling programs or
loading them from the persistent compilation cache (``jax.monitoring``
duration events)."""


def read(ctx):
    return ctx.compile_setup_s
