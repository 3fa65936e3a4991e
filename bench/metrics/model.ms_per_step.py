"""Device time of the model's forward and backward per step, in ms: the
ops under the program's ``forward`` scope, the backward pass's
``transpose(jvp(forward))`` included (the optimizer is not)."""
import stages


def read(ctx):
    return stages.ms_per_step(ctx, "forward")
