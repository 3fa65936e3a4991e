"""Device time of the frontier relabel per step, in ms, summed over the
sampling levels: the ops under the program's ``relabel`` scope."""
import stages


def read(ctx):
    return stages.ms_per_step(ctx, "relabel")
