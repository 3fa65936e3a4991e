"""Device time of neighbour sampling per step, in ms: the ops under the
program's ``sample`` scope (every level, kernel and relabel included)."""
import stages


def read(ctx):
    return stages.ms_per_step(ctx, "sample")
