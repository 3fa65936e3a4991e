"""Device time of the feature fetch per step, in ms: the ops under the
program's ``fetch`` scope (the feature store's exchange or gather)."""
import stages


def read(ctx):
    return stages.ms_per_step(ctx, "fetch")
