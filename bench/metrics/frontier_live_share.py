"""Share of the bottom sampling level's frontier slots that hold a node
after dedup, in %: the program's ``frontier_live`` over
``frontier_slots`` step metrics for the last level, averaged over the
steps the driver took while the traced window's profiler session
collected."""
import numpy as np

import stages


def read(ctx):
    rec = stages.record()
    steps = [] if rec is None else [m for m in list(rec.steps)
                                    if "frontier_live" in m]
    if not steps:
        return None
    shares = [float(np.asarray(m["frontier_live"])[-1])
              / float(np.asarray(m["frontier_slots"])[-1]) for m in steps]
    return 100.0 * sum(shares) / len(shares)
