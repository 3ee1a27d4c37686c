"""90th percentile of the per-step times in the timed window: the gaps
between the ready times of consecutive steps (host clock)."""
import numpy as np


def read(ctx):
    if len(ctx.step_s) < 10:
        return None
    return float(np.percentile(np.asarray(ctx.step_s) * 1e3, 90))
