"""95th percentile, over every sample learned in the window, of the time from
the sample's hand-over through `sample_fn` to the host holding the result of
the superstep that learned it (host clock)."""
import numpy as np


def read(ctx):
    lags = ctx.window.lags_s
    if lags.size == 0:
        return None
    return float(np.percentile(lags, 95)) * 1e3
