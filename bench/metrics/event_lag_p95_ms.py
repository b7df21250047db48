"""95th percentile, over every event due in an open-loop window, of the
completion of the step that took it minus its due time (host clock)."""
import numpy as np


def read(ctx):
    lags = ctx["window"].get("lags_s")
    if lags is None:
        return None
    return float(np.percentile(lags, 95)) * 1e3
