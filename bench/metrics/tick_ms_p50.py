"""Median host wall time of one streaming ``tick()`` in the window: the
device tick, the closed block's pull, the store append and the fold."""
import statistics


def read(ctx):
    if ctx["span"] != "tick":
        return None
    return statistics.median(ctx["window"]["step_s"]) * 1e3
