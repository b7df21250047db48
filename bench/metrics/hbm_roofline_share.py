"""The whole step's share of the chips' peak HBM bandwidth: the least
bytes per event the configuration's shapes demand (``least_bytes.py``)
times the traced window's events per second, over the peak of its chips
(``peaks.json``). Read in the traced run."""


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    w = ctx["window"]
    rate = w["events"] / w["seconds"]
    return (ctx["bytes_per_event"] * rate
            / (ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"]))
