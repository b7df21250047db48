"""Events stored and folded per second of the window (host clock): all of
the window's events over all of its time."""


def read(ctx):
    w = ctx["window"]
    return w["events"] / w["seconds"]
