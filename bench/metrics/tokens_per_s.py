"""Output tokens emitted per second of the window (host clock): every
token that the window's steps emitted, over all of its time."""


def read(ctx):
    w = ctx["window"]
    return w["events"] / w["seconds"]
