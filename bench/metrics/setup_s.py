"""Process start to window start: generation, building, compiling or
loading compiled programs, and the warm steps (host clock)."""


def read(ctx):
    return ctx["setup_s"]
