"""Host time of the window's ``store.append_sessions`` spans (encoding
the closed sessions into a segment and indexing it), in ns per event of
the traced window (program spans)."""
from bench.program_spans import ns_per_event


def read(ctx):
    return ns_per_event(ctx, ("store.append_sessions",))
