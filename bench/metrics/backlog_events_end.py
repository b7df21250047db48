"""Events due by the end of an open-loop window that no tick had taken
when it ended: how far ingest trails the schedule."""


def read(ctx):
    return ctx["window"].get("backlog_end")
