"""Host time of the window's device-to-host pulls: the tick's
``streampipe.pull`` of its closed block, rollups and counters, the day
job's ``distpipe.pull`` of its outputs and ``distpipe.gather`` of the
sessions into one relation; in ns per event of the traced window
(program spans)."""
from bench.program_spans import ns_per_event


def read(ctx):
    return ns_per_event(ctx, ("streampipe.pull", "distpipe.pull",
                              "distpipe.gather"))
