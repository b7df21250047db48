"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module has ``read(ctx) -> float | None``; ``None`` means the run
gave it nothing to read, and the metric is left out of the result line.
``ctx`` holds:

* ``setup_s``: process start to window start (host clock);
* ``window``: what ``loops.py`` measured (``events``, ``seconds``,
  ``step_s``, and for an open loop ``lags_s`` and ``backlog_end``);
* ``span``: the host span around one step (``tick`` or ``day``);
* ``trace``: ``trace_reduce.reduce`` of the traced window, or None;
* ``peaks``: the chip's row of ``peaks.json`` (None in a rehearsal);
* ``bytes_per_event``: the configuration's ``least_bytes`` function;
* ``chips``: devices the cell runs on.
"""
