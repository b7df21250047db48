"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module has ``read(ctx) -> float | None``; ``None`` means the run
gave it nothing to read, and the metric is left out of the result line.
``ctx`` holds:

* ``setup_s``: process start to window start (host clock);
* ``window``: what ``loops.py`` measured (``events``, ``seconds``,
  ``step_s``, and for an open loop ``lags_s`` and ``backlog_end``);
  ``events`` are tokens emitted in a serving cell;
* ``span``: the host span around one step (``tick``, ``day`` or
  ``serve_step``);
* ``trace``: ``trace_reduce.reduce`` of the traced window, or None;
* ``peaks``: the chip's row of ``peaks.json`` (None in a rehearsal);
* ``chips``: devices the cell runs on;

and what the system adds (``System.ctx``):

* log systems: ``bytes_per_event``, the configuration's ``least_bytes``
  function;
* the serving system: ``window_flops``, the model operations of the
  window's tokens (``model_flops.py``), ``window_tokens``, the tokens
  the window's requests gained, ``prompt_tokens`` of the requests
  admitted in the window and ``prefill_skipped``, those of them the
  prefix cache served.
"""
