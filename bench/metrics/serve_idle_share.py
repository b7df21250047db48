"""Share of the traced serving window in which no operation ran on the
chip (device trace), read as ``device_idle_share`` reads it."""
from bench.metrics.device_idle_share import read  # noqa: F401
