"""The least bytes a step must move in device memory, per event, from the
configuration's shapes alone: a yardstick independent of how the program
moves them (``hbm_roofline_share`` divides it by the peak).

An event enters as its five keys and a valid flag (user, cookie and
timestamp of 8 bytes, code of 4, ip of 8, flag of 1: 37 bytes) and
leaves as one 4-byte symbol of a stored session. The stream's tick also
reads and writes its ring of open sessions once: ``max_open`` slots, each
``max_len`` events of symbol, timestamp and ip (4 + 8 + 8 bytes) plus
user, cookie, length and a valid flag (8 + 8 + 4 + 1 bytes). The day job
also writes its rollups: the dense n-gram counts and the funnel reach, 8
bytes a cell.
"""
from __future__ import annotations

EVENT_IN = 8 + 8 + 8 + 4 + 8 + 1
SYMBOL_OUT = 4
RING_EVENT = 4 + 8 + 8
RING_SLOT = 8 + 8 + 4 + 1


def stream_tick(cfg: dict) -> float:
    """Bytes per event of one full tick of the streaming tier."""
    ring = cfg["max_open"] * (cfg["max_len"] * RING_EVENT + RING_SLOT)
    return EVENT_IN + SYMBOL_OUT + 2 * ring / cfg["tick_capacity"]


def day_job(cfg: dict) -> float:
    """Bytes per event of one day of the daily session job."""
    rollups = 8 * (cfg["alphabet_size"] ** cfg["ngram_n"]
                   + len(cfg["funnel"]))
    return EVENT_IN + SYMBOL_OUT + rollups / cfg["events_per_day"]
