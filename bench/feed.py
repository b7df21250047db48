"""Event feeds built from one generated unit: what the system is given.

``DayFeed`` hands out whole days. Day ``k`` is the generated day with
user ids, cookies and timestamps moved to a population and a date of its
own, so no two days share a user.

``StreamFeed`` is an endless, time-ordered stream: the firehose as the
log mover delivers it. One period of it holds every event of the
generated period (a whole day, or the first ``period_ms`` of one),
ordered by time within the period; an event that falls after the
period's end (a session running past it) belongs to the period before.
Period ``k`` starts ``k`` periods on, and each event is moved to the
population of the period it belongs to, so the stream never runs dry,
never repeats a user, and no event is ever older than one before it.
"""
from __future__ import annotations

import numpy as np

from bench.loggen import DAY_MS

COLUMNS = ("user_id", "session_id", "timestamp", "code", "ip")
USER_SHIFT = 1 << 40      # above every generated user id
COOKIE_SHIFT = 1 << 45    # above every generated cookie (user * 17 + client)


def shift(cols: dict[str, np.ndarray], day, days_later=None,
          period_ms: int = DAY_MS) -> dict[str, np.ndarray]:
    """Rows moved to the population ``day`` and ``days_later`` periods of
    ``period_ms`` on (default: ``day`` periods); scalars or one per row.
    Works on event columns and on sessions (``start_ts`` for
    ``timestamp``) alike."""
    day = np.asarray(day, np.int64)
    later = day if days_later is None else np.asarray(days_later, np.int64)
    out = dict(cols)
    out["user_id"] = cols["user_id"] + day * USER_SHIFT
    out["session_id"] = cols["session_id"] + day * COOKIE_SHIFT
    ts = "timestamp" if "timestamp" in cols else "start_ts"
    out[ts] = cols[ts] + later * period_ms
    return out


class DayFeed:
    def __init__(self, day: dict[str, np.ndarray]):
        self.base = {k: day[k] for k in COLUMNS}
        self.size = len(day["user_id"])

    def day(self, k: int) -> dict[str, np.ndarray]:
        return shift(self.base, k)


class StreamFeed:
    def __init__(self, day: dict[str, np.ndarray], start_ts_ms: int,
                 period_ms: int = DAY_MS):
        self.period = period_ms
        offset = day["timestamp"] - start_ts_ms
        order = np.lexsort((np.arange(len(offset)), offset % period_ms))
        self.lag = (offset // period_ms)[order]      # periods past its own
        self.base = {k: day[k][order] for k in COLUMNS}   # stream order
        self.first_day = int(self.lag.max())         # keeps day >= 0
        self.size = len(offset)

    def day_of(self, pos: np.ndarray) -> np.ndarray:
        """The day (population) each stream position belongs to."""
        return pos // self.size - self.lag[pos % self.size] + self.first_day

    def take(self, start: int, n: int) -> dict[str, np.ndarray]:
        """Stream positions ``[start, start + n)``."""
        pos = np.arange(start, start + n, dtype=np.int64)
        day = self.day_of(pos)
        return shift({k: v[pos % self.size] for k, v in self.base.items()},
                     day, self.days_later(day), self.period)

    def days_later(self, day):
        """How many periods population ``day`` lies after the generated
        one: period ``k`` then plays every event ``k`` periods on."""
        return day - self.first_day
