"""The comparison that decides ``correct``: what the program stored and
folded against the reference over the same events. Every number is exact
(limit 0): a count of differences, of drops, or of compiles."""
from __future__ import annotations

import sys

import numpy as np

from bench.reference import fast

LIMITS = dict(sessions_mismatched=0, bigram_abs_diff=0, funnel_abs_diff=0,
              events_dropped=0, sessions_truncated=0, window_compiles=0)


def compare(out: dict, ref: dict, window_compiles: int) -> dict:
    """``{name: {"value": v, "limit": l}}`` for each number compared."""
    values = dict(
        sessions_mismatched=fast.multiset_difference(
            fast.session_hashes(out["sessions"]),
            fast.session_hashes(ref["sessions"])),
        bigram_abs_diff=int(np.abs(out["bigrams"] - ref["bigrams"]).sum()),
        funnel_abs_diff=int(np.abs(out["reach"] - ref["reach"]).sum()),
        events_dropped=int(out["dropped"]),
        sessions_truncated=int(out["truncated"]),
        window_compiles=int(window_compiles))
    return {k: dict(value=v, limit=LIMITS[k]) for k, v in values.items()}


def passed(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def report(compared: dict, stream=sys.stderr) -> None:
    """One line per number: its name, its value and its limit."""
    for k, c in compared.items():
        print(f"{k} {c['value']} limit {c['limit']}", file=stream)
