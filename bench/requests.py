"""Requests to the served behaviour LM, made from the log tier's own
sessions: one day of client events from the seed (``loggen.py``, the
configuration's ``day`` distribution), sessionized by the reference
(``reference/fast.py``).

Each session gives one request, in the order in which the sessions end
(their last event, to the second). Its prompt is its user's sessions of
the day that start no later than it, in order of their start, each
written as the LM pipeline writes a session (``BOS``, the event codes
shifted past the four special tokens, ``EOS``), cut to the first
``prompt_cap`` tokens.
So a user's later requests repeat the prompt of the earlier ones as
their prefix. The traffic file gives ``prompt_cap``, ``max_new_tokens``,
the budget of every request, and ``from_day_share``: the requests start
with the first session to end after that share of the day. Sessions
start uniformly over the day, so the first ones to end are nearly all a
user's first; from the day's middle on, the prompts are as long, and as
often hold earlier sessions, as over the whole day.
"""
from __future__ import annotations

import numpy as np

from bench import loggen
from bench.feed import COLUMNS
from bench.reference import fast

PAD_ID, BOS_ID, EOS_ID, NUM_SPECIALS = 0, 1, 2, 4


class Requests:
    """Prompts held flat: request ``i`` is ``tokens[start[i]:start[i] +
    length[i]]``."""

    def __init__(self, tokens: np.ndarray, start: np.ndarray,
                 length: np.ndarray, earlier: np.ndarray):
        self.tokens, self.start, self.length = tokens, start, length
        self.earlier = earlier        # the prompt holds an earlier session

    def __len__(self) -> int:
        return len(self.length)

    def prompt(self, i: int) -> np.ndarray:
        a = int(self.start[i])
        return self.tokens[a:a + int(self.length[i])]


def _ranges(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """``start .. start + length - 1`` for each pair, one after another."""
    ends = np.cumsum(lengths)
    return (np.repeat(starts - (ends - lengths), lengths)
            + np.arange(int(ends[-1]) if len(ends) else 0))


def from_sessions(sess: dict, prompt_cap: int,
                  from_ms: int | None = None) -> Requests:
    """Requests of a day's sessions (``fast.sessionize``'s form), from the
    first session to end at or after ``from_ms`` on."""
    order = np.lexsort((sess["start_ts"], sess["user_id"]))
    n_tok = (np.diff(sess["offsets"]) + 2)[order]
    user = sess["user_id"][order]
    # every session as BOS, symbols + NUM_SPECIALS, EOS, users one after
    # another, each user's sessions by start
    tokens = np.full(int(n_tok.sum()), EOS_ID, np.int32)
    at = np.r_[0, np.cumsum(n_tok)[:-1]].astype(np.int64)
    tokens[at] = BOS_ID
    tokens[_ranges(at + 1, n_tok - 2)] = sess["symbols"][
        _ranges(sess["offsets"][order], n_tok - 2)] + NUM_SPECIALS
    first = np.r_[True, user[1:] != user[:-1]]
    user_at = np.maximum.accumulate(np.where(first, at, 0))
    length = np.minimum(at + n_tok - user_at, prompt_cap)
    end = (sess["start_ts"] + 1000 * sess["duration_s"])[order]
    by_end = np.lexsort((np.arange(len(order)), end))
    if from_ms is not None:
        by_end = by_end[end[by_end] >= from_ms]
    return Requests(tokens, user_at[by_end].astype(np.int64),
                    length[by_end].astype(np.int64), ~first[by_end])


def generate(cfg: dict, traffic: dict, seed: int) -> Requests:
    """The requests of the configuration's day from ``seed``."""
    day = loggen.generate(cfg, seed)
    sess = fast.sessionize(*(day[k] for k in COLUMNS), dedup=cfg["dedup"],
                           gap_ms=cfg["gap_ms"])
    return from_sessions(sess, traffic["prompt_cap"],
                         cfg["day"]["start_ts_ms"] + int(
                             traffic["from_day_share"] * loggen.DAY_MS))
