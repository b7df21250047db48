"""Materialized session sequences (paper §4.2).

The materialized relation is exactly the paper's (plus start_ts, which the
log mover knows anyway)::

    user_id: long, session_id: long, ip: long,
    session_sequence: symbols, duration: int

On TPU the ``session_sequence`` string becomes a padded int32 symbol tensor
(``symbols (S, L)`` + ``length (S,)``); ``as_unicode_strings`` reproduces the
paper's exact string representation (one unicode char per event, small code
point = frequent event) and ``varint.py`` its on-disk byte encoding.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

from .sessionize import PAD_CODE, Sessionized

# Unicode code-point mapping must skip the surrogate block D800-DFFF to keep
# every sequence a *valid* unicode string (paper: "any session sequence is a
# valid unicode string").
_SURROGATE_START = 0xD800
_SURROGATE_SIZE = 0x800


def code_to_codepoint(code: np.ndarray | int):
    """Frequency code -> unicode code point (bijective, order-preserving)."""
    c = np.asarray(code)
    return np.where(c >= _SURROGATE_START, c + _SURROGATE_SIZE, c)


def codepoint_to_code(cp: np.ndarray | int):
    cp = np.asarray(cp)
    return np.where(cp >= _SURROGATE_START + _SURROGATE_SIZE,
                    cp - _SURROGATE_SIZE, cp)


@dataclass
class SessionSequences:
    """Columnar store of materialized session sequences."""
    symbols: np.ndarray     # (S, L) int32, PAD_CODE padded
    length: np.ndarray      # (S,) int32 (true length; may exceed L if truncated)
    user_id: np.ndarray     # (S,) int64
    session_id: np.ndarray  # (S,) int64
    ip: np.ndarray          # (S,) int64
    start_ts: np.ndarray    # (S,) int64
    duration_s: np.ndarray  # (S,) int32

    @staticmethod
    def from_sessionized(s: Sessionized) -> "SessionSequences":
        t = s.trimmed()
        return SessionSequences(
            symbols=np.asarray(t.symbols), length=np.asarray(t.length),
            user_id=np.asarray(t.user_id), session_id=np.asarray(t.session_id),
            ip=np.asarray(t.ip), start_ts=np.asarray(t.start_ts),
            duration_s=np.asarray(t.duration_s))

    def __len__(self) -> int:
        return len(self.length)

    @property
    def max_len(self) -> int:
        return self.symbols.shape[1]

    def stored_length(self) -> np.ndarray:
        """Length actually materialized (<= max_len)."""
        return np.minimum(self.length, self.max_len)

    def mask(self) -> np.ndarray:
        """(S, L) bool validity mask."""
        return np.arange(self.max_len)[None, :] < self.stored_length()[:, None]

    def session_symbols(self, i: int) -> np.ndarray:
        return self.symbols[i, : min(int(self.length[i]), self.max_len)]

    def session_string(self, i: int) -> str:
        """One session in the paper's representation: a valid unicode string,
        one char per event, small code point = frequent event."""
        cps = code_to_codepoint(self.session_symbols(i))
        return "".join(chr(int(c)) for c in cps)

    def as_unicode_strings(self) -> list[str]:
        """The paper's representation: one valid unicode string per session."""
        return [self.session_string(i) for i in range(len(self))]

    @staticmethod
    def from_unicode_strings(strings: list[str], **meta) -> "SessionSequences":
        s = len(strings)
        lens = np.array([len(x) for x in strings], np.int32)
        max_len = int(lens.max()) if s else 0
        symbols = np.full((s, max_len), PAD_CODE, np.int32)
        for i, string in enumerate(strings):
            cps = np.array([ord(ch) for ch in string], np.int64)
            symbols[i, : len(string)] = codepoint_to_code(cps)
        def get(name, dtype, fill=0):
            return np.asarray(meta.get(name, np.full(s, fill)), dtype)
        return SessionSequences(
            symbols=symbols, length=lens,
            user_id=get("user_id", np.int64), session_id=get("session_id", np.int64),
            ip=get("ip", np.int64), start_ts=get("start_ts", np.int64),
            duration_s=get("duration_s", np.int32))

    # ---- persistence (atomic, the log-mover way) ----
    def save(self, path: str) -> None:
        tmp = path + ".tmp.npz"  # explicit .npz so numpy doesn't rename it
        np.savez_compressed(
            tmp,
            symbols=self.symbols, length=self.length, user_id=self.user_id,
            session_id=self.session_id, ip=self.ip, start_ts=self.start_ts,
            duration_s=self.duration_s)
        os.replace(tmp, path)

    @staticmethod
    def load(path: str) -> "SessionSequences":
        z = np.load(path)
        return SessionSequences(
            symbols=z["symbols"], length=z["length"], user_id=z["user_id"],
            session_id=z["session_id"], ip=z["ip"], start_ts=z["start_ts"],
            duration_s=z["duration_s"])

    def summary(self) -> dict:
        sl = self.stored_length()
        return dict(
            sessions=int(len(self)),
            events=int(self.length.sum()),
            mean_len=float(self.length.mean()) if len(self) else 0.0,
            mean_duration_s=float(self.duration_s.mean()) if len(self) else 0.0,
            distinct_users=int(len(np.unique(self.user_id))),
            stored_events=int(sl.sum()),
        )

    def to_json_rows(self, limit: int = 10) -> str:
        # Materialize only the strings actually emitted — the previous
        # version rebuilt every session string once per row (O(S^2)).
        rows = []
        for i in range(min(limit, len(self))):
            rows.append(dict(
                user_id=int(self.user_id[i]), session_id=int(self.session_id[i]),
                ip=int(self.ip[i]), duration=int(self.duration_s[i]),
                session_sequence=self.session_string(i)
                if i < 3 else f"<{int(self.length[i])} symbols>"))
        return json.dumps(rows, ensure_ascii=True, indent=2)
