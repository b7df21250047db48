"""The reference semantics of ``oracle.py``, computed with whole arrays.

Retry dedup keeps the first of the rows equal in (user, session,
timestamp, code, ip). Sessions group the kept rows by (user, session),
order them by (timestamp, code, ip) and split where two neighbours lie
more than the gap apart. A session carries its symbols in order, the
largest ip, the first timestamp and ``(last - first) // 1000`` seconds.
Bigram counts and funnel reach are taken over the sessions' symbols.

Sessions are held flat: ``symbols`` concatenated, ``offsets`` (S + 1) into
it. ``bench/tests`` hold every function here equal to ``oracle.py``.
"""
from __future__ import annotations

import numpy as np

from .oracle import DEFAULT_GAP_MS


def sessionize(user_id, session_id, timestamp, code, ip, *, dedup=True,
               gap_ms: int = DEFAULT_GAP_MS) -> dict[str, np.ndarray]:
    """Dedup (when ``dedup``) and sessionize one set of event rows."""
    u = np.asarray(user_id, np.int64)
    s = np.asarray(session_id, np.int64)
    t = np.asarray(timestamp, np.int64)
    c = np.asarray(code, np.int64)
    i = np.asarray(ip, np.int64)
    order = np.lexsort((np.arange(len(u)), i, c, t, s, u))
    u, s, t, c, i = (x[order] for x in (u, s, t, c, i))
    if dedup and len(u):
        dup = np.r_[False, (u[1:] == u[:-1]) & (s[1:] == s[:-1])
                    & (t[1:] == t[:-1]) & (c[1:] == c[:-1])
                    & (i[1:] == i[:-1])]
        u, s, t, c, i = (x[~dup] for x in (u, s, t, c, i))
    n = len(u)
    new = np.ones(n, bool)
    if n:
        new[1:] = ((u[1:] != u[:-1]) | (s[1:] != s[:-1])
                   | (t[1:] - t[:-1] > gap_ms))
    first = np.flatnonzero(new)
    last = np.r_[first[1:], n] - 1
    return dict(user_id=u[first], session_id=s[first], start_ts=t[first],
                ip=np.maximum.reduceat(i, first) if n else i[:0],
                duration_s=(t[last] - t[first]) // 1000,
                length=(last - first + 1).astype(np.int64),
                offsets=np.r_[first, n].astype(np.int64),
                symbols=c.astype(np.int32))


def concat(parts: list[dict]) -> dict[str, np.ndarray]:
    """Sessions of several disjoint sets of rows, as one relation."""
    out = {k: np.concatenate([p[k] for p in parts])
           for k in parts[0] if k != "offsets"}
    lengths = np.concatenate([np.diff(p["offsets"]) for p in parts])
    out["offsets"] = np.r_[0, np.cumsum(lengths)].astype(np.int64)
    return out


def from_padded(symbols, length, user_id, session_id, ip, start_ts,
                duration_s) -> dict[str, np.ndarray]:
    """A padded (S, L) session block in the flat form."""
    symbols = np.asarray(symbols)
    length = np.asarray(length, np.int64)
    stored = np.minimum(length, symbols.shape[1])
    mask = np.arange(symbols.shape[1])[None, :] < stored[:, None]
    return dict(user_id=np.asarray(user_id, np.int64),
                session_id=np.asarray(session_id, np.int64),
                start_ts=np.asarray(start_ts, np.int64),
                ip=np.asarray(ip, np.int64),
                duration_s=np.asarray(duration_s, np.int64),
                length=length,
                offsets=np.r_[0, np.cumsum(stored)].astype(np.int64),
                symbols=symbols[mask].astype(np.int32))


_M1, _M2 = np.uint64(0xBF58476D1CE4E5B9), np.uint64(0x94D049BB133111EB)


def _mix(x) -> np.ndarray:
    """splitmix64's finaliser over uint64 (wrapping)."""
    z = np.asarray(x).astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * _M1
    z = (z ^ (z >> np.uint64(27))) * _M2
    return z ^ (z >> np.uint64(31))


def session_hashes(sess: dict[str, np.ndarray]) -> np.ndarray:
    """One 64-bit hash per session over every field and each symbol at its
    position: equal sessions hash equal, and two that differ anywhere
    collide with odds of about 2**-64."""
    with np.errstate(over="ignore"):
        h = np.zeros(len(sess["length"]), np.uint64)
        for k, name in enumerate(("user_id", "session_id", "start_ts", "ip",
                                  "duration_s", "length")):
            h = h * np.uint64(0x100000001B3) + _mix(
                sess[name].astype(np.int64) + np.int64(k << 56))
        off = sess["offsets"]
        if len(sess["symbols"]):
            seg = np.repeat(np.arange(len(h)), np.diff(off))
            pos = np.arange(len(seg), dtype=np.int64) - off[seg]
            sym = _mix((sess["symbols"].astype(np.int64) << 20) + pos)
            h = h ^ _mix(np.add.reduceat(sym, off[:-1]))
    return h


def multiset_difference(a: np.ndarray, b: np.ndarray) -> int:
    """Size of the symmetric difference of two multisets of hashes."""
    keys, inv = np.unique(np.concatenate([a, b]), return_inverse=True)
    ca = np.bincount(inv[:len(a)], minlength=len(keys))
    cb = np.bincount(inv[len(a):], minlength=len(keys))
    return int(np.abs(ca - cb).sum())


def bigram_counts(sess: dict[str, np.ndarray], alphabet: int) -> np.ndarray:
    """Dense (alphabet ** 2,) counts of adjacent symbol pairs within
    sessions."""
    sym = sess["symbols"].astype(np.int64)
    inner = np.ones(len(sym), bool)
    inner[sess["offsets"][1:] - 1] = False      # last symbol of a session
    a = sym[:-1][inner[:-1]] if len(sym) else sym
    b = sym[1:][inner[:-1]] if len(sym) else sym
    return np.bincount(a * alphabet + b, minlength=alphabet ** 2)


def funnel_reach(sess: dict[str, np.ndarray], stages, alphabet: int
                 ) -> np.ndarray:
    """reach[k]: sessions whose symbols hold stages 0..k in order."""
    member = np.zeros((len(stages) + 1, alphabet), bool)
    for k, codes in enumerate(stages):
        member[k, np.asarray(codes, np.int64)] = True
    off = sess["offsets"]
    length = np.diff(off)
    depth = np.zeros(len(length), np.int64)
    for pos in range(int(length.max()) if len(length) else 0):
        live = np.flatnonzero(length > pos)
        sym = sess["symbols"][off[live] + pos]
        depth[live] += member[depth[live], sym]
    return np.array([(depth > k).sum() for k in range(len(stages))],
                    np.int64)
