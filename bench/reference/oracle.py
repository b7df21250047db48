"""Pure-Python definition of the log tier's semantics: the test oracle of
``bench/reference/fast.py`` (a copy of the program's ``core/oracle.py``,
kept here so that a change to the program cannot change it).

This is not the reference a run's check uses: ``fast.py`` is, because
this one takes minutes over a window's events. ``bench/tests`` hold
``fast.py`` equal to it. These implement the paper's semantics the "Pig
way": dict-based group-by, explicit sorting.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

# 30 minutes (paper section 4.2).
DEFAULT_GAP_MS = 30 * 60 * 1000


def sessionize_oracle(user_id, session_id, timestamp, code, ip=None,
                      valid=None, gap_ms: int = DEFAULT_GAP_MS):
    """Group-by (user, session) -> time sort -> 30-min split.

    Returns a list of session dicts sorted by (user_id, session_id,
    start_ts) — the same order the vectorized pipeline emits.
    """
    n = len(user_id)
    ip = np.zeros(n, np.int64) if ip is None else np.asarray(ip)
    valid = np.ones(n, bool) if valid is None else np.asarray(valid)
    groups: dict[tuple[int, int], list[tuple[int, int, int]]] = defaultdict(list)
    for i in range(n):
        if not valid[i]:
            continue
        groups[(int(user_id[i]), int(session_id[i]))].append(
            (int(timestamp[i]), int(code[i]), int(ip[i])))
    sessions = []
    for (u, s), rows in sorted(groups.items()):
        rows.sort()
        cur: list[tuple[int, int, int]] = []
        for row in rows:
            if cur and row[0] - cur[-1][0] > gap_ms:
                sessions.append(_emit(u, s, cur))
                cur = []
            cur.append(row)
        if cur:
            sessions.append(_emit(u, s, cur))
    return sessions


def _emit(u, s, rows):
    ts = [r[0] for r in rows]
    return dict(
        user_id=u,
        session_id=s,
        symbols=[r[1] for r in rows],
        ip=max(r[2] for r in rows),
        start_ts=ts[0],
        duration_s=(ts[-1] - ts[0]) // 1000,
        length=len(rows),
    )


def dedup_events_oracle(user_id, session_id, timestamp, code, ip=None,
                        valid=None) -> np.ndarray:
    """Reference for ``core.sessionize.mark_duplicate_events``: the validity
    mask with exact retry duplicates — identical (user, session, timestamp,
    code, ip) rows after the first — cleared, the "Pig way" (one seen-set)."""
    n = len(user_id)
    ip = np.zeros(n, np.int64) if ip is None else np.asarray(ip)
    valid = np.ones(n, bool) if valid is None else np.asarray(valid)
    seen: set[tuple] = set()
    keep = np.zeros(n, bool)
    for i in range(n):
        if not valid[i]:
            continue
        key = (int(user_id[i]), int(session_id[i]), int(timestamp[i]),
               int(code[i]), int(ip[i]))
        if key not in seen:
            seen.add(key)
            keep[i] = True
    return keep


def histogram_oracle(name_ids, num_names, valid=None):
    valid = np.ones(len(name_ids), bool) if valid is None else np.asarray(valid)
    out = np.zeros(num_names, np.int64)
    for i, nid in enumerate(name_ids):
        if valid[i]:
            out[int(nid)] += 1
    return out


def count_events_oracle(sessions, target_codes) -> tuple[int, int]:
    """(total occurrences, sessions with >=1 occurrence) — the SUM and COUNT
    variants of the paper's CountClientEvents UDF (§5.2)."""
    targets = set(int(c) for c in np.asarray(target_codes).ravel())
    total = 0
    containing = 0
    for sess in sessions:
        c = sum(1 for sym in sess["symbols"] if sym in targets)
        total += c
        containing += 1 if c > 0 else 0
    return total, containing


def funnel_oracle(sessions, stages) -> list[int]:
    """Per-stage reach counts (paper §5.3).

    ``stages`` is a list of stage specs; each spec is a set of codes that
    satisfy the stage. A session reaches stage k if stages 0..k match in
    order (subsequence semantics, the paper's regex over the session
    string). Returns reach[k] = #sessions whose deepest stage >= k.
    """
    stage_sets = [set(int(c) for c in np.asarray(s).ravel()) for s in stages]
    reach = [0] * len(stage_sets)
    for sess in sessions:
        k = 0
        for sym in sess["symbols"]:
            if k < len(stage_sets) and sym in stage_sets[k]:
                k += 1
                if k == len(stage_sets):
                    break
        for j in range(k):
            reach[j] += 1
    return reach


def ngram_counts_oracle(sessions, n: int):
    """n-gram -> count over session symbol streams (no cross-session grams)."""
    out: dict[tuple, int] = defaultdict(int)
    for sess in sessions:
        syms = sess["symbols"]
        for i in range(len(syms) - n + 1):
            out[tuple(syms[i:i + n])] += 1
    return dict(out)
