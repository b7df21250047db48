"""Session reconstruction (paper §4.2) as a TPU-native sort + segment pass.

The paper reconstructs sessions with a Hadoop group-by on
``(user_id, session_id)`` followed by a 30-minute-inactivity split. Here the
same dataflow is one stable lexicographic sort over (user, session,
timestamp) (``lexsort_perm``) followed by segment-boundary detection and
``segment_*`` reductions — no shuffle, no reducers, one XLA program. The
distributed variant (dist/collectives.py) prepends the paper's shuffle as an ``all_to_all`` keyed repartition over the mesh ``data`` axis.

Identifiers and timestamps are int64; JAX defaults to 32-bit, so the jitted
pipeline is traced under ``dist.compat.enable_x64`` — scoped here only,
never leaking into model code.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.compat import enable_x64

# 30 minutes, following standard practice (paper §4.2).
DEFAULT_GAP_MS = 30 * 60 * 1000
PAD_CODE = -1  # padding symbol in materialized sequence tensors

_I64_MAX = np.iinfo(np.int64).max


def _sort_words(x):
    """``x`` as order-preserving uint32 words, most significant first:
    unsigned comparison of the words, in order, is signed comparison of
    ``x``."""
    sign = jnp.uint32(0x80000000)
    if x.dtype == jnp.int64:
        return [(x >> 32).astype(jnp.int32).astype(jnp.uint32) ^ sign,
                x.astype(jnp.uint32)]
    return [x.astype(jnp.int32).astype(jnp.uint32) ^ sign]


@jax.named_scope("sort")
def lexsort_perm(keys):
    """The permutation that orders rows by ``keys`` (most significant
    first), ties kept in input order — the order a stable
    ``lax.sort(..., num_keys=len(keys))`` gives.

    Built as one stable single-key sort per 32-bit key word, least
    significant first, inside a ``fori_loop``. The TPU compiler takes
    minutes over one sort whose comparator spans several int64 keys (its
    compile time grows with the key width and the row count), while this
    loop compiles one narrow sort once, whatever the number of keys.
    """
    words = jnp.stack([w for k in keys for w in _sort_words(k)])
    n_words, n = words.shape

    def pass_(j, perm):
        key = words[n_words - 1 - j][perm]
        return jax.lax.sort((key, perm), num_keys=1, is_stable=True)[1]

    return jax.lax.fori_loop(0, n_words, pass_,
                             jnp.arange(n, dtype=jnp.int32))


@dataclass
class Sessionized:
    """Result of one sessionize pass. All arrays are device/ndarray.

    ``num_sessions`` is the *true* session count; arrays are materialized at
    the static caps (max_sessions, max_len) — rows past num_sessions and
    positions past length are padding. ``truncated`` flags capacity overflow
    so callers can re-run with larger caps (production behaviour: the log
    mover sizes caps from the histogram job's stats).
    """
    symbols: jax.Array      # (max_sessions, max_len) int32, PAD_CODE padded
    length: jax.Array       # (max_sessions,) int32 — true event count (may exceed max_len)
    user_id: jax.Array      # (max_sessions,) int64
    session_id: jax.Array   # (max_sessions,) int64
    ip: jax.Array           # (max_sessions,) int64 (uint32 range)
    start_ts: jax.Array     # (max_sessions,) int64 ms
    duration_s: jax.Array   # (max_sessions,) int32 seconds (paper stores seconds)
    num_sessions: jax.Array # () int32
    num_events: jax.Array   # () int32 — valid events processed
    truncated: jax.Array    # () bool — any session cap overflow

    def trimmed(self) -> "Sessionized":
        n = int(self.num_sessions)
        return Sessionized(
            symbols=np.asarray(self.symbols)[:n],
            length=np.asarray(self.length)[:n],
            user_id=np.asarray(self.user_id)[:n],
            session_id=np.asarray(self.session_id)[:n],
            ip=np.asarray(self.ip)[:n],
            start_ts=np.asarray(self.start_ts)[:n],
            duration_s=np.asarray(self.duration_s)[:n],
            num_sessions=np.int32(n),
            num_events=np.asarray(self.num_events),
            truncated=np.asarray(self.truncated),
        )


@jax.jit
@jax.named_scope("dedup")
def mark_duplicate_events(user_id, session_id, timestamp, code, ip, valid):
    """Within-user exact-duplicate removal — returns the validity mask with
    retry duplicates cleared.

    Scribe delivery is at-least-once: client retries and daemon resends
    materialize as byte-identical event rows (§3.1; the log mover absorbs
    file-level dupes, row-level ones survive into the warehouse). Two rows
    are duplicates when all of (user_id, session_id, timestamp, code, ip)
    match; the first occurrence (original order) survives. Implemented as
    one stable 5-key sort (``lexsort_perm``) + neighbour compare +
    scatter-back through the permutation — the same sort-based group-by the
    sessionizer uses, so it composes with it inside a single shard_map
    stage.
    """
    n = user_id.shape[0]
    i64max = jnp.asarray(_I64_MAX, jnp.int64)
    u = jnp.where(valid, user_id, i64max)
    s = jnp.where(valid, session_id, i64max)
    t = jnp.where(valid, timestamp, i64max)
    c = jnp.where(valid, code.astype(jnp.int64), i64max)
    p = jnp.where(valid, ip.astype(jnp.int64), i64max)
    idx_s = lexsort_perm((u, s, t, c, p))
    u, s, t, c, p = (x[idx_s] for x in (u, s, t, c, p))
    valid_s = valid[idx_s]
    same = ((u == jnp.roll(u, 1)) & (s == jnp.roll(s, 1))
            & (t == jnp.roll(t, 1)) & (c == jnp.roll(c, 1))
            & (p == jnp.roll(p, 1)))
    # Invalid rows sort last (all-max keys), so a valid row's predecessor is
    # always valid; first row can never be a duplicate.
    dup = same & valid_s & (jnp.arange(n) != 0)
    keep_sorted = valid_s & ~dup
    return jnp.zeros(n, bool).at[idx_s].set(keep_sorted)


@functools.partial(jax.jit, static_argnames=("gap_ms", "max_sessions",
                                             "max_len", "with_event_grids"))
@jax.named_scope("sessionize")
def _sessionize(user_id, session_id, timestamp, code, ip, valid,
                *, gap_ms: int, max_sessions: int, max_len: int,
                with_event_grids: bool = False):
    n = user_id.shape[0]
    i64max = jnp.asarray(_I64_MAX, jnp.int64)

    # Invalid rows sort to the end (all-max keys).
    u = jnp.where(valid, user_id, i64max)
    s = jnp.where(valid, session_id, i64max)
    t = jnp.where(valid, timestamp, i64max)

    perm = lexsort_perm((u, s, t))
    u, s, t = u[perm], s[perm], t[perm]
    code_s = code.astype(jnp.int32)[perm]
    ip_s = ip.astype(jnp.int64)[perm]
    valid_s = valid[perm]

    idx = jnp.arange(n, dtype=jnp.int32)
    prev_u = jnp.roll(u, 1)
    prev_s = jnp.roll(s, 1)
    prev_t = jnp.roll(t, 1)
    first = idx == 0
    new_seg = valid_s & (first
                         | (u != prev_u)
                         | (s != prev_s)
                         | ((t - prev_t) > gap_ms))

    # Dense segment id per event; invalid rows -> drop bucket (= max_sessions
    # after clamping, also used for capacity overflow).
    seg = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    seg = jnp.where(valid_s, seg, max_sessions)
    overflow = seg > max_sessions
    seg = jnp.minimum(seg, max_sessions)

    num_sessions_true = jnp.sum(new_seg.astype(jnp.int32))
    num_sessions = jnp.minimum(num_sessions_true, max_sessions)
    num_events = jnp.sum(valid_s.astype(jnp.int32))

    nseg = max_sessions + 1  # + drop bucket
    with jax.named_scope("segments"):
        ones = jnp.ones_like(seg)
        length = jax.ops.segment_sum(ones, seg, num_segments=nseg)
        start_idx = jax.ops.segment_min(idx, seg, num_segments=nseg)
        start_ts = jax.ops.segment_min(t, seg, num_segments=nseg)
        end_ts = jax.ops.segment_max(
            jnp.where(valid_s, t, jnp.asarray(0, jnp.int64)), seg,
            num_segments=nseg)
        seg_user = jax.ops.segment_max(
            jnp.where(valid_s, u, jnp.asarray(-1, jnp.int64)), seg,
            num_segments=nseg)
        seg_sess = jax.ops.segment_max(
            jnp.where(valid_s, s, jnp.asarray(-1, jnp.int64)), seg,
            num_segments=nseg)
        seg_ip = jax.ops.segment_max(
            jnp.where(valid_s, ip_s, jnp.asarray(-1, jnp.int64)), seg,
            num_segments=nseg)

    pos = idx - start_idx[seg]
    # Scatter codes into the padded (sessions, time) tensor; OOB rows/cols
    # (drop bucket, beyond max_len) are dropped by mode='drop'.
    with jax.named_scope("grid"):
        symbols = jnp.full((max_sessions, max_len), PAD_CODE, jnp.int32)
        symbols = symbols.at[seg, pos].set(code_s, mode="drop")

    duration_s = ((end_ts[:max_sessions] - start_ts[:max_sessions])
                  // 1000).astype(jnp.int32)
    empty = length[:max_sessions] == 0
    extras = {}
    if with_event_grids:
        # Per-event grids aligned with ``symbols`` (streaming ring state:
        # data/streampipe.py re-sorts open sessions with new events each
        # tick, so it must keep every stored event's timestamp and ip).
        with jax.named_scope("grid"):
            ts_grid = jnp.zeros((max_sessions, max_len), jnp.int64)
            ip_grid = jnp.zeros((max_sessions, max_len), jnp.int64)
            extras = dict(
                event_ts=ts_grid.at[seg, pos].set(t, mode="drop"),
                event_ip=ip_grid.at[seg, pos].set(ip_s, mode="drop"))
        extras["end_ts"] = jnp.where(empty, 0,
                                     jnp.asarray(end_ts[:max_sessions]))
    return dict(
        **extras,
        symbols=symbols,
        length=length[:max_sessions],
        user_id=jnp.where(empty, -1, seg_user[:max_sessions]),
        session_id=jnp.where(empty, -1, seg_sess[:max_sessions]),
        ip=jnp.where(empty, -1, seg_ip[:max_sessions]),
        start_ts=jnp.where(empty, 0, start_ts[:max_sessions]),
        duration_s=jnp.where(empty, 0, duration_s),
        num_sessions=num_sessions,
        num_events=num_events,
        truncated=jnp.any(overflow) | (num_sessions_true > max_sessions)
                  | jnp.any(length[:max_sessions] > max_len),
    )


def sessionize(user_id, session_id, timestamp, code, ip=None, valid=None, *,
               gap_ms: int = DEFAULT_GAP_MS,
               max_sessions: int | None = None,
               max_len: int | None = None,
               dedup: bool = False) -> Sessionized:
    """Reconstruct sessions and materialize padded symbol sequences.

    Inputs are parallel event columns in *arbitrary order* (the warehouse
    guarantees only partial time order, §2). Static caps default to
    worst-case (every event its own session / one session holding all).
    ``dedup=True`` drops exact retry duplicates first (the distributed
    pipeline's stage-2 semantics; see ``mark_duplicate_events``).
    """
    n = len(user_id)
    if max_sessions is None:
        max_sessions = n
    if max_len is None:
        max_len = n
    if ip is None:
        ip = np.zeros(n, np.int64)
    if valid is None:
        valid = np.ones(n, bool)
    with enable_x64():
        u = jnp.asarray(user_id, jnp.int64)
        s = jnp.asarray(session_id, jnp.int64)
        t = jnp.asarray(timestamp, jnp.int64)
        c = jnp.asarray(code, jnp.int32)
        i = jnp.asarray(ip, jnp.int64)
        v = jnp.asarray(valid, bool)
        if dedup:
            v = mark_duplicate_events(u, s, t, c, i, v)
        out = _sessionize(u, s, t, c, i, v,
                          gap_ms=int(gap_ms), max_sessions=int(max_sessions),
                          max_len=int(max_len))
    return Sessionized(**out)


def closed_prefix_mask(user_id, session_id, timestamp, *, gap_ms: int,
                       watermark: int) -> np.ndarray:
    """Per-event bool: the event's batch session is closed at
    ``watermark`` (its segment's last event + gap is strictly below it).

    Pure numpy oracle-side helper: segments are the batch sessionizer's
    ((user, session) group split on > ``gap_ms``). Within a group, closed
    segments are a prefix — so batch-sessionizing just the masked events
    reproduces exactly the closed sessions. Shared by the streaming tier's
    oracle harness (``data.streampipe``) and the segment store's compaction
    pass (``data.store``), which partitions event segments into
    closed-session rows vs the open residual with it.
    """
    u = np.asarray(user_id, np.int64)
    s = np.asarray(session_id, np.int64)
    t = np.asarray(timestamp, np.int64)
    n = len(u)
    if n == 0:
        return np.zeros(0, bool)
    order = np.lexsort((t, s, u))
    us, ss, ts = u[order], s[order], t[order]
    new_seg = np.ones(n, bool)
    new_seg[1:] = ((us[1:] != us[:-1]) | (ss[1:] != ss[:-1])
                   | ((ts[1:] - ts[:-1]) > gap_ms))
    seg = np.cumsum(new_seg) - 1
    last = np.full(int(seg[-1]) + 1, np.iinfo(np.int64).min, np.int64)
    np.maximum.at(last, seg, ts)
    out = np.zeros(n, bool)
    out[order] = (last[seg] + gap_ms) < watermark
    return out
