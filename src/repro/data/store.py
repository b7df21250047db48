"""The unified mega-table log store (paper §4.2–4.3).

The paper's endpoint is a single well-formatted log that every analytics
job reads from: raw client events land append-only, session sequences are
materialized once, and common queries never re-scan raw events. This
module is that store as an append-only collection of immutable columnar
**segments**:

* **Event segments** — one per micro-batch write (the log mover's unit).
  Rows are time-sorted; timestamps are delta + varint coded, user/session
  ids zigzag-varint coded, event ids are the dictionary codes
  (``core.dictionary`` frequency order) as unsigned varints.
* **Session segments** — the materialized relation of §4.2. Each session's
  symbol sequence is stored as the paper's UTF-8 string (small code point =
  frequent event), every session of a segment encoded in one array pass
  (``core.varint.encode_sessions``); the metadata columns
  (user, session, ip, start, duration, length) ride along varint-coded.
* **Per-segment metadata** — row/event counts, ``[min_ts, max_ts]`` (for
  session segments a conservative bound covering every event in every
  session), a ``user_shards``-bit presence bitmap over
  ``splitmix64(user) % user_shards`` buckets (the same hash
  ``dist.collectives.shard_of_user`` shards by), and a sparse
  code histogram. Metadata is what ``scan`` prunes on and what the
  catalog (``core.catalog.CatalogBuilder``) folds incrementally.

**Compaction** (`Store.compact(watermark)`) folds closed event segments
into session segments: decode every event segment that can contain a
closed session (``min_ts < watermark``), partition events with
``core.sessionize.closed_prefix_mask`` (re-sessionizing only at segment
boundaries), run the *same* fused sessionizer the batch pipeline runs over
the closed part, and re-encode the open remainder as one residual event
segment. Repeated compactions at monotone watermarks are oracle-equal to
one ``data.distpipe.single_host_pipeline`` pass over the full corpus — the
identical closed-prefix contract the streaming tier proves tick by tick.
Appends are expected to respect the compaction watermark (the log mover /
streaming tier contract); events that arrive below it are counted in
``late_appended`` and still materialize, but as their own late session.

**Scan** (`Store.scan(time_range, users, events)`) is the pruning query
path: segments whose metadata cannot match the filters are skipped before
any decoding (counted per prune reason in ``ScanStats``), surviving
segments decode and apply the exact row filters. Consumers —
``data.pipeline.SessionBatchPipeline.from_store``, the
``analytics.{counting,ngram,funnel}`` store wrappers, and the streaming
tier's closed-session sink — all read through here.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, replace

import numpy as np

from ..core import varint
from ..core.sequences import SessionSequences
from ..core.spans import span
from ..core.sessionize import (DEFAULT_GAP_MS, PAD_CODE, closed_prefix_mask,
                               sessionize)

# Compaction watermark meaning "close everything" (end of day / drain).
# Matches streampipe.WATERMARK_MAX; not full int64 so end+gap can't overflow.
COMPACT_ALL = 1 << 62

EVENT_COLS = ("timestamp", "user_id", "session_id", "code", "ip")
SESSION_COLS = ("start_ts", "user_id", "session_id", "ip", "duration_s",
                "length", "payload_len")


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — numpy twin of ``dist.collectives.mix64`` so
    segment metadata and the mesh repartition agree on user buckets."""
    x = np.asarray(x).astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def user_shard_mask(user_id, n_shards: int = 64) -> int:
    """Presence bitmap over ``splitmix64(user) % n_shards`` buckets."""
    u = np.asarray(user_id, np.int64)
    if u.size == 0:
        return 0
    shards = np.unique(_mix64(u) % np.uint64(n_shards))
    mask = 0
    for s in shards:
        mask |= 1 << int(s)
    return mask


def _code_counts(codes: np.ndarray) -> dict[int, int]:
    vals, cnts = np.unique(np.asarray(codes, np.int64), return_counts=True)
    return {int(v): int(c) for v, c in zip(vals, cnts)}


@dataclass(frozen=True)
class Segment:
    """One immutable columnar segment + the metadata ``scan`` prunes on.

    An **evicted** segment (``on_disk=True``, via ``Store.evict_to_disk``)
    keeps every metadata field resident — pruning never touches disk — but
    its ``blob`` is empty; ``disk_bytes`` remembers the spilled blob size
    so byte accounting is unchanged. Decoding an evicted segment without
    reloading it first is a loud error, not a silent empty result.
    """
    seg_id: int
    kind: str                 # "events" | "sessions"
    n: int                    # rows (events, or sessions)
    n_events: int             # true events covered (sessions: sum of length)
    min_ts: int               # events: min ts; sessions: min start_ts
    max_ts: int               # conservative upper bound on any event time
    user_mask: int            # user_shards-bit presence bitmap
    code_counts: dict[int, int] = field(repr=False)  # stored symbols only
    col_bytes: dict[str, int] = field(repr=False)
    blob: bytes = field(repr=False)
    on_disk: bool = False     # blob aged out to the spill dir
    disk_bytes: int = 0       # spilled blob size (0 while resident)

    @property
    def nbytes(self) -> int:
        return self.disk_bytes if self.on_disk else len(self.blob)


# ---------------------------------------------------------------------------
# segment codecs
# ---------------------------------------------------------------------------

def _encode_event_blob(t, u, s, c, i) -> tuple[bytes, dict[str, int]]:
    """Time-sorted event columns -> one blob; ts delta-coded."""
    blocks = dict(
        timestamp=varint.encode_ivarint(np.diff(t, prepend=np.int64(0))),
        user_id=varint.encode_ivarint(u),
        session_id=varint.encode_ivarint(s),
        code=varint.encode_uvarint(c),
        ip=varint.encode_ivarint(i),
    )
    return b"".join(blocks[k] for k in EVENT_COLS), \
        {k: len(v) for k, v in blocks.items()}


def encode_event_segment(seg_id: int, user_id, session_id, timestamp, code,
                         ip=None, *, user_shards: int = 64) -> Segment:
    """One micro-batch of raw events -> an immutable time-sorted segment."""
    t = np.asarray(timestamp, np.int64)
    n = len(t)
    order = np.argsort(t, kind="stable")
    t = t[order]
    u = np.asarray(user_id, np.int64)[order]
    s = np.asarray(session_id, np.int64)[order]
    c = np.asarray(code, np.int32)[order]
    i = (np.zeros(n, np.int64) if ip is None
         else np.asarray(ip, np.int64)[order])
    blob, col_bytes = _encode_event_blob(t, u, s, c, i)
    return Segment(
        seg_id=seg_id, kind="events", n=n, n_events=n,
        min_ts=int(t[0]) if n else 0, max_ts=int(t[-1]) if n else 0,
        user_mask=user_shard_mask(u, user_shards),
        code_counts=_code_counts(c), col_bytes=col_bytes, blob=blob)


def decode_event_segment(seg: Segment) -> dict[str, np.ndarray]:
    """Segment -> event columns (time-sorted, as encoded)."""
    assert seg.kind == "events"
    if seg.on_disk:
        raise ValueError(
            f"segment {seg.seg_id} is evicted to disk — reload its blob "
            "before decoding (Store.scan does this transparently)")
    n, off = seg.n, 0
    dt, off = varint.decode_ivarint(seg.blob, n, off)
    u, off = varint.decode_ivarint(seg.blob, n, off)
    s, off = varint.decode_ivarint(seg.blob, n, off)
    c, off = varint.decode_uvarint(seg.blob, n, off)
    i, off = varint.decode_ivarint(seg.blob, n, off)
    return dict(timestamp=np.cumsum(dt, dtype=np.int64),
                user_id=u.astype(np.int64), session_id=s.astype(np.int64),
                code=c.astype(np.int32), ip=i.astype(np.int64))


def encode_session_segment(seg_id: int, seqs: SessionSequences, *,
                           user_shards: int = 64) -> Segment:
    """Materialized sessions -> an immutable segment (row order preserved).

    Payloads are the paper's UTF-8 session strings, encoded in one array
    pass (``core.varint.encode_sessions``); ``max_ts`` is the
    conservative bound ``max(start_ts + (duration_s + 1) * 1000)`` — it
    covers every event of every session (duration is floor-seconds), so
    time pruning can never drop a matching segment.
    """
    codes, payload, payload_len = varint.encode_sessions(seqs)
    blob, col_bytes = _session_blob(seqs, payload, payload_len)
    return _session_segment(seg_id, seqs, codes, blob, col_bytes,
                            user_shards)


def _session_blob(seqs: SessionSequences, payload: np.ndarray,
                  payload_len: np.ndarray) -> tuple[bytes, dict[str, int]]:
    """The varint metadata columns, then the payloads, as one blob."""
    blocks = dict(
        start_ts=varint.encode_ivarint(
            np.diff(np.asarray(seqs.start_ts, np.int64),
                    prepend=np.int64(0))),
        user_id=varint.encode_ivarint(seqs.user_id),
        session_id=varint.encode_ivarint(seqs.session_id),
        ip=varint.encode_ivarint(seqs.ip),
        duration_s=varint.encode_uvarint(seqs.duration_s),
        length=varint.encode_uvarint(seqs.length),
        payload_len=varint.encode_uvarint(payload_len),
    )
    blob = b"".join([*(blocks[k] for k in SESSION_COLS), payload])
    col_bytes = {k: len(v) for k, v in blocks.items()}
    col_bytes["payload"] = len(payload)
    return blob, col_bytes


def _session_segment(seg_id: int, seqs: SessionSequences, codes: np.ndarray,
                     blob: bytes, col_bytes: dict[str, int],
                     user_shards: int) -> Segment:
    """The segment around an encoded blob, with the metadata ``scan``
    prunes on; ``codes`` are the stored symbols ``encode_sessions``
    returned."""
    n = len(seqs)
    start = np.asarray(seqs.start_ts, np.int64)
    hi = start + (np.asarray(seqs.duration_s, np.int64) + 1) * 1000
    return Segment(
        seg_id=seg_id, kind="sessions", n=n,
        n_events=int(np.asarray(seqs.length, np.int64).sum()),
        min_ts=int(start.min()) if n else 0,
        max_ts=int(hi.max()) if n else 0,
        user_mask=user_shard_mask(seqs.user_id, user_shards),
        code_counts=_code_counts(codes),
        col_bytes=col_bytes, blob=blob)


def decode_session_segment(seg: Segment, min_width: int = 0
                           ) -> SessionSequences:
    """Segment -> SessionSequences (row order as encoded; symbol matrix at
    least ``min_width`` wide so callers can concat across segments)."""
    assert seg.kind == "sessions"
    if seg.on_disk:
        raise ValueError(
            f"segment {seg.seg_id} is evicted to disk — reload its blob "
            "before decoding (Store.scan does this transparently)")
    n, off = seg.n, 0
    dstart, off = varint.decode_ivarint(seg.blob, n, off)
    u, off = varint.decode_ivarint(seg.blob, n, off)
    s, off = varint.decode_ivarint(seg.blob, n, off)
    i, off = varint.decode_ivarint(seg.blob, n, off)
    dur, off = varint.decode_uvarint(seg.blob, n, off)
    length, off = varint.decode_uvarint(seg.blob, n, off)
    plen, off = varint.decode_uvarint(seg.blob, n, off)
    plen = plen.astype(np.int64)
    starts = off + np.concatenate([[0], np.cumsum(plen)[:-1]]).astype(np.int64)
    symbol_rows = [varint.decode_session(seg.blob[a: a + l])
                   for a, l in zip(starts, plen)]
    width = max([len(r) for r in symbol_rows], default=0)
    width = max(width, min_width)
    symbols = np.full((n, width), PAD_CODE, np.int32)
    for j, r in enumerate(symbol_rows):
        symbols[j, : len(r)] = r
    return SessionSequences(
        symbols=symbols, length=length.astype(np.int32),
        user_id=u.astype(np.int64), session_id=s.astype(np.int64),
        ip=i.astype(np.int64),
        start_ts=np.cumsum(dstart, dtype=np.int64),
        duration_s=dur.astype(np.int32))


def concat_sequences(parts: list[SessionSequences],
                     min_width: int = 0) -> SessionSequences:
    """Concatenate session relations, padding symbols to a common width."""
    width = max([p.max_len for p in parts] + [min_width])
    if not parts:
        return SessionSequences(
            symbols=np.zeros((0, width), np.int32),
            length=np.zeros(0, np.int32), user_id=np.zeros(0, np.int64),
            session_id=np.zeros(0, np.int64), ip=np.zeros(0, np.int64),
            start_ts=np.zeros(0, np.int64),
            duration_s=np.zeros(0, np.int32))

    def wide(p: SessionSequences) -> np.ndarray:
        if p.max_len == width:
            return p.symbols
        out = np.full((len(p), width), PAD_CODE, np.int32)
        out[:, : p.max_len] = p.symbols
        return out

    return SessionSequences(
        symbols=np.concatenate([wide(p) for p in parts]),
        length=np.concatenate([p.length for p in parts]),
        user_id=np.concatenate([p.user_id for p in parts]),
        session_id=np.concatenate([p.session_id for p in parts]),
        ip=np.concatenate([p.ip for p in parts]),
        start_ts=np.concatenate([p.start_ts for p in parts]),
        duration_s=np.concatenate([p.duration_s for p in parts]))


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StoreConfig:
    """Sessionization semantics + metadata shape of one store.

    ``gap_ms``/``dedup``/``max_len`` must match the pipeline configs for
    the compaction-vs-``single_host_pipeline`` oracle equality to hold;
    ``user_shards`` is the width of the per-segment user presence bitmap.
    """
    gap_ms: int = DEFAULT_GAP_MS
    dedup: bool = True
    max_len: int = 2048
    user_shards: int = 64


@dataclass
class CompactionStats:
    watermark: int
    segments_in: int          # event segments folded
    events_in: int
    sessions_out: int         # closed sessions materialized
    events_closed: int
    residual_events: int      # still-open events re-encoded
    bytes_in: int
    bytes_out: int


@dataclass
class ScanStats:
    segments_total: int
    segments_decoded: int
    pruned_time: int
    pruned_users: int
    pruned_events: int
    rows_decoded: int
    rows_matched: int
    unmaterialized_events: int  # matching events still in event segments
    # RAM-headroom accounting (Store.evict_to_disk): evicted segments this
    # scan *considered* (metadata pruning is free either way) vs. evicted
    # segments it actually had to re-read from disk to decode — the gap is
    # I/O the metadata pruning saved
    segments_on_disk: int = 0
    segments_reloaded: int = 0

    @property
    def segments_pruned(self) -> int:
        return self.pruned_time + self.pruned_users + self.pruned_events


@dataclass
class ScanResult:
    sequences: SessionSequences
    events: dict[str, np.ndarray]
    stats: ScanStats


class Store:
    """Append-only segment store; see module docstring.

    Mutable state is only the segment list and counters — segments
    themselves are immutable, so readers hold no locks and a crashed
    compaction simply leaves the old segments in place (the log-mover
    idempotence story).
    """

    def __init__(self, cfg: StoreConfig = StoreConfig()):
        self.cfg = cfg
        self.segments: list[Segment] = []
        self._next_id = 0
        self.events_appended = 0
        self.late_appended = 0
        self.compaction_watermark = -(1 << 62)
        self.truncated = False
        # RAM-headroom cap (evict_to_disk): None = everything resident
        self.max_resident_segments: int | None = None
        self._spill_dir: str | None = None
        self.segments_evicted = 0     # cumulative blobs aged to disk
        self.segments_reloaded = 0    # cumulative transient re-reads

    def __len__(self) -> int:
        return len(self.segments)

    def _take_id(self) -> int:
        sid, self._next_id = self._next_id, self._next_id + 1
        return sid

    # -- writes ------------------------------------------------------------

    def append_events(self, user_id, session_id, timestamp, code,
                      ip=None) -> Segment:
        """One micro-batch write -> one immutable event segment."""
        t = np.asarray(timestamp, np.int64)
        seg = encode_event_segment(self._take_id(), user_id, session_id,
                                   t, code, ip,
                                   user_shards=self.cfg.user_shards)
        self.segments.append(seg)
        self.events_appended += seg.n
        self.late_appended += int((t < self.compaction_watermark).sum())
        return seg

    def append_sessions(self, seqs: SessionSequences) -> Segment:
        """Already-materialized sessions (the streaming tier's closed
        blocks) -> one immutable session segment."""
        events = int(np.asarray(seqs.length, np.int64).sum())
        with span("store.append_sessions", sessions=len(seqs),
                  events=events):
            with span("store.encode_payloads") as counts:
                codes, payload, payload_len = varint.encode_sessions(seqs)
                counts["bytes"] = len(payload)
            with span("store.encode_columns"):
                blob, col_bytes = _session_blob(seqs, payload, payload_len)
            with span("store.index"):
                seg = _session_segment(self._take_id(), seqs, codes, blob,
                                       col_bytes, self.cfg.user_shards)
                self.segments.append(seg)
                self.events_appended += seg.n_events
                self._enforce_residency()
            return seg

    # -- compaction --------------------------------------------------------

    def compact(self, watermark: int | None = None) -> CompactionStats:
        """Fold closed event segments into session segments at
        ``watermark`` (default: close everything).

        Only event segments with ``min_ts < watermark`` decode — a segment
        wholly at or past the watermark can neither contain nor extend a
        closed session (any extender event has ``ts <= end + gap <
        watermark``), so it is skipped untouched.
        """
        wm = COMPACT_ALL if watermark is None else int(watermark)
        wm = max(wm, self.compaction_watermark)
        cand = [g for g in self.segments
                if g.kind == "events" and g.min_ts < wm]
        self.compaction_watermark = wm
        if not cand:
            return CompactionStats(wm, 0, 0, 0, 0, 0, 0, 0)
        cols = [decode_event_segment(g) for g in cand]
        u = np.concatenate([c["user_id"] for c in cols])
        s = np.concatenate([c["session_id"] for c in cols])
        t = np.concatenate([c["timestamp"] for c in cols])
        c_ = np.concatenate([c["code"] for c in cols])
        i = np.concatenate([c["ip"] for c in cols])
        closed = closed_prefix_mask(u, s, t, gap_ms=self.cfg.gap_ms,
                                    watermark=wm)
        # (retry duplicates share all five keys, so a duplicate pair can
        # never straddle the closed/open split — dedup stays exact across
        # compactions)
        n_closed = int(closed.sum())
        sessions_out = 0
        cand_ids = {g.seg_id for g in cand}
        new_segments = [g for g in self.segments
                        if g.seg_id not in cand_ids]
        bytes_out = 0
        if n_closed:
            cap = 1 << max(n_closed - 1, 0).bit_length()
            pad = cap - n_closed

            def col(x, dtype):
                return np.concatenate([np.asarray(x, dtype)[closed],
                                       np.zeros(pad, dtype)])

            sess = sessionize(col(u, np.int64), col(s, np.int64),
                              col(t, np.int64), col(c_, np.int32),
                              col(i, np.int64), np.arange(cap) < n_closed,
                              gap_ms=self.cfg.gap_ms, max_sessions=cap,
                              max_len=self.cfg.max_len,
                              dedup=self.cfg.dedup)
            self.truncated |= bool(np.asarray(sess.truncated))
            seqs = SessionSequences.from_sessionized(sess)
            seg = encode_session_segment(self._take_id(), seqs,
                                         user_shards=self.cfg.user_shards)
            new_segments.append(seg)
            bytes_out += seg.nbytes
            sessions_out = len(seqs)
        n_open = len(u) - n_closed
        if n_open:
            m = ~closed
            seg = encode_event_segment(
                self._take_id(), u[m], s[m], t[m], c_[m], i[m],
                user_shards=self.cfg.user_shards)
            new_segments.append(seg)
            bytes_out += seg.nbytes
        self.segments = new_segments
        self._enforce_residency()
        return CompactionStats(
            watermark=wm, segments_in=len(cand), events_in=len(u),
            sessions_out=sessions_out, events_closed=n_closed,
            residual_events=n_open,
            bytes_in=sum(g.nbytes for g in cand), bytes_out=bytes_out)

    # -- RAM headroom: age cold segments to disk ---------------------------

    def evict_to_disk(self, max_resident_segments: int,
                      path: str | None = None) -> int:
        """Age oldest compacted (session) segments to disk until at most
        ``max_resident_segments`` of them keep their blob in RAM.

        The cap is sticky: future compactions and ``append_sessions``
        keep honoring it, so a long-running store's resident bytes stay
        bounded while its history grows. Only session segments age out —
        event segments are young by construction (compaction folds them
        away) and the next compaction would decode them anyway. Eviction
        writes the blob to ``path`` (the spill dir; required on the first
        call, remembered after) in the exact ``save``-format
        ``seg_<id>.bin`` blob, then drops it from the in-memory segment.
        All pruning metadata stays resident, so ``scan`` still prunes for
        free and only **re-reads the blobs it actually decodes** —
        transiently, the segment stays evicted (counted in
        ``ScanStats.segments_reloaded`` per scan and
        ``Store.segments_reloaded`` cumulatively). Returns the number of
        segments evicted by this call.
        """
        if max_resident_segments < 0:
            raise ValueError(
                f"max_resident_segments must be >= 0, "
                f"got {max_resident_segments}")
        if path is not None:
            self._spill_dir = path
        if self._spill_dir is None:
            raise ValueError(
                "evict_to_disk needs a spill path on the first call")
        self.max_resident_segments = int(max_resident_segments)
        return self._enforce_residency()

    def _enforce_residency(self) -> int:
        """Evict oldest (lowest seg_id) resident session segments beyond
        the cap. No-op until ``evict_to_disk`` sets one."""
        if self.max_resident_segments is None:
            return 0
        resident = [j for j, g in enumerate(self.segments)
                    if g.kind == "sessions" and not g.on_disk]
        resident.sort(key=lambda j: self.segments[j].seg_id)
        n_evict = max(0, len(resident) - self.max_resident_segments)
        os.makedirs(self._spill_dir, exist_ok=True)
        for j in resident[:n_evict]:
            g = self.segments[j]
            fp = os.path.join(self._spill_dir, f"seg_{g.seg_id}.bin")
            with open(fp, "wb") as f:
                f.write(g.blob)
            self.segments[j] = replace(g, blob=b"", on_disk=True,
                                       disk_bytes=len(g.blob))
            self.segments_evicted += 1
        return n_evict

    def _read_spill(self, seg: Segment) -> bytes:
        fp = os.path.join(self._spill_dir, f"seg_{seg.seg_id}.bin")
        with open(fp, "rb") as f:
            blob = f.read()
        if len(blob) != seg.disk_bytes:
            raise IOError(
                f"spill blob for segment {seg.seg_id} is {len(blob)} "
                f"bytes, expected {seg.disk_bytes} — spill dir corrupted?")
        return blob

    def _reload(self, seg: Segment) -> Segment:
        """A transient resident copy of an evicted segment (the stored
        segment stays on disk — reloads never grow resident bytes)."""
        return replace(seg, blob=self._read_spill(seg), on_disk=False,
                       disk_bytes=0)

    # -- the pruning query path --------------------------------------------

    def scan(self, time_range: tuple[int, int] | None = None,
             users=None, events=None, *,
             segment_ids=None, min_width: int = 0) -> ScanResult:
        """Decode only the segments whose metadata can match the filters.

        ``time_range=(lo, hi)`` is inclusive and matches sessions whose
        ``[start_ts, start_ts + duration_s*1000]`` span intersects it (and
        events with ``lo <= ts <= hi``); ``users`` is an id list (segment
        prune via the user-shard bitmap, exact row filter after);
        ``events`` is a code list (segment prune via the code histogram —
        a returned session contains at least one queried code).
        ``segment_ids`` restricts the scan to named segments (the
        streaming tier reads back only its own). Exact filters are in
        ``scan_matches_*`` so tests can assert pruning changes nothing.
        """
        lo, hi = time_range if time_range is not None else (None, None)
        q_user_mask = (user_shard_mask(users, self.cfg.user_shards)
                       if users is not None else None)
        users_arr = (np.asarray(users, np.int64)
                     if users is not None else None)
        events_arr = (np.asarray(events, np.int64)
                      if events is not None else None)
        wanted = set(segment_ids) if segment_ids is not None else None

        stats = ScanStats(0, 0, 0, 0, 0, 0, 0, 0)
        seq_parts: list[SessionSequences] = []
        ev_parts: list[dict[str, np.ndarray]] = []
        for seg in self.segments:
            if wanted is not None and seg.seg_id not in wanted:
                continue
            stats.segments_total += 1
            if seg.on_disk:
                stats.segments_on_disk += 1
            if time_range is not None and (seg.max_ts < lo
                                           or seg.min_ts > hi):
                stats.pruned_time += 1
                continue
            if q_user_mask is not None and not (seg.user_mask & q_user_mask):
                stats.pruned_users += 1
                continue
            if events_arr is not None and not any(
                    int(c) in seg.code_counts for c in events_arr):
                stats.pruned_events += 1
                continue
            if seg.on_disk:
                # survived every metadata prune: pay the disk read, but
                # only transiently — the stored segment stays evicted
                seg = self._reload(seg)
                stats.segments_reloaded += 1
                self.segments_reloaded += 1
            stats.segments_decoded += 1
            stats.rows_decoded += seg.n
            if seg.kind == "sessions":
                seqs = decode_session_segment(seg, min_width=min_width)
                keep = scan_matches_sessions(seqs, time_range, users_arr,
                                             events_arr)
                seq_parts.append(_take_rows(seqs, keep))
                stats.rows_matched += int(keep.sum())
            else:
                cols = decode_event_segment(seg)
                keep = scan_matches_events(cols, time_range, users_arr,
                                           events_arr)
                ev_parts.append({k: v[keep] for k, v in cols.items()})
                n_match = int(keep.sum())
                stats.rows_matched += n_match
                stats.unmaterialized_events += n_match
        ev = ({k: np.concatenate([p[k] for p in ev_parts])
               for k in EVENT_COLS} if ev_parts
              else {k: np.zeros(0, np.int64 if k != "code" else np.int32)
                    for k in EVENT_COLS})
        return ScanResult(
            sequences=concat_sequences(seq_parts, min_width=min_width),
            events=ev, stats=stats)

    def sequences(self, **scan_kwargs) -> SessionSequences:
        """Materialized sequences matching the filters; raises if matching
        events are still un-compacted (the analytics contract)."""
        res = self.scan(**scan_kwargs)
        if res.stats.unmaterialized_events:
            raise ValueError(
                f"{res.stats.unmaterialized_events} matching events are "
                "still in event segments — run Store.compact() before "
                "querying materialized sequences")
        return res.sequences

    # -- bookkeeping -------------------------------------------------------

    def stored_bytes(self) -> dict[str, int]:
        out = {"events": 0, "sessions": 0}
        for seg in self.segments:
            out[seg.kind] += seg.nbytes
        out["total"] = out["events"] + out["sessions"]
        return out

    def summary(self) -> dict:
        by_kind = {"events": 0, "sessions": 0}
        for seg in self.segments:
            by_kind[seg.kind] += 1
        on_disk = sum(1 for seg in self.segments if seg.on_disk)
        return dict(
            segments=len(self.segments),
            event_segments=by_kind["events"],
            session_segments=by_kind["sessions"],
            segments_on_disk=on_disk,
            segments_evicted=self.segments_evicted,
            segments_reloaded=self.segments_reloaded,
            events_appended=self.events_appended,
            late_appended=self.late_appended,
            compaction_watermark=self.compaction_watermark,
            truncated=self.truncated,
            bytes=self.stored_bytes())

    # -- persistence (atomic manifest + one blob per segment) --------------

    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        manifest = dict(
            cfg=dict(gap_ms=self.cfg.gap_ms, dedup=self.cfg.dedup,
                     max_len=self.cfg.max_len,
                     user_shards=self.cfg.user_shards),
            next_id=self._next_id, events_appended=self.events_appended,
            late_appended=self.late_appended,
            compaction_watermark=self.compaction_watermark,
            truncated=self.truncated,
            segments=[dict(
                seg_id=g.seg_id, kind=g.kind, n=g.n, n_events=g.n_events,
                min_ts=g.min_ts, max_ts=g.max_ts, user_mask=g.user_mask,
                code_counts={str(k): v for k, v in g.code_counts.items()},
                col_bytes=g.col_bytes) for g in self.segments])
        for g in self.segments:
            # evicted blobs round-trip through the spill dir, so a saved
            # store is always fully materialized — load() never needs to
            # know the source store was under a residency cap
            blob = self._read_spill(g) if g.on_disk else g.blob
            with open(os.path.join(path, f"seg_{g.seg_id}.bin"), "wb") as f:
                f.write(blob)
        tmp = os.path.join(path, "manifest.json.tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f)
        os.replace(tmp, os.path.join(path, "manifest.json"))

    @staticmethod
    def load(path: str) -> "Store":
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        store = Store(StoreConfig(**manifest["cfg"]))
        store._next_id = manifest["next_id"]
        store.events_appended = manifest["events_appended"]
        store.late_appended = manifest["late_appended"]
        store.compaction_watermark = manifest["compaction_watermark"]
        store.truncated = manifest["truncated"]
        for m in manifest["segments"]:
            with open(os.path.join(path, f"seg_{m['seg_id']}.bin"),
                      "rb") as f:
                blob = f.read()
            store.segments.append(Segment(
                seg_id=m["seg_id"], kind=m["kind"], n=m["n"],
                n_events=m["n_events"], min_ts=m["min_ts"],
                max_ts=m["max_ts"], user_mask=m["user_mask"],
                code_counts={int(k): v
                             for k, v in m["code_counts"].items()},
                col_bytes=m["col_bytes"], blob=blob))
        return store


# ---------------------------------------------------------------------------
# exact row filters (shared by scan and the pruning-correctness tests)
# ---------------------------------------------------------------------------

def scan_matches_sessions(seqs: SessionSequences,
                          time_range, users_arr, events_arr) -> np.ndarray:
    """Row mask: the exact predicate ``scan``'s session filters implement."""
    keep = np.ones(len(seqs), bool)
    if time_range is not None:
        lo, hi = time_range
        start = np.asarray(seqs.start_ts, np.int64)
        end = start + np.asarray(seqs.duration_s, np.int64) * 1000
        keep &= (start <= hi) & (end >= lo)
    if users_arr is not None:
        keep &= np.isin(seqs.user_id, users_arr)
    if events_arr is not None:
        hit = np.isin(seqs.symbols, events_arr) & seqs.mask()
        keep &= hit.any(axis=1)
    return keep


def scan_matches_events(cols: dict[str, np.ndarray],
                        time_range, users_arr, events_arr) -> np.ndarray:
    keep = np.ones(len(cols["timestamp"]), bool)
    if time_range is not None:
        lo, hi = time_range
        keep &= (cols["timestamp"] >= lo) & (cols["timestamp"] <= hi)
    if users_arr is not None:
        keep &= np.isin(cols["user_id"], users_arr)
    if events_arr is not None:
        keep &= np.isin(cols["code"], events_arr)
    return keep


def _take_rows(seqs: SessionSequences, keep: np.ndarray) -> SessionSequences:
    return SessionSequences(
        symbols=seqs.symbols[keep], length=seqs.length[keep],
        user_id=seqs.user_id[keep], session_id=seqs.session_id[keep],
        ip=seqs.ip[keep], start_ts=seqs.start_ts[keep],
        duration_s=seqs.duration_s[keep])
