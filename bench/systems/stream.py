"""The streaming tier under test: ``StreamPipeline.tick`` over a ``data``
mesh of the cell's chips, fed time-ordered ticks of ``StreamFeed``.

A step is one call of ``tick()``: it returns once the tick's closed
sessions are appended to the store and its rollup deltas are folded into
the running totals. ``finish`` flushes the stream after the window, so
every event fed ends in a stored session and the check sees them all.
"""
from __future__ import annotations

import numpy as np

from bench.feed import COLUMNS, StreamFeed, shift
from bench.loggen import DAY_MS
from bench.logtier import LogSystem
from bench.reference import fast

SPAN = "tick"


class System(LogSystem):
    span = SPAN
    sub_spans = ()

    def build(self, mesh) -> None:
        from repro.data.streampipe import StreamConfig, make_stream_pipeline
        cfg, stages = self.cfg, self.stages
        self.feed = StreamFeed(self.day, cfg["day"]["start_ts_ms"],
                               cfg.get("period_ms", DAY_MS))
        self.unit = cfg["tick_capacity"]
        self.sp = make_stream_pipeline(mesh, StreamConfig(
            alphabet_size=cfg["alphabet_size"], max_open=cfg["max_open"],
            max_len=cfg["max_len"], tick_capacity=cfg["tick_capacity"],
            capacity_factor=cfg["capacity_factor"], gap_ms=cfg["gap_ms"],
            allowed_lateness_ms=cfg["allowed_lateness_ms"],
            dedup=cfg["dedup"], ngram_n=cfg["ngram_n"]), stages)
        self.fed = 0

    def step(self, n: int) -> int:
        c = self.feed.take(self.fed, n)
        self.fed += n
        self.sp.tick(*(c[k] for k in COLUMNS))
        return n

    def dropped(self) -> int:
        sp = self.sp
        return (sp.late_dropped + sp.shuffle_dropped
                + sp.ring_dropped_events)

    def finish(self) -> None:
        self.sp.flush()

    def output(self) -> dict:
        """What the program stored and folded, as the check reads it."""
        sp = self.sp
        seqs = sp.sessions()
        return dict(
            sessions=fast.from_padded(
                seqs.symbols, seqs.length, seqs.user_id, seqs.session_id,
                seqs.ip, seqs.start_ts, seqs.duration_s),
            bigrams=np.asarray(sp.ngram_totals, np.int64),
            reach=np.asarray(sp.reach_totals, np.int64),
            dropped=self.dropped() + sp.ring_dropped_sessions,
            truncated=int(sp.truncated))

    def reference(self, dedup: bool = True) -> dict:
        """The reference over every event fed. A period whose every event
        was fed gives the generated period's sessions moved to its
        population; the periods cut by the first and last tick are
        sessionized from the rows fed. Periods share no user, so their
        sessions are independent."""
        cfg = self.cfg
        A = cfg["alphabet_size"]
        pos = np.arange(self.fed, dtype=np.int64)
        day_of = self.feed.day_of(pos)
        counts = np.bincount(day_of)
        full = np.flatnonzero(counts == self.feed.size)
        cut = ~np.isin(day_of, full)
        rows = self.feed.take(0, 0)
        if cut.any():
            rows = {k: np.concatenate([self.feed.take(int(a), int(b - a))[k]
                                       for a, b in _runs(pos[cut])])
                    for k in COLUMNS}
        partial = fast.sessionize(*(rows[k] for k in COLUMNS), dedup=dedup,
                                  gap_ms=cfg["gap_ms"])
        parts, bigrams, reach = [partial], fast.bigram_counts(partial, A), \
            fast.funnel_reach(partial, self.stages, A)
        if len(full):
            base = fast.sessionize(*(self.day[k] for k in COLUMNS),
                                   dedup=dedup, gap_ms=cfg["gap_ms"])
            parts += [shift(base, d, self.feed.days_later(d),
                            self.feed.period) for d in full]
            bigrams = bigrams + len(full) * fast.bigram_counts(base, A)
            reach = reach + len(full) * fast.funnel_reach(base, self.stages,
                                                          A)
        return dict(sessions=fast.concat(parts), bigrams=bigrams,
                    reach=reach)


def _runs(pos: np.ndarray):
    """Contiguous runs of sorted positions as ``(start, stop)`` pairs."""
    breaks = np.flatnonzero(np.diff(pos) != 1) + 1
    starts = np.r_[0, breaks]
    stops = np.r_[breaks, len(pos)]
    return [(pos[a], pos[b - 1] + 1) for a, b in zip(starts, stops)]
