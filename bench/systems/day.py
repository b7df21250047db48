"""The daily session job under test: ``DistributedPipeline.__call__`` over
a ``data`` mesh of the cell's chips, then ``Store.append_sessions`` of the
day's sessions (the store the stream writes to), then the day's rollups
folded into running totals. A step is one whole day of ``DayFeed``."""
from __future__ import annotations

import numpy as np

from bench.feed import COLUMNS, DayFeed, shift
from bench.logtier import LogSystem
from bench.reference import fast

SPAN = "day"


class System(LogSystem):
    span = SPAN
    sub_spans = ("day.pipeline", "day.store", "day.fold")

    def build(self, mesh) -> None:
        from repro.data.distpipe import (DistPipelineConfig,
                                         make_distributed_pipeline)
        from repro.data.store import Store, StoreConfig
        cfg, stages = self.cfg, self.stages
        self.feed = DayFeed(self.day)
        self.unit = self.feed.size
        self.dp = make_distributed_pipeline(mesh, DistPipelineConfig(
            alphabet_size=cfg["alphabet_size"],
            max_sessions_per_shard=cfg["max_sessions_per_shard"],
            max_len=cfg["max_len"], gap_ms=cfg["gap_ms"],
            capacity_factor=cfg["capacity_factor"], dedup=cfg["dedup"],
            ngram_n=cfg["ngram_n"]), stages)
        self.store = Store(StoreConfig(gap_ms=cfg["gap_ms"],
                                       dedup=cfg["dedup"],
                                       max_len=cfg["max_len"]))
        self.bigrams = np.zeros(cfg["alphabet_size"] ** cfg["ngram_n"],
                                np.int64)
        self.reach = np.zeros(len(stages), np.int64)
        self.days = 0
        self.n_dropped = 0
        self.truncated = False
        self.fed = 0

    def step(self, n: int) -> int:
        if n != self.unit:
            raise ValueError(f"the day job takes whole days of {self.unit} "
                             f"events, not {n}: give it closed-loop traffic")
        from jax.profiler import TraceAnnotation
        cols = self.feed.day(self.days)
        with TraceAnnotation("day.pipeline"):
            res = self.dp(*(cols[k] for k in COLUMNS))
        with TraceAnnotation("day.store"):
            self.store.append_sessions(res.to_sequences())
        with TraceAnnotation("day.fold"):
            self.bigrams += res.ngram_counts
            self.reach += np.array([c for _, c in res.funnel_reach],
                                   np.int64)
            self.n_dropped += res.dropped
            self.truncated |= res.truncated
        self.days += 1
        self.fed += n
        return n

    def dropped(self) -> int:
        return self.n_dropped

    def finish(self) -> None:
        pass

    def output(self) -> dict:
        seqs = self.store.scan(min_width=self.cfg["max_len"]).sequences
        return dict(
            sessions=fast.from_padded(
                seqs.symbols, seqs.length, seqs.user_id, seqs.session_id,
                seqs.ip, seqs.start_ts, seqs.duration_s),
            bigrams=self.bigrams.copy(), reach=self.reach.copy(),
            dropped=self.n_dropped, truncated=int(self.truncated))

    def reference(self, dedup: bool = True) -> dict:
        """Each day run is the generated day moved to its own population
        and date, and sessions move with their rows, so the reference
        sessionizes the generated day once and moves the result."""
        A = self.cfg["alphabet_size"]
        base = fast.sessionize(*(self.day[k] for k in COLUMNS), dedup=dedup,
                               gap_ms=self.cfg["gap_ms"])
        return dict(
            sessions=fast.concat([shift(base, k) for k in range(self.days)]),
            bigrams=self.days * fast.bigram_counts(base, A),
            reach=self.days * fast.funnel_reach(base, self.stages, A))
