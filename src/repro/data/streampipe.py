"""Streaming "fast data" ingestion: incremental sessionize + online rollups.

``data/distpipe.py`` is batch-oriented — a closed hour of client events in,
session sequences and rollups out. Both Twitter follow-ups push the same
unified-logging infrastructure to seconds-level latency: the real-time
related-query architecture (arxiv 1210.7350) sessionizes in-flight, and
Loginson (arxiv 1703.02602) puts a buffered transform-and-load tier in
front of the store. This module is that tier over the existing
``repro.dist`` collectives:

* **Ring buffer of open sessions.** Each shard owns a fixed-capacity,
  device-resident table of open sessions keyed by user: per-slot
  ``(user_id, session_id, length)`` plus ``(max_open, max_len)`` grids of
  symbols, event timestamps, and event ips (the per-event grids are what
  make exact out-of-order merging possible — a late-but-in-watermark event
  is re-sorted into its session, not appended).
* **Micro-batch ticks.** Each tick repartitions its new events with the
  same keyed ``all_to_all`` the batch pipeline uses
  (``dist.collectives.keyed_all_to_all``), drops-and-counts events older
  than the watermark in force at arrival, then re-runs the fused
  sort + segment sessionizer (``core.sessionize._sessionize``) over
  (flattened ring events ∪ new events). Because it is the *same* kernel
  the batch path runs, closed-prefix bit-equality is by construction, not
  by reimplementation. Per-tick cost is O(open events + tick events) —
  independent of how much history has already been folded away.
* **Watermark semantics.** The watermark is monotone; by default it
  trails the max event time seen by ``allowed_lateness_ms`` (explicit
  ``tick(..., watermark=)`` overrides, clamped monotone). Events with
  ``ts < watermark`` at arrival are late: dropped and counted. A session
  closes when ``last_event_ts + gap_ms < watermark`` — no acceptable
  future event can extend it, so its contribution is final (the paper's
  30-minute gap crossing the watermark).
* **Incremental rollup deltas.** Closed sessions emit dense n-gram and
  funnel-reach deltas (``analytics.ngram.dense_ngram_counts``,
  ``analytics.funnel.reach_histogram``), psum-merged across shards and
  accumulated into running totals host-side. Integer histograms make the
  fold exact: totals after N ticks are bit-equal to one batch rollup over
  the same closed sessions.
* **Overflow accounting.** Repartition capacity overflow and ring
  overflow (more open sessions than ``max_open``) drop whole rows /
  sessions deterministically and are *counted*, never silent — surviving
  sessions are unaffected.

Oracle contract (tests/test_streampipe.py, ``stream_tput`` benchmark row):
replaying any event stream tick-by-tick, the closed sessions and running
rollup totals at every watermark are bit-equal to
``data.distpipe.single_host_pipeline`` run over the *closed prefix* of the
accepted events (``closed_prefix_mask``). Cross-tick exact-retry dedup is
exact too: a duplicate of an open-session event is removed against the
ring (the ring keeps full per-event keys), and a duplicate of an
already-closed event is necessarily late (its timestamp predates the
watermark that closed the session) so it is dropped either way.

Truncation caveat: a session longer than ``max_len`` keeps only its first
``max_len`` events in the ring, so subsequent merges cannot see the tail;
``truncated`` is flagged sticky and closed-prefix equality is only claimed
for untruncated streams (same contract as the batch pipeline's caps).
"""
from __future__ import annotations

import collections
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analytics.funnel import build_stage_table, reach_histogram
from ..analytics.ngram import dense_ngram_counts
from ..core.sequences import SessionSequences
from ..core.spans import span
from ..core.sessionize import (DEFAULT_GAP_MS, PAD_CODE, _I64_MAX,
                               _sessionize, closed_prefix_mask,
                               mark_duplicate_events)
from ..dist.collectives import keyed_all_to_all, shard_of_user
from ..dist.compat import enable_x64, shard_map, use_mesh
from .distpipe import DistPipelineConfig, SingleHostResult, \
    single_host_pipeline
from .store import Store, StoreConfig

# Initial watermark / flush watermark. Not the full int64 range so that
# ``end_ts + gap_ms`` can never overflow next to them.
WATERMARK_MIN = -(1 << 62)
WATERMARK_MAX = 1 << 62

RING_FIELDS = ("user_id", "session_id", "length", "symbols", "event_ts",
               "event_ip", "valid")
CLOSED_FIELDS = ("symbols", "length", "user_id", "session_id", "ip",
                 "start_ts", "duration_s")
_PER_ROW_FIELDS = CLOSED_FIELDS + ("event_ts", "event_ip", "end_ts")
COUNTER_FIELDS = ("late_dropped", "shuffle_dropped", "ring_dropped_events",
                  "ring_dropped_sessions", "open_sessions",
                  "closed_sessions", "truncated")


@dataclass(frozen=True)
class StreamConfig:
    """Static shape/semantics knobs of one streaming pipeline instance.

    ``max_open`` is the per-shard ring capacity (open sessions);
    ``tick_capacity`` bounds the events per tick (hosts pad up to it so the
    tick compiles once and never retraces); ``allowed_lateness_ms`` is how
    far the default watermark trails the max event time seen. ``gap_ms``,
    ``dedup``, ``ngram_n`` and ``alphabet_size`` mirror
    ``DistPipelineConfig`` — they must match the batch pipeline's for the
    closed-prefix equivalence to hold.
    """
    alphabet_size: int
    max_open: int
    max_len: int
    tick_capacity: int
    axis: str = "data"
    gap_ms: int = DEFAULT_GAP_MS
    allowed_lateness_ms: int = 0
    capacity_factor: float = 2.0
    dedup: bool = True
    ngram_n: int = 2

    def batch_config(self, max_sessions_per_shard: int = 1
                     ) -> DistPipelineConfig:
        """The batch-pipeline config with matching semantics — the oracle
        side of the closed-prefix equivalence."""
        return DistPipelineConfig(
            alphabet_size=self.alphabet_size,
            max_sessions_per_shard=max_sessions_per_shard,
            max_len=self.max_len, axis=self.axis, gap_ms=self.gap_ms,
            dedup=self.dedup, ngram_n=self.ngram_n)


@dataclass
class TickResult:
    """Host-visible outcome of one tick.

    ``accepted`` masks the tick's *input* rows that passed the late filter
    (the replay harness feeds exactly these to the batch oracle);
    ``open_sessions`` is the post-tick ring occupancy summed over shards.
    Dropped counts are per-tick, not cumulative.
    """
    watermark: int
    accepted: np.ndarray
    closed_sessions: int
    open_sessions: int
    late_dropped: int
    shuffle_dropped: int
    ring_dropped_events: int
    ring_dropped_sessions: int
    truncated: bool


@dataclass
class StreamResult:
    """Closed-so-far sessions + running rollup totals, field-compatible
    with ``distpipe.SingleHostResult`` for oracle comparisons."""
    sequences: SessionSequences
    ngram_counts: np.ndarray
    funnel_reach: list[tuple[int, int]] | None
    truncated: bool
    late_dropped: int
    shuffle_dropped: int
    ring_dropped_events: int

    def num_sessions(self) -> int:
        return len(self.sequences)

    def to_sequences(self) -> SessionSequences:
        return self.sequences


def _init_ring_np(cfg: StreamConfig) -> dict[str, np.ndarray]:
    O, L = cfg.max_open, cfg.max_len
    return dict(
        user_id=np.full(O, -1, np.int64),
        session_id=np.full(O, -1, np.int64),
        length=np.zeros(O, np.int32),
        symbols=np.full((O, L), PAD_CODE, np.int32),
        event_ts=np.zeros((O, L), np.int64),
        event_ip=np.zeros((O, L), np.int64),
        valid=np.zeros(O, bool),
    )


def stream_state_structs(cfg: StreamConfig, n_shards: int = 0
                         ) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs of the ring state (leading shard dim when
    ``n_shards`` > 0) — the dry-run harness lowers the tick with these."""
    lead = (n_shards,) if n_shards else ()
    return {k: jax.ShapeDtypeStruct(lead + v.shape, v.dtype)
            for k, v in _init_ring_np(cfg).items()}


def _tick_core(ring, ev, wm_prev, wm_new, stage_tab, *, cfg: StreamConfig,
               n_stages: int):
    """One shard's tick: late filter -> merge into open sessions -> close
    past the watermark -> rollup deltas. Pure; fixed shapes throughout.

    ``ev`` columns have a fixed per-shard length (``tick_capacity`` on a
    single host, ``n_shards * capacity`` post-``all_to_all``); rows beyond
    the tick are ``valid=False``. Returns
    ``(new_ring, closed_block, n_closed, ngram_delta, reach_delta,
    counters)`` where ``closed_block`` rows ``[:n_closed]`` are the
    sessions closed this tick (sessionizer sort order).
    """
    O, L = cfg.max_open, cfg.max_len
    T = ev["user_id"].shape[0]
    s_cap = O + T  # worst case: every ring segment + one split per event

    late = ev["valid"] & (ev["timestamp"] < wm_prev)
    n_late = jnp.sum(late.astype(jnp.int32))
    ev_valid = ev["valid"] & ~late

    # Flatten the ring back into event rows. Stored events carry their full
    # (user, session, ts, code, ip) key, so dedup and re-sort against the
    # new events are exact.
    with jax.named_scope("ring"):
        stored = jnp.minimum(ring["length"], L)
        col = jnp.arange(L, dtype=jnp.int32)
        r_valid = (ring["valid"][:, None] & (col[None, :] < stored[:, None]))
        r_user = jnp.broadcast_to(ring["user_id"][:, None], (O, L))
        r_sess = jnp.broadcast_to(ring["session_id"][:, None], (O, L))

        u = jnp.concatenate([r_user.reshape(-1), ev["user_id"]])
        s = jnp.concatenate([r_sess.reshape(-1), ev["session_id"]])
        t = jnp.concatenate([ring["event_ts"].reshape(-1), ev["timestamp"]])
        c = jnp.concatenate([ring["symbols"].reshape(-1), ev["code"]])
        i = jnp.concatenate([ring["event_ip"].reshape(-1), ev["ip"]])
        v = jnp.concatenate([r_valid.reshape(-1), ev_valid])
    if cfg.dedup:
        # Ring rows precede tick rows, so a retry duplicate of a stored
        # event is the copy that dies — ring contents stay stable.
        v = mark_duplicate_events(u, s, t, c, i, v)

    sess = _sessionize(u, s, t, c, i, v, gap_ms=cfg.gap_ms,
                       max_sessions=s_cap, max_len=L, with_event_grids=True)

    row = jnp.arange(s_cap, dtype=jnp.int32)
    with jax.named_scope("ring"):
        nonempty = row < sess["num_sessions"]
        # Closed iff no future event can join: any extender has
        # ts <= end_ts + gap, and future arrivals have ts >= watermark.
        closed = nonempty & (sess["end_ts"] + cfg.gap_ms < wm_new)
        open_m = nonempty & ~closed

        with jax.named_scope("sort"):
            perm_c = jnp.argsort(~closed, stable=True)  # closed rows first
        cb = {k: sess[k][perm_c] for k in _PER_ROW_FIELDS}
        n_closed = jnp.sum(closed.astype(jnp.int32))

    c_stored = jnp.minimum(cb["length"], L)
    c_mask = ((row[:, None] < n_closed)
              & (jnp.arange(L)[None, :] < c_stored[:, None]))
    grams = dense_ngram_counts(cb["symbols"], c_mask, cfg.ngram_n,
                               cfg.alphabet_size)
    if n_stages:
        reach = reach_histogram(cb["symbols"], c_mask, stage_tab, n_stages)
    else:
        reach = jnp.zeros((0,), jnp.int32)

    with jax.named_scope("ring"):
        with jax.named_scope("sort"):
            perm_o = jnp.argsort(~open_m, stable=True)  # open rows first
        ob = {k: sess[k][perm_o] for k in _PER_ROW_FIELDS}
        n_open = jnp.sum(open_m.astype(jnp.int32))
        keep = jnp.arange(O, dtype=jnp.int32) < jnp.minimum(n_open, O)
        new_ring = dict(
            user_id=jnp.where(keep, ob["user_id"][:O], -1),
            session_id=jnp.where(keep, ob["session_id"][:O], -1),
            length=jnp.where(keep, ob["length"][:O], 0),
            symbols=jnp.where(keep[:, None], ob["symbols"][:O], PAD_CODE),
            event_ts=jnp.where(keep[:, None], ob["event_ts"][:O], 0),
            event_ip=jnp.where(keep[:, None], ob["event_ip"][:O], 0),
            valid=keep,
        )
    # Ring overflow: open sessions ranked past capacity are dropped whole
    # (deterministic — sessionizer sort order), counted never silent.
    over = (row >= O) & (row < n_open)
    counters = dict(
        late_dropped=n_late.astype(jnp.int64),
        shuffle_dropped=jnp.zeros((), jnp.int64),
        ring_dropped_events=jnp.sum(
            jnp.where(over, ob["length"], 0)).astype(jnp.int64),
        ring_dropped_sessions=jnp.maximum(n_open - O, 0).astype(jnp.int64),
        open_sessions=jnp.minimum(n_open, O).astype(jnp.int64),
        closed_sessions=n_closed.astype(jnp.int64),
        truncated=sess["truncated"].astype(jnp.int64),
    )
    closed_block = {k: cb[k] for k in CLOSED_FIELDS}
    return new_ring, closed_block, n_closed, grams, reach, counters


@functools.lru_cache(maxsize=None)
def _single_host_tick(cfg: StreamConfig, n_stages: int):
    """Jitted single-host tick, cached per (cfg, n_stages) so every
    ``SingleHostStream`` with the same shapes shares one jit cache (the
    property tests build hundreds of instances). The returned counter
    increments only when jit (re)traces — the zero-retrace assertion."""
    counter = collections.Counter()

    def fn(ring, ev, wm_prev, wm_new, stage_tab):
        counter["tick"] += 1  # runs at trace time only
        ring, cb, n_closed, grams, reach, counters = _tick_core(
            ring, ev, wm_prev, wm_new, stage_tab, cfg=cfg, n_stages=n_stages)
        # one shard, laid out as the mesh tick lays out its outputs
        return (ring, {k: v[None] for k, v in cb.items()}, n_closed[None],
                grams, reach, counters)

    return jax.jit(fn), counter


def build_stream_tick_fn(mesh: Mesh, cfg: StreamConfig, n_stages: int):
    """The shard_map-ed distributed tick, un-jitted (the dry-run harness
    lowers it with ShapeDtypeStructs; ``StreamPipeline`` jits it).

    Takes ``(ring, user_id, session_id, timestamp, code, ip, valid,
    wm_prev, wm_new, stage_table)`` — ring fields stacked on a leading
    shard dim and sharded over ``cfg.axis`` like the event columns;
    watermarks and stage table replicated — and returns ``(new_ring,
    closed_block, n_closed_per_shard, ngram_delta, reach_delta, counters)``
    with the deltas and counters psum-merged.
    """
    axis, n_shards = cfg.axis, mesh.shape[cfg.axis]
    if cfg.tick_capacity % n_shards:
        raise ValueError(
            f"tick_capacity={cfg.tick_capacity} must divide evenly over "
            f"{n_shards} '{axis}' shards")
    local_t = cfg.tick_capacity // n_shards
    capacity = max(int(np.ceil(local_t * cfg.capacity_factor / n_shards)), 1)

    def local_fn(ring, user_id, session_id, timestamp, code, ip, valid,
                 wm_prev, wm_new, stage_tab):
        ring = {k: v[0] for k, v in ring.items()}
        # Stage 1: keyed all_to_all repartition by user (padding rows are
        # spread round-robin so they never crowd one destination).
        idx = jnp.arange(local_t, dtype=jnp.int32)
        dest = jnp.where(valid, shard_of_user(user_id, n_shards),
                         idx % n_shards)
        cols = dict(user_id=user_id, session_id=session_id,
                    timestamp=timestamp, code=code, ip=ip,
                    valid=valid.astype(jnp.int32))
        flat, dropped = keyed_all_to_all(cols, dest, axis, n_shards,
                                         capacity)
        ev = dict(user_id=flat["user_id"], session_id=flat["session_id"],
                  timestamp=flat["timestamp"], code=flat["code"],
                  ip=flat["ip"], valid=flat["valid"].astype(bool))
        new_ring, cb, n_closed, grams, reach, counters = _tick_core(
            ring, ev, wm_prev, wm_new, stage_tab, cfg=cfg,
            n_stages=n_stages)
        counters["shuffle_dropped"] = dropped.astype(jnp.int64)
        grams = jax.lax.psum(grams, axis)
        reach = jax.lax.psum(reach, axis)
        counters = {k: jax.lax.psum(v, axis) for k, v in counters.items()}
        new_ring = {k: v[None] for k, v in new_ring.items()}
        cb = {k: v[None] for k, v in cb.items()}
        return new_ring, cb, n_closed[None], grams, reach, counters

    return shard_map(
        local_fn, mesh=mesh,
        in_specs=({k: P(axis) for k in RING_FIELDS},)
                 + (P(axis),) * 6 + (P(), P(), P()),
        out_specs=({k: P(axis) for k in RING_FIELDS},
                   {k: P(axis) for k in CLOSED_FIELDS},
                   P(axis), P(), P(),
                   {k: P() for k in COUNTER_FIELDS}))


class _StreamBase:
    """Shared host bookkeeping: watermark advance, late masks, the
    segment-store sink for closed sessions, running totals. Subclasses
    implement ``_device_tick``."""

    def __init__(self, cfg: StreamConfig, stages=None,
                 store: Store | None = None):
        self.cfg = cfg
        self.stages = stages
        self.stage_table = (None if stages is None else
                            build_stage_table(stages, cfg.alphabet_size))
        self.n_stages = (0 if self.stage_table is None
                         else len(self.stage_table))
        self._stage_tab = jnp.asarray(
            np.zeros((0, cfg.alphabet_size), bool)
            if self.stage_table is None else self.stage_table)
        self.watermark = WATERMARK_MIN
        self.max_ts_seen = WATERMARK_MIN
        self.ngram_totals = np.zeros(cfg.alphabet_size ** cfg.ngram_n,
                                     np.int64)
        self.reach_totals = np.zeros(self.n_stages, np.int64)
        # Closed sessions land in the unified segment store (one immutable
        # session segment per watermark that closed any), not in host
        # arrays — the same store the batch path compacts into. Pass a
        # shared ``store`` to fan several streams into one mega-table.
        self.store = store if store is not None else Store(StoreConfig(
            gap_ms=cfg.gap_ms, dedup=cfg.dedup, max_len=cfg.max_len))
        self._segment_ids: list[int] = []
        self.closed_total = 0
        self.late_dropped = 0
        self.shuffle_dropped = 0
        self.ring_dropped_events = 0
        self.ring_dropped_sessions = 0
        self.truncated = False

    # -- subclass surface --------------------------------------------------

    def _device_tick(self, ev: dict[str, jax.Array], wm_prev: jax.Array,
                     wm_new: jax.Array):
        """Dispatch the tick on the device-resident padded events, keep
        the new ring, and return ``self._pull`` of the other outputs."""
        raise NotImplementedError

    # -- the tick ----------------------------------------------------------

    def tick(self, user_id, session_id, timestamp, code, ip=None, *,
             watermark: int | None = None) -> TickResult:
        """Ingest one micro-batch and advance the watermark.

        ``watermark`` overrides the default (max event ts seen minus
        ``allowed_lateness_ms``); either way it is clamped monotone. Rows
        older than the *previous* watermark are late — dropped and counted
        (they arrived after their session could already have closed);
        rows between the previous and new watermark still merge, then
        sessions whose 30-minute gap crosses the new watermark close.
        """
        cfg = self.cfg
        n = len(user_id)
        if n > cfg.tick_capacity:
            raise ValueError(
                f"tick has {n} events > tick_capacity={cfg.tick_capacity}; "
                "split the tick or build the stream with a larger capacity")
        flush = watermark is not None and watermark >= WATERMARK_MAX
        with span("streampipe.tick", events=n, flush=int(flush)):
            ts = np.asarray(timestamp, np.int64)
            wm_prev = self.watermark
            if n:
                self.max_ts_seen = max(self.max_ts_seen, int(ts.max()))
            if watermark is not None:
                wm_new = max(wm_prev, int(watermark))
            elif n:
                wm_new = max(wm_prev, int(ts.max()) - cfg.allowed_lateness_ms)
            else:
                wm_new = wm_prev
            accepted = (ts >= wm_prev) if n else np.zeros(0, bool)

            with span("streampipe.put"):
                ev = self._pad_events(user_id, session_id, ts, code, ip, n)
                with enable_x64():
                    ev = {k: jnp.asarray(v) for k, v in ev.items()}
                    wm = (jnp.asarray(wm_prev, jnp.int64),
                          jnp.asarray(wm_new, jnp.int64))
            closed, grams, reach, counters = self._device_tick(ev, *wm)
            if len(closed["length"]):
                seg = self.store.append_sessions(SessionSequences(
                    **{k: closed[k] for k in CLOSED_FIELDS}))
                self._segment_ids.append(seg.seg_id)
            with span("streampipe.fold"):
                self.ngram_totals += grams.astype(np.int64)
                if self.n_stages:
                    self.reach_totals += reach.astype(np.int64)
                self.watermark = wm_new
                self.closed_total += counters["closed_sessions"]
                self.late_dropped += counters["late_dropped"]
                self.shuffle_dropped += counters["shuffle_dropped"]
                self.ring_dropped_events += counters["ring_dropped_events"]
                self.ring_dropped_sessions += counters["ring_dropped_sessions"]
                self.truncated |= bool(counters["truncated"])
            return TickResult(
                watermark=wm_new, accepted=accepted,
                closed_sessions=counters["closed_sessions"],
                open_sessions=counters["open_sessions"],
                late_dropped=counters["late_dropped"],
                shuffle_dropped=counters["shuffle_dropped"],
                ring_dropped_events=counters["ring_dropped_events"],
                ring_dropped_sessions=counters["ring_dropped_sessions"],
                truncated=bool(counters["truncated"]))

    def flush(self) -> TickResult:
        """Advance the watermark past every possible event: all open
        sessions close (end of day / drain)."""
        z64 = np.zeros(0, np.int64)
        return self.tick(z64, z64, z64, np.zeros(0, np.int32),
                         watermark=WATERMARK_MAX)

    def _pull(self, cb, n_closed, grams, reach, counters):
        """The tick's outputs to the host: each shard's closed rows
        ``[:n_closed[shard]]``, the rollup deltas and the counters."""
        with span("streampipe.wait"):
            nc = np.asarray(n_closed)
        pulled = jax.tree.leaves((cb, grams, reach, counters))
        with span("streampipe.pull", bytes=sum(x.nbytes for x in pulled),
                  sessions=int(nc.sum())):
            closed = {k: np.concatenate([np.asarray(v)[sh, : int(nc[sh])]
                                         for sh in range(len(nc))])
                      for k, v in cb.items()}
            counters = {k: int(np.asarray(v)) for k, v in counters.items()}
            return closed, np.asarray(grams), np.asarray(reach), counters

    def _pad_events(self, user_id, session_id, ts, code, ip, n):
        cap = self.cfg.tick_capacity
        pad = cap - n
        if ip is None:
            ip = np.zeros(n, np.int64)

        def col(x, dtype):
            x = np.asarray(x, dtype)
            return np.concatenate([x, np.zeros(pad, dtype)]) if pad else x

        return dict(user_id=col(user_id, np.int64),
                    session_id=col(session_id, np.int64),
                    timestamp=col(ts, np.int64),
                    code=col(code, np.int32),
                    ip=col(ip, np.int64),
                    valid=np.arange(cap) < n)

    # -- results -----------------------------------------------------------

    @property
    def watermark_lag_ms(self) -> int:
        """How far the watermark trails the newest event seen."""
        return max(self.max_ts_seen - self.watermark, 0)

    def sessions(self) -> SessionSequences:
        """All sessions closed so far (tick order within shard order),
        decoded back from this stream's own session segments in the
        store — the store is the source of truth, not host arrays."""
        return self.store.scan(segment_ids=self._segment_ids,
                               min_width=self.cfg.max_len).sequences

    def result(self) -> StreamResult:
        reach = (None if self.stage_table is None else
                 [(j, int(c)) for j, c in enumerate(self.reach_totals)])
        return StreamResult(
            sequences=self.sessions(),
            ngram_counts=self.ngram_totals.copy(),
            funnel_reach=reach, truncated=self.truncated,
            late_dropped=self.late_dropped,
            shuffle_dropped=self.shuffle_dropped,
            ring_dropped_events=self.ring_dropped_events)


class SingleHostStream(_StreamBase):
    """The streaming path on one host (no mesh) — the oracle for
    ``StreamPipeline`` and itself oracle-tested against the batch
    ``single_host_pipeline`` on every closed prefix."""

    def __init__(self, cfg: StreamConfig, stages=None,
                 store: Store | None = None):
        super().__init__(cfg, stages, store)
        self._tick_jit, self.trace_counts = _single_host_tick(
            cfg, self.n_stages)
        self._ring = _init_ring_np(cfg)

    def open_state(self) -> dict[str, np.ndarray]:
        """Host copy of the ring (tests/debugging)."""
        return {k: np.asarray(v) for k, v in self._ring.items()}

    def _device_tick(self, ev, wm_prev, wm_new):
        with enable_x64(), span("streampipe.dispatch",
                                traces=self.trace_counts["tick"]):
            self._ring, *out = self._tick_jit(self._ring, ev, wm_prev,
                                              wm_new, self._stage_tab)
        return self._pull(*out)


class StreamPipeline(_StreamBase):
    """The distributed streaming path: per-shard rings over
    ``mesh[cfg.axis]``, keyed all_to_all repartition each tick, psum-merged
    rollup deltas. Bit-equal to ``SingleHostStream`` fed the same ticks
    (sessions compared as multisets — shard partitioning permutes order)."""

    def __init__(self, mesh: Mesh, cfg: StreamConfig, stages=None,
                 store: Store | None = None):
        super().__init__(cfg, stages, store)
        self.mesh = mesh
        self.n_shards = mesh.shape[cfg.axis]
        self.trace_counts = collections.Counter()
        fn = build_stream_tick_fn(mesh, cfg, self.n_stages)

        def counted(*args):
            self.trace_counts["tick"] += 1  # trace time only
            return fn(*args)

        self._tick_jit = jax.jit(counted)
        # The ring starts where every tick leaves it — sharded over the
        # mesh — so the first tick's input types match every later one's
        # and the tick traces once.
        sharded = NamedSharding(mesh, P(cfg.axis))
        with enable_x64():
            self._ring = {
                k: jax.device_put(
                    np.broadcast_to(v, (self.n_shards,) + v.shape), sharded)
                for k, v in _init_ring_np(cfg).items()}

    def _device_tick(self, ev, wm_prev, wm_new):
        with enable_x64(), use_mesh(self.mesh), span(
                "streampipe.dispatch", traces=self.trace_counts["tick"]):
            self._ring, *out = self._tick_jit(
                self._ring, ev["user_id"], ev["session_id"], ev["timestamp"],
                ev["code"], ev["ip"], ev["valid"], wm_prev, wm_new,
                self._stage_tab)
        return self._pull(*out)


def single_host_stream(cfg: StreamConfig, stages=None,
                       store: Store | None = None) -> SingleHostStream:
    """Build the single-host streaming oracle path. ``store`` is the
    segment store closed sessions sink into (default: a fresh one)."""
    return SingleHostStream(cfg, stages, store)


def make_stream_pipeline(mesh: Mesh, cfg: StreamConfig, stages=None,
                         store: Store | None = None) -> StreamPipeline:
    """Build the distributed streaming pipeline over ``mesh[cfg.axis]``.
    ``stages`` is the optional funnel spec, as in
    ``make_distributed_pipeline``; ``store`` the shared segment store."""
    return StreamPipeline(mesh, cfg, stages, store)


# ---------------------------------------------------------------------------
# replay harness + batch oracle helpers
# ---------------------------------------------------------------------------

def batch_closed_prefix(cfg: StreamConfig, stages, user_id, session_id,
                        timestamp, code, ip, accepted,
                        watermark: int) -> SingleHostResult:
    """The batch oracle over the closed prefix: restrict the accepted
    events to closed sessions at ``watermark`` and run
    ``single_host_pipeline`` with matching semantics.

    Inputs are padded to the next power of two (masked invalid) so the
    replay harness's per-watermark oracle runs hit a small ladder of jit
    shapes instead of retracing at every prefix length.
    """
    acc = np.asarray(accepted, bool)
    u = np.asarray(user_id, np.int64)[acc]
    s = np.asarray(session_id, np.int64)[acc]
    t = np.asarray(timestamp, np.int64)[acc]
    c = np.asarray(code, np.int32)[acc]
    i = np.asarray(ip, np.int64)[acc]
    m = closed_prefix_mask(u, s, t, gap_ms=cfg.gap_ms, watermark=watermark)
    nv = int(m.sum())
    cap = 1 << max(nv - 1, 0).bit_length()
    pad = cap - nv

    def col(x, dtype):
        return np.concatenate([np.asarray(x, dtype)[m],
                               np.zeros(pad, dtype)])

    return single_host_pipeline(
        col(u, np.int64), col(s, np.int64), col(t, np.int64),
        col(c, np.int32), col(i, np.int64), np.arange(cap) < nv,
        cfg=cfg.batch_config(cap), stages=stages, max_sessions=cap)


def session_multiset(seqs: SessionSequences) -> list[tuple]:
    """Canonical sortable view of a session relation — the comparator for
    the bit-equality assertions (shard/tick partitioning permutes rows)."""
    m = seqs.mask()
    return sorted(
        (int(seqs.user_id[j]), int(seqs.session_id[j]),
         int(seqs.start_ts[j]), int(seqs.ip[j]), int(seqs.duration_s[j]),
         tuple(int(x) for x in seqs.symbols[j][m[j]]))
        for j in range(len(seqs)))


def assert_stream_equals_batch(stream: _StreamBase,
                               oracle: SingleHostResult) -> None:
    """Bitwise closed-prefix equality: running rollup totals equal the
    batch rollups, closed sessions equal as a multiset."""
    got = stream.result()
    assert np.array_equal(got.ngram_counts, oracle.ngram_counts), \
        "n-gram totals diverge from the batch oracle"
    if oracle.funnel_reach is not None:
        assert got.funnel_reach == oracle.funnel_reach, \
            (got.funnel_reach, oracle.funnel_reach)
    assert session_multiset(got.sequences) == \
        session_multiset(oracle.sequences), \
        "closed sessions diverge from the batch oracle"


def split_ticks(timestamp, n_ticks: int) -> list[np.ndarray]:
    """Index arrays for ``n_ticks`` contiguous time-ordered micro-batches
    (the log mover's arrival order; shuffle them to simulate lateness)."""
    order = np.argsort(np.asarray(timestamp, np.int64), kind="stable")
    return [ix for ix in np.array_split(order, n_ticks) if True]


def replay(stream: _StreamBase, user_id, session_id, timestamp, code,
           ip=None, *, n_ticks: int = 8,
           tick_index: list[np.ndarray] | None = None,
           assert_closed_prefix: bool = False, stages=None,
           flush: bool = True) -> list[TickResult]:
    """Feed a whole event log through ``stream`` tick-by-tick.

    ``tick_index`` overrides the default time-ordered split. With
    ``assert_closed_prefix`` the accepted prefix is checked against the
    batch oracle *at every watermark* (and after the final flush) —
    the acceptance harness for tests and the ``stream_tput`` benchmark.
    ``stages`` defaults to the stream's own funnel spec.
    """
    if stages is None:
        stages = stream.stages
    u = np.asarray(user_id, np.int64)
    s = np.asarray(session_id, np.int64)
    t = np.asarray(timestamp, np.int64)
    c = np.asarray(code, np.int32)
    i = (np.zeros(len(u), np.int64) if ip is None
         else np.asarray(ip, np.int64))
    ticks = tick_index if tick_index is not None else split_ticks(t, n_ticks)
    fed = {k: [] for k in "ustci"}
    accepted: list[np.ndarray] = []
    results = []

    def check():
        cols = {k: (np.concatenate(v) if v else
                    np.zeros(0, np.int64 if k != "c" else np.int32))
                for k, v in fed.items()}
        acc = (np.concatenate(accepted) if accepted
               else np.zeros(0, bool))
        oracle = batch_closed_prefix(
            stream.cfg, stages, cols["u"], cols["s"], cols["t"], cols["c"],
            cols["i"], acc, stream.watermark)
        assert_stream_equals_batch(stream, oracle)

    for ix in ticks:
        res = stream.tick(u[ix], s[ix], t[ix], c[ix], i[ix])
        results.append(res)
        for k, v in zip("ustci", (u, s, t, c, i)):
            fed[k].append(v[ix])
        accepted.append(res.accepted)
        if assert_closed_prefix:
            check()
    if flush:
        results.append(stream.flush())
        if assert_closed_prefix:
            check()
    return results
