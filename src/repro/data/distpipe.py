"""Distributed multi-stage log pipeline over ``repro.dist`` (§4–§5 at mesh
scale).

The paper's claim is that one unified log format plus pre-materialized
session sequences turns ad-hoc per-query scans into a reusable pipeline:
client events -> sessionize -> session sequences -> rollups. This module is
that pipeline as ONE composable sharded dataflow — three shard_map stages
sharing the ``repro.dist`` primitives, replacing the single-host numpy path
as the scalable entry point (``data/pipeline.py`` stays as the LM-batch
consumer of the materialized sequences):

* **Stage 1 — keyed repartition.** Each ``data``-axis shard holds an
  arbitrary slice of the hour's raw event columns (exactly how the log
  mover deposits them). Rows are bucketed by ``shard_of_user`` and an
  ``all_to_all`` performs the keyed shuffle (``dist.collectives
  .keyed_all_to_all``) — all events of a user land on one shard, so
  sessions never straddle shards. Fixed-capacity bucketing counts (never
  silently drops) overflow.
* **Stage 2 — dedup + sessionize.** Scribe delivery is at-least-once;
  row-level retry duplicates survive into the warehouse. Each shard clears
  them with ``core.sessionize.mark_duplicate_events`` and runs the fused
  sort + segment sessionizer on its now-complete per-user slice.
* **Stage 3 — sharded rollups.** Fixed-shape shard-local aggregates merged
  with one ``psum`` tree each (the ``make_distributed_histogram`` pattern):
  dense n-gram counts over packed window keys
  (``analytics.ngram.dense_ngram_counts``) and the funnel-automaton reach
  table (``analytics.funnel.reach_histogram``). Session tensors stay
  sharded (gathered lazily by ``DistPipelineResult.to_sequences``).

On a host-local (1, N) mesh the outputs are bit-equal to the single-host
oracle path (``single_host_pipeline``); tests/test_distpipe.py holds that
equivalence including ragged (non-divisible) input sizes, which the wrapper
handles by padding with invalid rows spread round-robin across shards.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..analytics.funnel import build_stage_table, funnel_reach, \
    reach_histogram
from ..analytics.ngram import dense_ngram_counts
from ..core.sequences import SessionSequences
from ..core.spans import span
from ..core.sessionize import DEFAULT_GAP_MS, mark_duplicate_events, \
    sessionize, _sessionize
from ..dist.collectives import keyed_all_to_all, shard_of_user
from ..dist.compat import enable_x64, shard_map, use_mesh

SESSION_FIELDS = ("symbols", "length", "user_id", "session_id", "ip",
                  "start_ts", "duration_s", "num_sessions", "num_events",
                  "truncated")


@dataclass(frozen=True)
class DistPipelineConfig:
    """Static shape/semantics knobs of one pipeline instance.

    ``capacity_factor`` sizes the per-destination repartition buckets
    relative to a perfectly uniform split (production sizes this from the
    previous histogram job); overflow is counted in ``dropped``, and the
    caller re-runs with a larger factor. ``alphabet_size ** ngram_n`` must
    fit in memory — the rollup is a dense mergeable histogram.
    """
    alphabet_size: int
    max_sessions_per_shard: int
    max_len: int
    axis: str = "data"
    gap_ms: int = DEFAULT_GAP_MS
    capacity_factor: float = 2.0
    dedup: bool = True
    ngram_n: int = 2


@dataclass
class DistPipelineResult:
    """Pipeline outputs: sharded session tensors + merged global rollups.

    ``sessions`` fields carry a leading (n_shards,) dim; rows past
    ``sessions["num_sessions"][shard]`` are padding. ``ngram_counts`` is the
    dense (alphabet_size**ngram_n,) global count vector; ``funnel_reach``
    matches ``analytics.funnel.funnel_reach`` output (None when the pipeline
    was built without stages). ``dropped`` counts rows lost to repartition
    capacity overflow (0 unless ``capacity_factor`` was too small).
    """
    sessions: dict[str, np.ndarray]
    ngram_counts: np.ndarray
    funnel_reach: list[tuple[int, int]] | None
    dropped: int
    truncated: bool

    def num_sessions(self) -> int:
        return int(self.sessions["num_sessions"].sum())

    def to_sequences(self) -> SessionSequences:
        """Gather the sharded sessions into one host-side relation (shard
        order, per-shard (user, session, start) order)."""
        ns = self.sessions["num_sessions"]
        with span("distpipe.gather", sessions=int(ns.sum())):
            parts = {name: [self.sessions[name][sh, : int(ns[sh])]
                            for sh in range(len(ns))]
                     for name in ("symbols", "length", "user_id",
                                  "session_id", "ip", "start_ts",
                                  "duration_s")}
            return SessionSequences(
                **{k: np.concatenate(v) for k, v in parts.items()})


def build_pipeline_fn(mesh: Mesh, cfg: DistPipelineConfig, n_stages: int):
    """The shard_map-ed three-stage dataflow, un-jitted.

    Exposed separately from ``make_distributed_pipeline`` so the dry-run
    harness can ``jit(...).lower()`` it with ShapeDtypeStructs on the
    production mesh (launch/dryrun.py --pipeline) without allocating the
    hour's event columns.

    Takes ``(user_id, session_id, timestamp, code, ip, valid, stage_table)``
    — all int64/int32/bool columns sharded on the leading dim over
    ``cfg.axis``, stage_table replicated — and returns
    ``(sessions, ngram_counts, reach, dropped)``.
    """
    axis, n_shards = cfg.axis, mesh.shape[cfg.axis]

    def local_fn(user_id, session_id, timestamp, code, ip, valid, stage_tab):
        # ---- stage 1: keyed all_to_all repartition by user ----
        n_local = user_id.shape[0]
        capacity = int(np.ceil(n_local * cfg.capacity_factor / n_shards))
        idx = jnp.arange(n_local, dtype=jnp.int32)
        # Padding/invalid rows are spread round-robin so they never crowd
        # one destination's capacity.
        dest = jnp.where(valid, shard_of_user(user_id, n_shards),
                         idx % n_shards)
        cols = dict(user_id=user_id, session_id=session_id,
                    timestamp=timestamp, code=code, ip=ip,
                    valid=valid.astype(jnp.int32))
        flat, dropped = keyed_all_to_all(cols, dest, axis, n_shards, capacity)
        # Received padding rows: zero-initialized buckets have valid=0.
        valid_r = flat["valid"].astype(bool)

        # ---- stage 2: within-user dedup + sessionize ----
        if cfg.dedup:
            valid_r = mark_duplicate_events(
                flat["user_id"], flat["session_id"], flat["timestamp"],
                flat["code"], flat["ip"], valid_r)
        sess = _sessionize(
            flat["user_id"], flat["session_id"], flat["timestamp"],
            flat["code"], flat["ip"], valid_r,
            gap_ms=cfg.gap_ms, max_sessions=cfg.max_sessions_per_shard,
            max_len=cfg.max_len)

        # ---- stage 3: sharded rollups, one psum tree each ----
        stored = jnp.minimum(sess["length"], cfg.max_len)
        mask = jnp.arange(cfg.max_len)[None, :] < stored[:, None]
        grams = dense_ngram_counts(sess["symbols"], mask, cfg.ngram_n,
                                   cfg.alphabet_size)
        grams = jax.lax.psum(grams, axis)
        if n_stages:
            reach = jax.lax.psum(
                reach_histogram(sess["symbols"], mask, stage_tab, n_stages),
                axis)
        else:
            reach = jnp.zeros((0,), jnp.int32)
        total_dropped = jax.lax.psum(dropped, axis)
        sess = {k: v[None] for k, v in sess.items()}
        return sess, grams, reach, total_dropped[None]

    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis),) * 6 + (P(),),
        out_specs=({k: P(axis) for k in SESSION_FIELDS}, P(), P(), P(axis)))


class DistributedPipeline:
    """Callable wrapper: host columns in, ``DistPipelineResult`` out.

    Handles ragged inputs (pads each column to a multiple of the shard
    count with invalid rows), int64 promotion under ``enable_x64``, and
    mesh activation. ``self.fn`` is the raw shard_map-ed dataflow for
    callers that manage jit/lowering themselves (dry-run harness).
    """

    def __init__(self, mesh: Mesh, cfg: DistPipelineConfig, stages=None):
        self.mesh = mesh
        self.cfg = cfg
        self.stage_table = (None if stages is None else
                            build_stage_table(stages, cfg.alphabet_size))
        n_stages = 0 if self.stage_table is None else len(self.stage_table)
        self.fn = build_pipeline_fn(mesh, cfg, n_stages)
        self._jitted = jax.jit(self.fn)

    def __call__(self, user_id, session_id, timestamp, code, ip=None,
                 valid=None) -> DistPipelineResult:
        cfg = self.cfg
        n = len(user_id)
        n_shards = self.mesh.shape[cfg.axis]
        if ip is None:
            ip = np.zeros(n, np.int64)
        if valid is None:
            valid = np.ones(n, bool)
        pad = (-n) % n_shards

        def col(x, dtype):
            x = np.asarray(x, dtype)
            return np.concatenate([x, np.zeros(pad, dtype)]) if pad else x

        table = (np.zeros((0, cfg.alphabet_size), bool)
                 if self.stage_table is None else self.stage_table)
        with span("distpipe.call", events=n):
            with enable_x64():
                with span("distpipe.put"):
                    args = (jnp.asarray(col(user_id, np.int64)),
                            jnp.asarray(col(session_id, np.int64)),
                            jnp.asarray(col(timestamp, np.int64)),
                            jnp.asarray(col(code, np.int32)),
                            jnp.asarray(col(ip, np.int64)),
                            jnp.asarray(col(valid, bool)),
                            jnp.asarray(table))
                with use_mesh(self.mesh), span("distpipe.dispatch"):
                    out = self._jitted(*args)
            with span("distpipe.wait"):
                sess, grams, reach, dropped = jax.block_until_ready(out)
            with span("distpipe.pull", bytes=sum(
                    x.nbytes for x in jax.tree.leaves(out))):
                sess = {k: np.asarray(v) for k, v in sess.items()}
                grams, reach = np.asarray(grams), np.asarray(reach)
                dropped = np.asarray(dropped)
            return DistPipelineResult(
                sessions=sess,
                ngram_counts=grams.astype(np.int64),
                funnel_reach=(None if self.stage_table is None else
                              [(j, int(c)) for j, c in enumerate(reach)]),
                dropped=int(dropped[0]),
                truncated=bool(sess["truncated"].any()))


def make_distributed_pipeline(mesh: Mesh, cfg: DistPipelineConfig,
                              stages=None) -> DistributedPipeline:
    """Build the distributed pipeline over ``mesh[cfg.axis]``.

    ``stages`` is an optional funnel spec — a list of per-stage code sets
    (as produced by ``EventDictionary.codes_matching``); omit it to skip the
    funnel rollup.
    """
    return DistributedPipeline(mesh, cfg, stages)


@dataclass
class SingleHostResult:
    """Oracle-path outputs, field-compatible with ``DistPipelineResult``."""
    sequences: SessionSequences
    ngram_counts: np.ndarray
    funnel_reach: list[tuple[int, int]] | None
    truncated: bool

    def num_sessions(self) -> int:
        return len(self.sequences)

    def to_sequences(self) -> SessionSequences:
        return self.sequences


def single_host_pipeline(user_id, session_id, timestamp, code, ip=None,
                         valid=None, *, cfg: DistPipelineConfig,
                         stages=None, max_sessions: int | None = None
                         ) -> SingleHostResult:
    """The same dedup -> sessionize -> n-gram/funnel dataflow on one host —
    the equivalence oracle for the distributed pipeline (and the
    single-host baseline in benchmarks/pipeline_tput.py)."""
    s = sessionize(user_id, session_id, timestamp, code, ip, valid,
                   gap_ms=cfg.gap_ms, dedup=cfg.dedup,
                   max_sessions=max_sessions, max_len=cfg.max_len)
    seqs = SessionSequences.from_sessionized(s)
    with enable_x64():
        dense = np.asarray(dense_ngram_counts(
            jnp.asarray(seqs.symbols), jnp.asarray(seqs.mask()),
            cfg.ngram_n, cfg.alphabet_size)).astype(np.int64)
    reach = (None if stages is None else
             funnel_reach(seqs, stages, cfg.alphabet_size))
    return SingleHostResult(sequences=seqs, ngram_counts=dense,
                            funnel_reach=reach,
                            truncated=bool(np.asarray(s.truncated)))
