"""Pipeline-stage throughput: the vectorized JAX group-by vs the Pig-style
Python oracle, dictionary build, the LM batch pipeline feed rate, the
full 3-stage log pipeline — single-host vs distributed over a mesh of the
devices present (repartition -> dedup+sessionize -> ngram/funnel rollups)
— and the streaming fast-data tier (micro-batch ticks through
repro.data.streampipe, checked bit-equal against the batch oracle)."""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import EventDictionary, sessionize
from repro.core.oracle import sessionize_oracle
from repro.data import SessionBatchPipeline, PipelineConfig
from repro.data.loggen import SIGNUP_FUNNEL
from .common import corpus, timeit, row

# Machine-readable payload for benchmarks/run.py --json (the CI gate parses
# the "stream" section: watermark lag and stream-vs-batch equivalence).
LAST_JSON: dict | None = None
JSON_PATH = "BENCH_pipeline.json"


def _distpipe_rows(n_users: int = 2000, seed: int = 42) -> list[str]:
    """The same corpus and funnel through ``single_host_pipeline`` and the
    distributed pipeline over every device of this process, in-process
    (one process per chip); the rollups must agree before either row is
    reported."""
    from repro.data import generate, LogGenConfig
    from repro.data.distpipe import (DistPipelineConfig,
                                     make_distributed_pipeline,
                                     single_host_pipeline)
    from repro.dist import make_mesh

    log = generate(LogGenConfig(n_users=n_users, seed=seed))
    b = log.batch
    d = EventDictionary.build(b.table, b.name_id)
    codes = np.asarray(d.encode_ids(b.name_id))
    stages = [d.codes_matching(p) for p in SIGNUP_FUNNEL]
    n = len(b)
    ip = b.ip.astype(np.int64)
    n_dev = len(jax.devices())
    cfg = DistPipelineConfig(alphabet_size=d.alphabet_size,
                             max_sessions_per_shard=-(-n // n_dev),
                             max_len=2048)

    def timed(fn, repeats=3):
        out = fn()  # warmup (jit compile); result reused for the check
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e6)
        return float(np.median(ts)), out

    us_single, ora = timed(lambda: single_host_pipeline(
        b.user_id, b.session_id, b.timestamp, codes, ip, cfg=cfg,
        stages=stages))
    pipe = make_distributed_pipeline(make_mesh((n_dev,), ("data",)), cfg,
                                      stages)
    us_dist, res = timed(
        lambda: pipe(b.user_id, b.session_id, b.timestamp, codes, ip))

    assert res.dropped == 0
    assert res.num_sessions() == ora.num_sessions()
    assert np.array_equal(res.ngram_counts, ora.ngram_counts)
    assert res.funnel_reach == ora.funnel_reach
    return [
        row("pipeline_single_host", us_single,
            f"{n / (us_single / 1e6) / 1e6:.2f}M events/s "
            "dedup+sessionize+ngram+funnel"),
        row(f"pipeline_distributed_{n_dev}shard", us_dist,
            f"{n / (us_dist / 1e6) / 1e6:.2f}M events/s "
            f"repartition+dedup+sessionize+rollups, {n_dev} shard(s)"),
    ]


def _stream_rows(n_users: int = 500, seed: int = 42,
                 n_ticks: int = 16) -> list[str]:
    """One loggen day replayed tick-by-tick through the single-host
    streaming tier: events/sec per tick, watermark lag, ring occupancy,
    and a bit-equality check against the batch pipeline after flush."""
    from repro.data import generate, LogGenConfig
    from repro.data.distpipe import single_host_pipeline
    from repro.data.streampipe import (StreamConfig, session_multiset,
                                       single_host_stream, split_ticks)
    global LAST_JSON
    log = generate(LogGenConfig(n_users=n_users, seed=seed))
    b = log.batch
    d = EventDictionary.build(b.table, b.name_id)
    codes = np.asarray(d.encode_ids(b.name_id), np.int32)
    ip = b.ip.astype(np.int64)
    stages = [d.codes_matching(p) for p in SIGNUP_FUNNEL]
    n = len(b)
    ticks = split_ticks(b.timestamp, n_ticks)
    cap = 1 << int(max(len(ix) for ix in ticks) - 1).bit_length()
    # ring sized ~4x the corpus's peak open sessions / longest session —
    # the per-tick merge cost is O(max_open * max_len + tick_capacity)
    cfg = StreamConfig(alphabet_size=d.alphabet_size, max_open=128,
                       max_len=128, tick_capacity=cap,
                       allowed_lateness_ms=60_000)

    def one_replay(rec=None):
        s = single_host_stream(cfg, stages)
        for ix in ticks:
            t0 = time.perf_counter()
            res = s.tick(b.user_id[ix], b.session_id[ix], b.timestamp[ix],
                         codes[ix], ip[ix])
            if rec is not None:
                rec.append(((time.perf_counter() - t0) * 1e6, len(ix),
                            res.open_sessions, s.watermark_lag_ms))
        s.flush()
        return s

    one_replay()  # warmup: compiles the tick; later replays hit the cache
    rec: list[tuple] = []
    s = one_replay(rec)
    got = s.result()
    oracle = single_host_pipeline(b.user_id, b.session_id, b.timestamp,
                                  codes, ip, cfg=cfg.batch_config(n),
                                  stages=stages)
    bit_equal = bool(
        np.array_equal(got.ngram_counts, oracle.ngram_counts)
        and got.funnel_reach == oracle.funnel_reach
        and session_multiset(got.sequences)
        == session_multiset(oracle.sequences))
    us_tick = float(np.median([r[0] for r in rec]))
    ev_per_s = sum(r[1] for r in rec) / (sum(r[0] for r in rec) / 1e6)
    lag_mean = float(np.mean([r[3] for r in rec]))
    occ_peak = max(r[2] for r in rec)
    occ_mean = float(np.mean([r[2] for r in rec]))
    LAST_JSON = {"stream": {
        "n_events": n, "n_ticks": n_ticks,
        "tick_capacity": cfg.tick_capacity, "max_open": cfg.max_open,
        "us_per_tick": us_tick, "events_per_sec": ev_per_s,
        "watermark_lag_ms_mean": lag_mean,
        "occupancy_mean": occ_mean, "occupancy_peak": occ_peak,
        "late_dropped": s.late_dropped,
        "ring_dropped_events": s.ring_dropped_events,
        "bit_equal": bit_equal,
    }}
    return [row("stream_tput", us_tick,
                f"{ev_per_s / 1e3:.1f}K events/s/tick "
                f"lag={lag_mean:.0f}ms occ={occ_peak}/{cfg.max_open} "
                f"bit_equal={bit_equal}")]


def run() -> list[str]:
    c = corpus()
    b, codes, seqs = c["batch"], c["codes"], c["seqs"]
    n = len(b)

    us_jax = timeit(lambda: sessionize(
        b.user_id, b.session_id, b.timestamp, codes, b.ip.astype(np.int64),
        max_sessions=n, max_len=2048).symbols.block_until_ready(), repeats=3)
    us_py = timeit(lambda: sessionize_oracle(
        b.user_id, b.session_id, b.timestamp, codes), repeats=1, warmup=0)

    us_dict = timeit(lambda: EventDictionary.build(b.table, b.name_id))

    pipe = SessionBatchPipeline(seqs, PipelineConfig(seq_len=512,
                                                     global_batch=8))
    nb = pipe.batches_per_epoch()

    def one_epoch():
        for _ in pipe.epoch(0):
            pass

    us_pipe = timeit(one_epoch, repeats=2)
    toks = nb * 8 * 512
    return [
        row("sessionize_jax", us_jax,
            f"{n / (us_jax / 1e6) / 1e6:.2f}M events/s"),
        row("sessionize_python_oracle", us_py,
            f"{n / (us_py / 1e6) / 1e6:.2f}M events/s "
            f"(jax speedup={us_py / us_jax:.1f}x)"),
        row("dictionary_build", us_dict, f"alphabet from {n} events"),
        row("lm_batch_pipeline_epoch", us_pipe,
            f"{toks / (us_pipe / 1e6) / 1e6:.2f}M tokens/s prefetch=2"),
        *_distpipe_rows(),
        *_stream_rows(),
    ]
