"""Drive the system's main path once on a TPU and check every result.

    python chip_smoke.py [--seed N]     # one chip: log, serve, kernel phases
    python chip_smoke.py --chips 4      # four chips: the sharded log
                                        # pipeline and streaming tier only
    python chip_smoke.py --rehearse [--chips 4]
                                        # same control flow at a tiny size
                                        # on the CPU, kernels interpreted

Phases (one chip):

* log — a loggen day of 2^20 client events through the dictionary coding,
  ``single_host_pipeline`` (dedup, sessionize, n-gram and funnel rollups),
  the distributed pipeline on a one-device mesh, a 16-tick replay through
  the streaming tier, and the segment store (append, two compactions, one
  pruned scan). Everything is compared exactly with ``core/oracle.py``.
* serve — ``behavior-lm-100m`` FULL in bf16 through the continuous
  scheduler (paged KV cache, prefix cache, 16 slots), 64 requests cut from
  the day's sessions, 16 new tokens each, served twice: the first pass
  compiles, the second must not retrace. Greedy tokens must equal the
  ``Server.generate_batch`` oracle's.
* kernel — one call of each Pallas kernel at the served model's widths,
  against its jnp reference.

With ``--chips 4`` the distributed pipeline and the streaming tier run
sharded over a 4-device ``data`` mesh at 4 x 2^20 events, against the
oracle, and nothing else.

Every phase raises on any difference. Each phase prints one line with its
wall seconds and the part of them XLA spent compiling (host clock: set-up
figures, not measurements) and the device's ``peak_bytes_in_use``. The last line is one JSON object
naming the device. Without a TPU the script exits non-zero and prints no
result (``--rehearse`` alone runs elsewhere, and its result names the CPU).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
N_TICKS = 16
MAX_LEN = 256            # stored events per session (the dry-run's shape)


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated day of events")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: log, serve and kernel phases on one chip; "
                         "4: the sharded log tier over four chips only")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU with interpreted kernels: "
                         "checks control flow, never a chip result")
    return ap.parse_args()


class Phase:
    """Prints one line per phase: wall seconds, the part of them XLA spent
    compiling (what the persistent compilation cache saves), the rest, and
    the device's peak bytes so far."""

    compile_s = 0.0

    @classmethod
    def listen(cls):
        import jax

        def on_event(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                cls.compile_s += secs
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name: str, **facts):
        self.name, self.facts = name, facts

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        import jax
        wall = time.perf_counter() - self.t0
        comp = Phase.compile_s - self.c0
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps(dict(
            phase=self.name, wall_s=round(wall, 3),
            xla_compile_s=round(comp, 3), rest_s=round(wall - comp, 3),
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            **self.facts)), flush=True)
        return False


def check(cond, what):
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# the day of client events and its oracle
# ---------------------------------------------------------------------------

def loggen_day(n_events: int, seed: int, copies: int = 1):
    """``n_events`` loggen events of one day (default mix: about 46 events
    per user), dictionary-coded, plus the oracle's sessions. ``copies`` > 1
    tiles one generated day over that many disjoint user populations
    (generation is a host loop; this keeps it at one day's cost)."""
    from repro.core import EventDictionary
    from repro.core.dictionary import histogram
    from repro.core.oracle import (dedup_events_oracle, histogram_oracle,
                                   sessionize_oracle)
    from repro.data import LogGenConfig, generate
    from repro.data.loggen import SIGNUP_FUNNEL

    per_copy = n_events // copies
    log = generate(LogGenConfig(n_users=int(per_copy / 44.5) + 1,
                                horizon_days=1, seed=seed))
    b = log.batch
    check(len(b) >= per_copy, f"loggen made {len(b)} < {per_copy} events")

    def tile(x, step=0):
        return np.concatenate([x[:per_copy] + k * step
                               for k in range(copies)])

    name_id = tile(b.name_id)
    d = EventDictionary.build(b.table, name_id)
    d.verify()
    check(np.array_equal(np.asarray(histogram(name_id, len(b.table))),
                         histogram_oracle(name_id, len(b.table))),
          "device histogram != oracle histogram")
    code = np.asarray(d.encode_ids(name_id), np.int32)
    check(np.array_equal(np.asarray(d.decode_codes(code)), name_id),
          "dictionary decode(encode(x)) != x")
    ev = dict(user_id=tile(b.user_id, 1 << 40),
              session_id=tile(b.session_id), timestamp=tile(b.timestamp),
              code=code, ip=tile(b.ip.astype(np.int64)))
    keep = dedup_events_oracle(*ev.values())
    oracle = sessionize_oracle(*ev.values(), valid=keep)
    stages = [d.codes_matching(p) for p in SIGNUP_FUNNEL]
    return ev, d, stages, oracle


def oracle_rows(sessions):
    """The oracle's sessions in the comparator's canonical form."""
    return sorted((s["user_id"], s["session_id"], s["start_ts"], s["ip"],
                   s["duration_s"], tuple(s["symbols"])) for s in sessions)


def check_rollups(what, seqs, ngrams, reach, oracle, stages, alphabet):
    """Sessions, dense bigram counts and funnel reach vs the oracle."""
    from repro.core.oracle import funnel_oracle, ngram_counts_oracle
    from repro.data.streampipe import session_multiset
    check(not (seqs.length > MAX_LEN).any(),
          f"{what}: a session exceeds max_len={MAX_LEN}")
    check(session_multiset(seqs) == oracle_rows(oracle),
          f"{what}: sessions differ from the oracle")
    dense = np.zeros(alphabet ** 2, np.int64)
    for (a, c), k in ngram_counts_oracle(oracle, 2).items():
        dense[a * alphabet + c] = k
    check(np.array_equal(np.asarray(ngrams, np.int64), dense),
          f"{what}: bigram counts differ from the oracle")
    want = funnel_oracle(oracle, stages)
    check([c for _, c in reach] == want,
          f"{what}: funnel reach {reach} != oracle {want}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def stream_replay(mesh, ev, alphabet, stages, *, max_open):
    """The day in N_TICKS time-ordered ticks through the streaming tier."""
    from repro.data.streampipe import (StreamConfig, make_stream_pipeline,
                                       replay)
    n, shards = len(ev["user_id"]), mesh.shape["data"]
    # a time-ordered tick holds the users active in one slice of the day,
    # so its repartition is skewed: size the buckets for the worst case
    cfg = StreamConfig(alphabet_size=alphabet, max_open=max_open,
                       max_len=MAX_LEN, tick_capacity=-(-n // N_TICKS),
                       capacity_factor=float(shards))
    sp = make_stream_pipeline(mesh, cfg, stages)
    replay(sp, *ev.values(), n_ticks=N_TICKS)
    check(sp.trace_counts["tick"] == 1,
          f"stream tick traced {sp.trace_counts['tick']} times")
    check(sp.late_dropped == sp.shuffle_dropped == 0,
          "stream dropped events (late or repartition overflow)")
    check(sp.ring_dropped_sessions == 0 and not sp.truncated,
          f"stream ring of {max_open} overflowed or truncated")
    return sp


def log_phase(ev, d, stages, oracle, *, max_open):
    from repro.data.distpipe import (DistPipelineConfig,
                                     make_distributed_pipeline,
                                     single_host_pipeline)
    from repro.data.store import Store, StoreConfig
    from repro.data.streampipe import session_multiset
    from repro.dist import make_mesh

    n, A = len(ev["user_id"]), d.alphabet_size
    cfg = DistPipelineConfig(alphabet_size=A, max_sessions_per_shard=n,
                             max_len=MAX_LEN)
    with Phase("log.single_host_pipeline", events=n):
        res = single_host_pipeline(*ev.values(), cfg=cfg, stages=stages)
        check(not res.truncated, "single-host pipeline truncated")
        check_rollups("single-host pipeline", res.sequences,
                      res.ngram_counts, res.funnel_reach, oracle, stages, A)
    mesh = make_mesh((1,), ("data",))
    with Phase("log.distributed_pipeline", events=n, shards=1):
        dist = make_distributed_pipeline(mesh, cfg, stages)(*ev.values())
        check(dist.dropped == 0 and not dist.truncated,
              "distributed pipeline dropped or truncated")
        check_rollups("distributed pipeline", dist.to_sequences(),
                      dist.ngram_counts, dist.funnel_reach, oracle, stages, A)
    with Phase("log.stream", events=n, ticks=N_TICKS, max_open=max_open):
        sp = stream_replay(mesh, ev, A, stages, max_open=max_open)
        got = sp.result()
        check_rollups("stream", got.sequences, got.ngram_counts,
                      got.funnel_reach, oracle, stages, A)
    with Phase("log.store", events=n):
        store = Store(StoreConfig(max_len=MAX_LEN))
        order = np.argsort(ev["timestamp"], kind="stable")
        for ix in np.array_split(order, N_TICKS):
            store.append_events(*(v[ix] for v in ev.values()))
        t = np.sort(ev["timestamp"])
        store.compact(watermark=int(t[n // 2]))
        store.compact()
        check(store.segments and all(g.kind == "sessions"
                                     for g in store.segments),
              "store left event segments after the final compaction")
        check(session_multiset(store.sequences()) == oracle_rows(oracle),
              "compacted store differs from the oracle")
        lo, hi = int(t[n // 8]), int(t[n // 4])
        scan = store.scan(time_range=(lo, hi))
        check(scan.stats.segments_pruned >= 1,
              f"time-range scan pruned nothing: {scan.stats}")
        want = [s for s in oracle if s["start_ts"] <= hi
                and s["start_ts"] + 1000 * s["duration_s"] >= lo]
        check(session_multiset(scan.sequences) == oracle_rows(want),
              "pruned scan differs from the oracle")
    return res.sequences


def tolerance(dtype):
    """The repo's comparison bound for a dtype (tests/test_kernels.py)."""
    import jax.numpy as jnp
    return (dict(rtol=2e-2, atol=2e-2) if jnp.dtype(dtype) == jnp.bfloat16
            else dict(rtol=2e-5, atol=2e-5))


def check_greedy(what, served, logits, tol):
    """Each served token must be the oracle's greedy choice up to ``tol``.

    ``logits`` are the oracle's, teacher-forced on the served tokens, so
    every step is judged in the served context: the served token's logit
    must lie within ``atol + rtol * |max|`` of the step's largest. The
    scheduler and the fixed-batch oracle are different XLA programs (batch
    16 paged vs 64 dense, prefill width bucket vs rectangle), which XLA
    does not promise to round alike; in bf16 that flips near-ties. A wrong
    cache block or position lands far from the argmax instead. Returns the
    largest shortfall seen."""
    worst = 0.0
    for i, toks in enumerate(served):
        lg = logits[i, :len(toks)]
        best = lg.max(-1)
        short = best - lg[np.arange(len(toks)), toks]
        bad = short > tol["atol"] + tol["rtol"] * np.abs(best)
        check(not bad.any(),
              f"{what}: request {i} token {int(np.argmax(bad))} is "
              f"{float(short.max()):.4f} below the oracle's greedy logit")
        worst = max(worst, float(short.max()))
    return worst


def serve_phase(seqs, alphabet, *, rehearse: bool):
    from repro.data import EOS_ID, lm_vocab_size
    from repro.launch.serve import BUCKETS, build_model, request_stream
    from repro.serve import (ContinuousScheduler, SchedulerConfig, Server,
                             ServeConfig, ServeMetrics)

    slots, n_req, new_tokens = 16, 64, 16
    with Phase("serve.build", arch="behavior-lm-100m",
               widths="SMOKE" if rehearse else "FULL"):
        api, params = build_model("behavior-lm-100m", lm_vocab_size(alphabet),
                                  smoke=rehearse)
        stream = [toks for toks, _, _ in
                  request_stream(seqs, api.cfg, n_req, slots)]
        sched = ContinuousScheduler(api, params, SchedulerConfig(
            batch=slots, buckets=BUCKETS, max_new_tokens=new_tokens,
            paged=True, block_size=16, prefix_cache=True),
            metrics=ServeMetrics())
    with Phase("serve.oracle", requests=n_req):
        rect = np.zeros((n_req, max(len(t) for t in stream)), np.int32)
        for i, t in enumerate(stream):
            rect[i, :len(t)] = t
        oracle = Server(api, params, ServeConfig(max_new_tokens=new_tokens))
        greedy = oracle.generate_batch(rect)
    traces = None
    for name in ("serve.warmup", "serve.window"):
        with Phase(name, requests=n_req, slots=slots,
                   new_tokens=new_tokens) as ph:
            rids = [sched.submit(t) for t in stream]
            outs = sched.run()
            served = [outs[rid] for rid in rids]
            forced = np.full((n_req, new_tokens), EOS_ID, np.int32)
            for i, toks in enumerate(served):
                forced[i, :len(toks)] = toks
            ph.facts["max_logit_shortfall"] = check_greedy(
                name, served, oracle.score_batch(rect, forced),
                tolerance(api.cfg.dtype))
            ph.facts["equal_to_generate_batch"] = sum(
                np.array_equal(t, greedy[i][:len(t)])
                for i, t in enumerate(served))
            ph.facts["trace_counts"] = dict(sched.trace_counts)
            ph.facts["prefix_hit_rate"] = \
                sched.metrics.summary()["prefix_hit_rate"]
        if traces is not None:
            check(dict(sched.trace_counts) == traces,
                  f"retraced after warm-up: {traces} -> "
                  f"{dict(sched.trace_counts)}")
        traces = dict(sched.trace_counts)
    return api.cfg


def kernel_phase(seqs, stages, alphabet, cfg, *, rehearse: bool):
    import jax
    import jax.numpy as jnp
    from repro.analytics.funnel import build_stage_table
    from repro.kernels.event_count.ops import histogram
    from repro.kernels.flash_attention.ops import (flash_attention,
                                                   paged_decode_attention)
    from repro.kernels.funnel_match.ops import deepest_stage

    impl = "interpret" if rehearse else "pallas"
    H, D = cfg.num_heads, cfg.resolved_head_dim
    dt, tol = jnp.dtype(cfg.dtype), tolerance(cfg.dtype)
    key = iter(jax.random.split(jax.random.PRNGKey(0), 8))

    def normal(shape):
        return jax.random.normal(next(key), shape, jnp.float32).astype(dt)

    with Phase("kernel.flash_attention", impl=impl, heads=H, head_dim=D):
        q, k, v = (normal((1, H, 512, D)) for _ in range(3))
        got = flash_attention(q, k, v, causal=True, impl=impl)
        want = flash_attention(q, k, v, causal=True, impl="ref")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)
    with Phase("kernel.paged_decode_attention", impl=impl, block_size=16):
        B, bs, nb = 16, 16, cfg.max_cache_len // 16
        kp, vp = normal((B * nb + 1, H, bs, D)), normal((B * nb + 1, H, bs, D))
        q = normal((B, H, 1, D))
        table = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
        kv_len = np.random.default_rng(0).integers(1, nb * bs + 1, B,
                                                   dtype=np.int32)
        got = paged_decode_attention(q, kp, vp, table, kv_len, impl=impl)
        want = paged_decode_attention(q, kp, vp, table, kv_len, impl="ref")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)
    sym, mask = seqs.symbols, seqs.mask()
    with Phase("kernel.histogram", impl=impl, alphabet=alphabet):
        got = histogram(sym, mask, alphabet, impl=impl)
        want = histogram(sym, mask, alphabet, impl="ref")
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              "histogram kernel != reference")
    with Phase("kernel.deepest_stage", impl=impl, stages=len(stages)):
        table = build_stage_table(stages, alphabet)
        got = deepest_stage(sym, mask, table, impl=impl)
        want = deepest_stage(sym, mask, table, impl="ref")
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              "funnel kernel != reference")


def sharded_phase(ev, d, stages, oracle, n_chips, *, max_open):
    """The distributed pipeline and the streaming tier over all chips."""
    import jax
    from repro.data.distpipe import (DistPipelineConfig,
                                     make_distributed_pipeline)
    from repro.dist import make_mesh

    n, A = len(ev["user_id"]), d.alphabet_size
    mesh = make_mesh((n_chips,), ("data",))
    cfg = DistPipelineConfig(alphabet_size=A,
                             max_sessions_per_shard=n // n_chips,
                             max_len=MAX_LEN)
    with Phase("sharded.distributed_pipeline", events=n, shards=n_chips):
        dist = make_distributed_pipeline(mesh, cfg, stages)(*ev.values())
        check(dist.dropped == 0 and not dist.truncated,
              "distributed pipeline dropped or truncated")
        per_shard = np.asarray(dist.sessions["num_sessions"]).tolist()
        check(min(per_shard) > 0, f"a shard got no sessions: {per_shard}")
        check_rollups("distributed pipeline", dist.to_sequences(),
                      dist.ngram_counts, dist.funnel_reach, oracle, stages, A)
    with Phase("sharded.stream", events=n, shards=n_chips,
               ticks=N_TICKS) as ph:
        sp = stream_replay(mesh, ev, A, stages, max_open=max_open)
        ring = sp._ring["symbols"]
        devices = sorted(s.device.id for s in ring.addressable_shards)
        check(devices == sorted(dv.id for dv in jax.devices()[:n_chips])
              and all(s.data.shape[0] == 1 for s in ring.addressable_shards),
              f"stream ring not split one shard per device: {devices}")
        ph.facts["ring_devices"] = devices
        got = sp.result()
        check_rollups("stream", got.sequences, got.ngram_counts,
                      got.funnel_reach, oracle, stages, A)


def main() -> int:
    args = parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    Phase.listen()
    per_chip = 1 << (12 if args.rehearse else 20)
    max_open = 1 << (8 if args.rehearse else 12)
    n = per_chip * args.chips
    with Phase("loggen", events=n, compile_cache=cache):
        ev, d, stages, oracle = loggen_day(n, args.seed, copies=args.chips)
    if args.chips > 1:
        sharded_phase(ev, d, stages, oracle, args.chips, max_open=max_open)
    else:
        seqs = log_phase(ev, d, stages, oracle, max_open=max_open)
        cfg = serve_phase(seqs, d.alphabet_size, rehearse=args.rehearse)
        kernel_phase(seqs, stages, d.alphabet_size, cfg,
                     rehearse=args.rehearse)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
