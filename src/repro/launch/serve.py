"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Restores the newest checkpoint (if any) and serves batched next-event
predictions over session prefixes drawn from the live pipeline. The
default (and ``--continuous``) path serves the prefixes as an open-ended
request stream (variable prompt lengths, > 3x the slot count) through the
continuous-batching scheduler — **every registry family**, including
ssm/hybrid (recurrent rows) and encdec/vlm (per-request frames/patches
extras) — and prints the latency/throughput summary afterwards.

``--batch`` opts into the fixed-batch ``Server.generate_batch`` oracle
path explicitly (one lockstep rectangle, no admission/eviction) — the
silent family downgrade it used to hide is gone; unknown families now
fail loudly at scheduler construction.

``--replicas N`` scales the continuous path out to a serving fleet: N
independent scheduler replicas behind a ``ReplicaRouter``
(``serve/fleet.py``), with ``--route {rr,jsq,affinity}`` selecting
round-robin, join-shortest-queue over occupancy gossip, or
prefix-affinity (requires ``--paged --prefix-cache``) routing. The
summary adds the fleet rollup: per-replica routed/admitted counts and
the load-imbalance stat.
"""
from __future__ import annotations

import argparse

import numpy as np


def _decode_names(tokens, d, num_specials: int):
    """Token ids -> event names. vocab may be padded past the dictionary
    alphabet (``max(vocab, 16)``), so clamp instead of raising."""
    names = []
    for t in tokens:
        t = int(t)
        if t < num_specials:
            names.append("<s>")
        elif t - num_specials < d.alphabet_size:
            names.append(d.name_of(t - num_specials))
        else:
            names.append("<unk>")
    return names


def _request_extras(cfg, rng):
    """Per-request encoder inputs for the stubbed frontends (the live
    pipeline carries tokens only): random frame/patch embeddings."""
    if cfg.family == "encdec":
        return dict(frames=rng.standard_normal(
            (cfg.n_frames, cfg.d_model)).astype(np.float32))
    if cfg.family == "vlm":
        return dict(patches=rng.standard_normal(
            (cfg.n_patches, cfg.vision_dim)).astype(np.float32))
    return None


BUCKETS = (16, 32, 64)   # prefill widths the continuous path compiles


def build_model(arch: str, vocab: int, *, smoke: bool = False,
                ckpt: str | None = None):
    """The served model: ``arch``'s FULL (or SMOKE) config over the
    corpus vocabulary, with random weights from seed 0 unless ``ckpt``
    holds a checkpoint. Returns ``(api, params)``."""
    import jax
    from ..configs import full_config, smoke_config
    from ..models import get_model
    from ..train import CheckpointManager, OptConfig, init_opt_state

    cfg = (smoke_config(arch) if smoke else full_config(arch))
    cfg = cfg.with_(vocab_size=max(vocab, 16), max_cache_len=256)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    mgr = CheckpointManager(ckpt) if ckpt else None
    if mgr is not None and mgr.latest_step() is not None:
        state = dict(params=params,
                     opt=init_opt_state(params, OptConfig()))
        state = mgr.restore(state)
        params = jax.tree.map(jax.numpy.asarray, state["params"])
        print(f"restored checkpoint step {mgr.latest_step()}")
    else:
        print("no checkpoint found — serving untrained weights")
    return api, params


def request_stream(seqs, cfg, n_req: int, slots: int, *,
                   max_prompt: int = 33, priority_classes: int = 1):
    """The served request stream: ``(tokens, extra, priority)`` per
    request. Prompts are 4..``max_prompt`` tokens cut from the packed
    session rows (``SessionBatchPipeline``), request ``i`` from row
    ``i % slots`` of step ``i % slots`` — so later requests revisit the
    rows of earlier ones. Seeded: the same corpus gives the same stream."""
    from ..data import PipelineConfig, SessionBatchPipeline
    from ..serve import prompt_lengths

    pipe = SessionBatchPipeline(seqs, PipelineConfig(
        seq_len=64, global_batch=slots))
    rng = np.random.default_rng(0)
    out = []
    for i in range(n_req):
        row = pipe.batch_at(0, i % slots)["tokens"]
        row = np.asarray(row[i % row.shape[0]])
        n = int(rng.integers(4, min(33, max_prompt + 1)))
        n = min(n, int(prompt_lengths(row[None])[0]))  # stay on real toks
        out.append((row[:n], _request_extras(cfg, rng),
                    int(rng.integers(priority_classes))))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="behavior-lm-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-table rows (continuous) / rectangle rows "
                         "(--batch)")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a request stream through the "
                         "continuous-batching scheduler (the default; "
                         "kept as an explicit flag)")
    ap.add_argument("--batch", action="store_true",
                    help="opt into the fixed-batch Server.generate_batch "
                         "oracle path instead of the scheduler")
    ap.add_argument("--requests", type=int, default=0,
                    help="stream size for the continuous path "
                         "(default 3x slots)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (fixed-size blocks shared across "
                         "slots; caps.paged families)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block for --paged")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="session-prefix caching on top of --paged: "
                         "prompts whose leading blocks are already "
                         "resident share them copy-free (refcounted) and "
                         "prefill only the divergent tail")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="optimistic admission factor on top of --paged: "
                         "reserve up to this multiple of pool capacity; "
                         "actual exhaustion mid-decode preempts the "
                         "lowest-priority request (1.0 = honest "
                         "worst-case reservation, the default)")
    ap.add_argument("--priority", type=int, default=1,
                    help="number of priority classes: requests are "
                         "assigned a seeded random class in [0, N); "
                         "higher classes admit first and are preempted "
                         "last (1 = everything priority 0)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a fleet of N independent "
                         "scheduler replicas (each its own slab/prefix "
                         "registry) behind a ReplicaRouter; 1 = the "
                         "single-scheduler path")
    ap.add_argument("--route", choices=("rr", "jsq", "affinity"),
                    default="jsq",
                    help="fleet routing policy for --replicas > 1: "
                         "round-robin, join-shortest-queue on occupancy "
                         "gossip, or prefix-affinity with JSQ spill "
                         "(affinity requires --paged --prefix-cache)")
    args = ap.parse_args()
    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache requires --paged (it shares blocks of "
                 "the paged KV pool)")
    if args.overcommit > 1.0 and not args.paged:
        ap.error("--overcommit > 1.0 requires --paged (only the block "
                 "pool can preempt on exhaustion)")
    if args.overcommit < 1.0:
        ap.error("--overcommit must be >= 1.0")
    if args.priority < 1:
        ap.error("--priority must be >= 1 class")
    if args.batch and args.continuous:
        ap.error("--batch and --continuous are mutually exclusive")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.replicas > 1 and args.batch:
        ap.error("--replicas needs the continuous path (the fleet routes "
                 "an open request stream, not one rectangle)")
    if args.replicas > 1 and args.route == "affinity" \
            and not (args.paged and args.prefix_cache):
        ap.error("--route affinity requires --paged --prefix-cache (it "
                 "scores replicas by resident prefix chains)")

    from ..core import EventDictionary, SessionSequences, sessionize
    from ..data import generate, LogGenConfig, lm_vocab_size, NUM_SPECIALS
    from ..serve import (Server, ServeConfig, ContinuousScheduler,
                         SchedulerConfig, ServeMetrics, ReplicaRouter,
                         FleetConfig)
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    log = generate(LogGenConfig(n_users=400, seed=0))
    b = log.batch
    d = EventDictionary.build(b.table, b.name_id)
    codes = np.asarray(d.encode_ids(b.name_id))
    s = sessionize(b.user_id, b.session_id, b.timestamp, codes,
                   b.ip.astype(np.int64), max_sessions=len(b), max_len=2048)
    seqs = SessionSequences.from_sessionized(s)
    api, params = build_model(args.arch, lm_vocab_size(d.alphabet_size),
                              smoke=args.smoke, ckpt=args.ckpt)
    cfg = api.cfg
    slots = max(args.slots, 1)

    if args.batch:
        from ..data import PipelineConfig, SessionBatchPipeline
        pipe = SessionBatchPipeline(seqs, PipelineConfig(
            seq_len=64, global_batch=slots))
        prompts = pipe.batch_at(0, 0)["tokens"][:slots, :32]
        extra = _request_extras(cfg, np.random.default_rng(0))
        if extra is not None:
            extra = {k: np.stack([v] * prompts.shape[0])
                     for k, v in extra.items()}
        srv = Server(api, params, ServeConfig(
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature))
        gen = srv.generate_batch(prompts, extra)
        for i in range(prompts.shape[0]):
            names = _decode_names(gen[i], d, NUM_SPECIALS)
            print(f"request {i}: " + " -> ".join(n.split(":")[-1]
                                                 for n in names))
        return

    # continuous (default): every family serves through the scheduler;
    # an unknown family raises at construction instead of downgrading.
    # --replicas > 1 serves the same stream through a fleet of
    # independent replicas behind the ReplicaRouter (same surface).
    n_req = args.requests or 3 * slots * args.replicas
    scfg = SchedulerConfig(
        batch=slots, buckets=BUCKETS,
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature, paged=args.paged,
        block_size=args.block_size,
        prefix_cache=args.prefix_cache,
        overcommit=args.overcommit)
    if args.replicas > 1:
        sched = ReplicaRouter(api, params, scfg, FleetConfig(
            replicas=args.replicas, route=args.route))
    else:
        metrics = ServeMetrics()
        sched = ContinuousScheduler(api, params, scfg, metrics=metrics)
    # over-commit caps the prompt so a preempted request's re-prefill
    # (prompt + generated) always fits the largest compiled bucket
    max_prompt = 33 if args.overcommit <= 1.0 else \
        max(4, max(BUCKETS) - args.max_new_tokens + 1)
    rids = [sched.submit(toks, extra=extra, priority=prio)
            for toks, extra, prio in request_stream(
                seqs, cfg, n_req, slots, max_prompt=max_prompt,
                priority_classes=args.priority)]
    outs = sched.run()
    for rid in rids[:slots]:
        names = _decode_names(outs[rid], d, NUM_SPECIALS)
        print(f"request {rid}: "
              + " -> ".join(n.split(":")[-1] for n in names))
    summ = sched.summary() if args.replicas > 1 else metrics.summary()
    print("served {requests} requests, {tokens} tokens, "
          "{tokens_per_sec:.1f} tok/s, p50 latency {p50_latency_s:.3f}s,"
          " p99 {p99_latency_s:.3f}s".format(**summ))
    print("queue wait p50 {p50_queue_wait_s:.4f}s / p99 "
          "{p99_queue_wait_s:.4f}s, admitted TTFT p50 "
          "{p50_ttft_admit_s:.4f}s".format(**summ))
    if args.overcommit > 1.0 or args.priority > 1:
        print(f"over-commit {args.overcommit}x: "
              f"{summ['preemptions']} preemption(s)")
        for prio, ps in sorted(summ["per_priority"].items(), reverse=True):
            print("  class {p}: {requests} requests, {n} preemption(s), "
                  "p99 latency {p99_latency_s:.3f}s, p99 queue wait "
                  "{p99_queue_wait_s:.4f}s".format(
                      p=prio, n=ps["preemptions"], **ps))
    if summ["kv_total_blocks"]:
        print("decode state: peak {kv_live_blocks_peak}/{kv_total_blocks} "
              "{unit} live ({kv_util_peak:.0%}), peak resident "
              "{kv_peak_resident_bytes} bytes".format(
                  unit="blocks" if args.paged else "rows", **summ))
    if args.prefix_cache:
        print("prefix cache: {prefix_hit_rate:.0%} hit rate, "
              "{prefix_blocks_reused} blocks reused, "
              "{prefill_tokens_skipped} prefill tokens skipped, "
              "mean TTFT hit {mean_ttft_hit_s:.4f}s vs miss "
              "{mean_ttft_miss_s:.4f}s".format(**summ))
    if args.replicas > 1:
        f = summ["fleet"]
        print("fleet: {n} replicas, route={route}, routed {routed}, "
              "admitted {adm}, load imbalance {imb:.2f} "
              "(max/mean admitted), {ticks} gossip ticks".format(
                  n=f["replicas"], route=f["route"],
                  routed=f["routed_per_replica"],
                  adm=f["admitted_per_replica"],
                  imb=f["load_imbalance"], ticks=f["gossip_ticks"]))
        for ri, rep in enumerate(sched.replicas):
            print(f"  replica {ri}: jit traces {dict(rep.trace_counts)} "
                  f"(prefills={rep.prefills}, "
                  f"decode_steps={rep.decode_steps})")
    else:
        print(f"jit traces: {dict(sched.trace_counts)} "
              f"(prefills={sched.prefills}, decode_steps="
              f"{sched.decode_steps})")


if __name__ == "__main__":
    main()
