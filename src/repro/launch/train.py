"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Builds the corpus (session sequences from the unified logging pipeline),
constructs the model on the requested mesh, and drives the fault-tolerant
Trainer (NaN guards, async checkpoints, deterministic resume). On this CPU
container use --smoke (reduced config); the same flags target a real pod.
"""
from __future__ import annotations

import argparse
import os

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="behavior-lm-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--users", type=int, default=800)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "ef_int8", "sign"])
    args = ap.parse_args()

    if args.data_axis * args.model_axis > 1:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count="
            f"{args.data_axis * args.model_axis}")

    import jax
    from ..configs import full_config, smoke_config
    from ..core import EventDictionary, SessionSequences, sessionize
    from ..data import (generate, LogGenConfig, SessionBatchPipeline,
                        PipelineConfig, lm_vocab_size)
    from ..dist.compat import use_mesh
    from ..dist.mesh import make_host_mesh
    from ..dist.sharding import ShardingRules, adapt_rules_for_mesh
    from ..models import get_model
    from ..train import OptConfig, Trainer, TrainerConfig
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    log = generate(LogGenConfig(n_users=args.users, seed=0))
    b = log.batch
    d = EventDictionary.build(b.table, b.name_id)
    codes = np.asarray(d.encode_ids(b.name_id))
    s = sessionize(b.user_id, b.session_id, b.timestamp, codes,
                   b.ip.astype(np.int64), max_sessions=len(b), max_len=2048)
    seqs = SessionSequences.from_sessionized(s)
    vocab = lm_vocab_size(d.alphabet_size)
    print(f"corpus: {len(seqs)} sessions, lm vocab {vocab}")

    cfg = (smoke_config(args.arch) if args.smoke else full_config(args.arch))
    cfg = cfg.with_(vocab_size=max(vocab, 16))
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit(f"{args.arch}: modality frontends are stubbed — "
                         f"train via tests/benchmarks, not this LM driver")

    mesh = rules = None
    if args.data_axis * args.model_axis > 1:
        mesh = make_host_mesh(data=args.data_axis, model=args.model_axis)
        rules = adapt_rules_for_mesh(ShardingRules(batch=("data",)), mesh)
        api = get_model(cfg, mesh, rules)
    else:
        api = get_model(cfg)

    pipe = SessionBatchPipeline(seqs, PipelineConfig(
        seq_len=args.seq_len, global_batch=args.global_batch))
    tr = Trainer(api,
                 OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                           total_steps=args.steps,
                           compression=args.compression),
                 TrainerConfig(total_steps=args.steps,
                               checkpoint_every=max(args.steps // 4, 1),
                               log_every=10, checkpoint_dir=args.ckpt),
                 log_fn=lambda st, m: print(
                     f"step {st:5d} loss={m['loss']:.4f} "
                     f"gnorm={m['grad_norm']:.2f} lr={m['lr']:.2e} "
                     f"{m['steps_per_s']:.2f} steps/s", flush=True))

    if mesh is not None:
        with use_mesh(mesh):
            out = tr.run(pipe)
    else:
        out = tr.run(pipe)
    print("final:", out["history"][-1])


if __name__ == "__main__":
    main()
