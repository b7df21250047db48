"""Serving loop: batched prefill + incremental decode.

``Server.generate`` is the fixed-batch compatibility surface, and for
EVERY family it is a thin wrapper over the continuous-batching
``ContinuousScheduler`` (scheduler.py): each row is trimmed to its real
length, admitted as one request (per-row encoder extras — frames/patches
— ride along), and decoded with per-row positions — so right-padded
prompts decode bit-identically to their trimmed copies. The family
rejection branches are gone: ssm/hybrid serve through ``RecurrentState``
/ ``HybridState`` (ragged prefill freezes the recurrence across pads) and
encdec/vlm through ``CrossAttnState`` (see ``serve/cache.py``).

``Server.generate_batch`` is the explicit fixed-batch oracle — one
prefill over the whole rectangle, lockstep decode to the longest row —
kept as the independent reference the family-matrix equivalence tests
(and ``launch/serve.py --batch``) compare the scheduler against, with the
decode-loop correctness fixes:

* the RNG key is split *before* the first post-prefill sample, so the
  prefill-token draw and later decode draws are independent streams;
* the loop never launches a decode whose logits would be discarded, and
  short-circuits as soon as every row has emitted EOS;
* rows that hit EOS stay frozen at EOS.
"""
from __future__ import annotations

import collections
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..models.registry import ModelApi
from ..data.pipeline import PAD_ID, EOS_ID
from .scheduler import ContinuousScheduler, SchedulerConfig


@dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0     # 0 = greedy
    seed: int = 0
    # paged KV cache (caps.paged families): fixed-size blocks shared
    # across slots instead of a max_cache_len stripe per row — serve/paged
    paged: bool = False
    block_size: int = 16
    num_blocks: int | None = None
    # session-prefix caching (requires paged): refcounted block sharing +
    # tail-only prefill for prompts with resident prefixes
    prefix_cache: bool = False
    # optimistic admission (requires paged): reserve up to this factor of
    # pool capacity; exhaustion mid-decode preempts the lowest-priority
    # victim (see serve/scheduler.py). 1.0 = honest reservation.
    overcommit: float = 1.0
    # run BlockPool.check_invariants after every evict/preempt
    debug: bool = False
    # cap on cached (batch, bucket) schedulers: each pins its compiled
    # prefill/decode fns AND its decode-state slab on device, so a
    # long-lived server seeing many shapes must not grow without bound —
    # least-recently-used shapes are evicted (loudly, via warnings.warn)
    max_schedulers: int = 8


def prompt_lengths(prompts: np.ndarray) -> np.ndarray:
    """Per-row real lengths of right-PAD-padded prompts: one past the last
    non-PAD token, clamped to >= 1 (an all-PAD row serves a length-1 pad
    prompt rather than an illegal empty one)."""
    prompts = np.asarray(prompts)
    not_pad = prompts != PAD_ID
    lens = prompts.shape[1] - np.argmax(not_pad[:, ::-1], axis=1)
    lens = np.where(not_pad.any(axis=1), lens, 1)
    return lens.astype(np.int32)


class Server:
    def __init__(self, api: ModelApi, params, scfg: ServeConfig, mesh=None):
        self.api = api
        self.params = params
        self.scfg = scfg
        self.mesh = mesh
        self._prefill = jax.jit(lambda p, b: api.prefill(p, b))
        self._decode = jax.jit(
            lambda p, tok, st, i: api.decode_step(p, tok, st, i))
        self.decode_calls = 0        # batch-path decode_step invocations
        # LRU over (batch, bucket) shapes, capped at scfg.max_schedulers
        self._schedulers: collections.OrderedDict[tuple,
                                                  ContinuousScheduler] = \
            collections.OrderedDict()
        self.scheduler_evictions = 0

    def _sample(self, logits, key):
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)

    def _bucket_width(self, prompt_len: int) -> int:
        """Round the prefill width up a power-of-two ladder so generate()
        calls with nearby prompt widths share one compiled scheduler (rows
        are trimmed to real length before submit, so the width is only a
        compilation key). Position-bounded families fall back to the exact
        width when the rounded bucket would overflow the KV cache but the
        prompt itself fits; recurrent state has no such bound."""
        b = 8
        while b < prompt_len:
            b *= 2
        if not self.api.caps.positioned:
            return b
        cap = self.api.cfg.max_cache_len - self.scfg.max_new_tokens + 1
        return b if b <= cap else prompt_len

    def scheduler_for(self, batch: int, bucket: int) -> ContinuousScheduler:
        """The cached continuous scheduler for a (slots, bucket) shape —
        cached so repeated generate() calls reuse the compiled fns.

        The cache is a true LRU capped at ``scfg.max_schedulers``: every
        cached scheduler pins compiled executables and a device slab, so
        a long-lived fleet process cycling through many shapes would
        otherwise accrete them forever. Evicting the coldest shape is
        safe — ``generate`` drains its scheduler synchronously, so a
        cached scheduler is never mid-request — but it throws away that
        shape's compilation, so the eviction is *loud* (a
        ``warnings.warn`` naming the shape): seeing it repeatedly means
        ``max_schedulers`` is too small for the workload's shape mix.
        """
        key = (batch, bucket)
        sched = self._schedulers.get(key)
        if sched is not None:
            self._schedulers.move_to_end(key)
            return sched
        while len(self._schedulers) >= max(1, self.scfg.max_schedulers):
            old_key, _ = self._schedulers.popitem(last=False)
            self.scheduler_evictions += 1
            warnings.warn(
                f"Server scheduler cache full ({self.scfg.max_schedulers} "
                f"shapes): evicting least-recently-used shape "
                f"(batch, bucket)={old_key} and its compiled fns — raise "
                "ServeConfig.max_schedulers if this recurs",
                RuntimeWarning, stacklevel=2)
        sched = ContinuousScheduler(
            self.api, self.params,
            SchedulerConfig(batch=batch, buckets=(bucket,),
                            max_new_tokens=self.scfg.max_new_tokens,
                            temperature=self.scfg.temperature,
                            seed=self.scfg.seed,
                            paged=self.scfg.paged,
                            block_size=self.scfg.block_size,
                            num_blocks=self.scfg.num_blocks,
                            prefix_cache=self.scfg.prefix_cache,
                            overcommit=self.scfg.overcommit,
                            debug=self.scfg.debug),
            mesh=self.mesh)
        self._schedulers[key] = sched
        return sched

    def generate(self, prompts: np.ndarray, extra: dict | None = None):
        """prompts: (B, L) int32, PAD-padded on the right. Returns
        (B, max_new_tokens) tokens; rows freeze at EOS once emitted.

        Every family routes through the continuous scheduler: rows are
        trimmed to their real lengths and admitted as one request each
        (``extra`` values are sliced per row — encdec frames, vlm
        patches), so a padded prompt decodes identically to its trimmed
        copy.
        """
        prompts = np.asarray(prompts, np.int32)
        b, l = prompts.shape
        lens = prompt_lengths(prompts)
        sched = self.scheduler_for(b, self._bucket_width(int(lens.max())))
        rids = []
        for i in range(b):
            row_extra = None
            if extra:
                row_extra = {k: np.asarray(v)[i] for k, v in extra.items()}
            rids.append(sched.submit(
                prompts[i, :lens[i]],
                max_new_tokens=self.scfg.max_new_tokens, extra=row_extra))
        outs = sched.run()
        n = self.scfg.max_new_tokens
        rows = []
        for rid in rids:
            toks = outs[rid][:n]
            rows.append(np.concatenate(
                [toks, np.full(n - len(toks), EOS_ID, np.int32)]))
        return np.stack(rows, axis=0)

    def generate_batch(self, prompts: np.ndarray, extra: dict | None = None):
        """Fixed-batch oracle: one ragged prefill over the whole (B, L)
        rectangle, lockstep decode to the longest row. The independent
        reference path the scheduler is asserted bit-equal against."""
        prompts = np.asarray(prompts, np.int32)
        b, l = prompts.shape
        batch = dict(tokens=jnp.asarray(prompts, jnp.int32),
                     lengths=jnp.asarray(prompt_lengths(prompts)))
        if extra:
            batch.update({k: jnp.asarray(v) for k, v in extra.items()})
        logits, state, index = self._prefill(self.params, batch)
        key, sub = jax.random.split(jax.random.PRNGKey(self.scfg.seed))
        out = []
        tok = self._sample(logits, sub)
        done = jnp.zeros((b,), bool)
        n = self.scfg.max_new_tokens
        for t in range(n):
            out.append(np.asarray(tok))
            done = done | (tok == EOS_ID)
            if t == n - 1 or bool(done.all()):
                break      # never launch a decode whose logits are unused
            key, sub = jax.random.split(key)
            logits, state = self._decode(self.params, tok, state, index + t)
            self.decode_calls += 1
            tok = jnp.where(done, EOS_ID, self._sample(logits, sub))
        while len(out) < n:          # EOS-frozen tail after short-circuit
            out.append(np.full((b,), EOS_ID, np.int32))
        return np.stack(out, axis=1)

    def score_batch(self, prompts: np.ndarray, tokens: np.ndarray):
        """The fixed-batch path teacher-forced on ``tokens`` (B, T): the
        (B, T, vocab) float32 logits that token ``t`` of each row is drawn
        from, given the prompt and that row's tokens before ``t`` — the
        same prefill and decode programs ``generate_batch`` runs."""
        prompts = np.asarray(prompts, np.int32)
        tokens = np.asarray(tokens, np.int32)
        logits, state, index = self._prefill(self.params, dict(
            tokens=jnp.asarray(prompts),
            lengths=jnp.asarray(prompt_lengths(prompts))))
        out = [np.asarray(logits, np.float32)]
        for t in range(tokens.shape[1] - 1):
            logits, state = self._decode(self.params, jnp.asarray(tokens[:, t]),
                                         state, index + t)
            out.append(np.asarray(logits, np.float32))
        return np.stack(out, axis=1)
