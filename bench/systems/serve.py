"""The served behaviour LM under test: ``ContinuousScheduler`` (paged KV
pool, prefix cache, greedy) over the dense decoder of
``repro.configs.paper.FULL`` with the configuration's sizes, on one chip,
serving requests made from a day's sessions (``requests.py``). The
weights are the benchmark's, drawn from the seed
(``reference/dense_lm.py``), in the program's layout and type, with
EOS's row of the tied embedding zero (``weights``).

A step tops the scheduler's queue up to ``queue_factor`` times its slots
with the day's next requests and runs one ``ContinuousScheduler.step``
(admissions, one decode step over the slot table, evictions); it returns
the tokens the step emitted: each admission's first token and one token
per decoding slot. Every request runs its whole budget (``weights``).
``warm`` makes the scheduler compile every prefill, insert and decode
program the traffic can reach (``warm_pairs``), then
fills the slots with the traffic's first requests a few at a time, over
as many steps as a request's budget, so that in the window requests end,
and are admitted, a few in every step: a backlog in its steady state,
not waves of admissions as wide as the slot table.
``finish`` runs every request submitted to its end.

``compared``: ``token_logit_shortfall_max``, the widest gap, over every
token served to a sample of the requests that finished in the window
(drawn from the seed, with the longest in it), between the plain
reference's best logit and its logit of the served token, teacher-forced;
``requests_unfinished``, submitted requests that ``finish`` did not see
to their end; ``window_compiles``. With ``control`` the token that the
float8 reference puts first is scored in place of each served token.
"""
from __future__ import annotations

import functools
import sys
import time

import numpy as np

from bench import model_flops, requests
from bench.reference import dense_lm

SPAN = "serve_step"
MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "tie_embeddings",
              "rope_theta", "norm_eps", "dtype", "param_dtype",
              "max_cache_len")
WARM_ROUNDS = 8


def weights(key, m: dict) -> dict:
    """``dense_lm.init``'s weights with EOS's row of the tied embedding
    zero: EOS's logit is then 0 at every position, below the best of the
    other tokens' random logits, so greedy never ends a request before
    its budget. Random weights would otherwise set, seed by seed, how
    often a request ends early, and with it the work of a window."""
    p = dense_lm.init(key, m)
    return dict(p, embed=p["embed"].at[requests.EOS_ID].set(0.0))


def _bucket(n: int, buckets) -> int:
    return min(b for b in buckets if b >= n)


def warm_shapes(buckets, block: int, cap: int) -> dict:
    """Every prefill the scheduler can run for a prompt of at most ``cap``
    tokens, as ``{(cache_len, bucket, prefix): (n, start, cow)}``: one
    prompt of ``n`` tokens whose prefill starts at ``start`` for each. A
    prompt with no resident prefix prefills its bucket from 0; one whose
    first ``m`` full blocks are resident prefills from ``m * block``; one
    whose blocks before its last token's are resident, with a resident
    block that extends them over its tokens but the last, copies that
    block and prefills its last token alone (``cow``). The prefill's
    cache covers ``start + bucket`` positions in whole blocks."""
    shapes = {}
    for n in range(2, cap + 1):
        limit = (n - 1) // block
        plans = [(m * block, False) for m in range(limit + 1)]
        if limit * block < n - 1:
            plans.append((n - 1, True))
        for start, cow in plans:
            b = _bucket(n - start, buckets)
            cover = -(-(start + b) // block) * block
            shapes.setdefault((cover, b, start > 0), (n, start, cow))
    return shapes


def warm_pairs(shapes, block: int, vocab: int, rng) -> list[tuple]:
    """For each of ``warm_shapes``, the prompts to submit one after the
    other so the scheduler runs that prefill: a prompt whose blocks are
    resident when the next is admitted, then the prompt that shares
    them. Tokens are drawn afresh for each, so no two share a block."""
    def other(t):                     # a token other than ``t``
        return (t - 4 + 1) % (vocab - 4) + 4

    pairs = []
    for n, start, cow in shapes.values():
        if start == 0:
            pairs.append((rng.integers(4, vocab, n),))
            continue
        base = rng.integers(4, vocab, max(n, (start // block + 1) * block))
        if cow:
            second = np.r_[base[:n - 1], other(base[n - 1])]
        else:
            tail = rng.integers(4, vocab, n - start)
            tail[0] = other(base[start])
            second = np.r_[base[:start], tail]
        pairs.append((base, second))
    return pairs


class System:
    span = SPAN
    sub_spans = ()

    def __init__(self, cfg: dict, traffic: dict, reqs: requests.Requests,
                 seed: int, mesh):
        import jax
        from repro.configs.paper import FULL
        from repro.models import get_model
        from repro.serve import (ContinuousScheduler, SchedulerConfig,
                                 ServeMetrics)

        if mesh.devices.size != 1:
            raise ValueError("the served model runs on one chip")
        self.cfg, self.traffic, self.reqs, self.seed = (cfg, traffic, reqs,
                                                        seed)
        self.m = {k: cfg[k] for k in MODEL_KEYS}
        self.api = get_model(FULL.with_(**self.m))
        self.params = jax.jit(functools.partial(weights, m=self.m))(
            dense_lm.key_of(seed))
        want = jax.eval_shape(self.api.init, jax.random.PRNGKey(0))
        if jax.tree.structure(want) != jax.tree.structure(self.params) or \
                any((a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                    jax.tree.leaves(want), jax.tree.leaves(self.params))):
            raise ValueError("the benchmark's weights do not have the "
                             "program's layout")
        self.budget = traffic["max_new_tokens"]
        self.buckets = tuple(cfg["buckets"])
        if max(self.buckets) < traffic["prompt_cap"]:
            raise ValueError("the prefill buckets do not cover the prompt "
                             "cap")
        self.sched = ContinuousScheduler(self.api, self.params,
                                         SchedulerConfig(
            batch=cfg["slots"], buckets=self.buckets,
            max_new_tokens=self.budget, paged=True,
            block_size=cfg["block_size"], num_blocks=cfg["num_blocks"],
            prefix_cache=True), metrics=ServeMetrics())
        self.unit = cfg["slots"]
        self.queue = traffic["queue_factor"] * cfg["slots"]
        self.rid_of = np.zeros(len(reqs), np.int64)   # request -> its rid
        self.submitted = 0
        self.blocks = []              # (live, live + reserved) a window step

    @classmethod
    def from_seed(cls, cfg: dict, traffic: dict, seed: int,
                  mesh) -> "System":
        t0 = time.perf_counter()
        reqs = requests.generate(cfg, traffic, seed)
        print(f"serve: {len(reqs)} requests in "
              f"{time.perf_counter() - t0:.2f} s", file=sys.stderr)
        return cls(cfg, traffic, reqs, seed, mesh)

    def _done(self, out) -> bool:
        return len(out) >= self.budget or (len(out) > 0 and int(out[-1])
                                           == requests.EOS_ID)

    def _state(self):
        """Tokens emitted so far by each request submitted, and whether
        it is done."""
        outs = self.sched.outputs
        got = [outs.get(int(r), ()) for r in self.rid_of[:self.submitted]]
        return (np.array([len(o) for o in got], np.int64),
                np.array([self._done(o) for o in got], bool))

    # -- set-up and the window ---------------------------------------------

    def _say(self, what: str, t0: float) -> None:
        print(f"serve: {what} in {time.perf_counter() - t0:.2f} s",
              file=sys.stderr)

    def warm(self) -> None:
        t0 = time.perf_counter()
        rng = np.random.default_rng([self.seed, 2])
        shapes = warm_shapes(self.buckets, self.cfg["block_size"],
                             self.traffic["prompt_cap"])
        pairs = warm_pairs(shapes, self.cfg["block_size"],
                           self.m["vocab_size"], rng)
        for _ in range(WARM_ROUNDS):
            rids = [[self.sched.submit(p, max_new_tokens=2) for p in pair]
                    for pair in pairs]
            outs = self.sched.run()
            # a prompt done at admission (its first token EOS) is never
            # inserted and leaves no blocks to share: draw that pair again
            keys = [k for k, r in zip(shapes, rids)
                    if min(len(outs[x]) for x in r) < 2]
            if not keys:
                break
            shapes = {k: shapes[k] for k in keys}
            pairs = warm_pairs(shapes, self.cfg["block_size"],
                               self.m["vocab_size"], rng)
        self._say("warm prefills", t0)
        t0 = time.perf_counter()
        per_step = -(-self.unit // self.budget)
        for _ in range(self.budget):
            for _ in range(per_step):
                self._submit_next()
            self.sched.step()
        self._say(f"{self.budget} steps to fill the slots", t0)
        self.emitted_start, self.done_start = self._state()
        self.counts_start = (self.sched.decode_steps, self.sched.prefills)

    def _submit_next(self) -> None:
        i = self.submitted
        if i == len(self.reqs):
            raise RuntimeError(f"the day's {i} requests ran out: the "
                               "configuration needs a larger day")
        self.rid_of[i] = self.sched.submit(self.reqs.prompt(i))
        self.submitted += 1

    def _feed(self) -> None:
        while self.sched.num_pending < self.queue:
            self._submit_next()

    def step(self, n: int) -> int:
        self._feed()
        before = self.sched.prefills
        emitted = self.sched.step()
        pool = self.sched.pool
        self.blocks.append((pool.live_blocks,
                            pool.capacity - pool.available))
        return len(emitted) + self.sched.prefills - before

    def finish(self) -> None:
        t0 = time.perf_counter()
        self.metrics = self.sched.metrics
        self.emitted_end, self.done_end = self._state()
        decodes, prefills = (self.sched.decode_steps - self.counts_start[0],
                             self.sched.prefills - self.counts_start[1])
        *_, admitted = self._window()
        timing = self.metrics.requests
        prefill_s = sum(timing[int(r)].first_token - timing[int(r)].admit
                        for r in self.rid_of[:self.submitted][admitted])
        print(f"serve: the window ran {decodes} decode steps and "
              f"{prefills} prefills; admission to first token took "
              f"{prefill_s:.3f} s in all", file=sys.stderr)
        idx = np.flatnonzero(admitted)
        live, held = np.mean(self.blocks, axis=0) / self.sched.pool.capacity
        print(f"serve: the window's {len(idx)} admitted prompts: median "
              f"{np.median(self.reqs.length[idx]):.1f} tokens, "
              f"{self.reqs.earlier[idx].mean():.3f} with an earlier "
              "session; pool blocks, mean over its steps: live "
              f"{live:.3f}, live or reserved {held:.3f}", file=sys.stderr)
        queued, active = self.sched.num_pending, self.sched.num_active
        drained = self.sched.run()
        self._say(f"drained {queued} queued and {active} active", t0)
        self.outputs = [np.asarray(drained[int(r)]) for r in
                        self.rid_of[:self.submitted]]

    # -- after the window -------------------------------------------------

    def _window(self):
        """Per request submitted: the tokens emitted before the window
        and by its end, and whether it was admitted in the window."""
        e0 = np.zeros(self.submitted, np.int64)
        e0[:len(self.emitted_start)] = self.emitted_start
        return e0, self.emitted_end, (e0 == 0) & (self.emitted_end > 0)

    def compared(self, window_compiles: int, control: bool) -> dict:
        e0, e1, _ = self._window()
        d0 = np.zeros(self.submitted, bool)
        d0[:len(self.done_start)] = self.done_start
        finished = np.flatnonzero(self.done_end & ~d0)
        unfinished = sum(not self._done(o) for o in self.outputs)
        rng = np.random.default_rng([self.seed, 3])
        k = min(self.cfg["check_requests"], len(finished))
        longest = finished[np.argmax(self.reqs.length[finished] + e1[
            finished])] if len(finished) else None
        sample = sorted(set(rng.choice(finished, k, replace=False).tolist())
                        | ({int(longest)} if longest is not None else set()))
        self.sched = None               # the pool and the program's state
        t0 = time.perf_counter()
        gaps = dense_lm.shortfall(
            self.params, [(self.reqs.prompt(i), self.outputs[i])
                          for i in sample],
            self.m, length=self.m["max_cache_len"],
            rows=self.cfg["check_rows"], quantize=control)
        self._say(f"reference over {len(sample)} requests, "
                  f"{sum(len(self.outputs[i]) for i in sample)} served "
                  "tokens", t0)
        values = dict(token_logit_shortfall_max=float(gaps.max())
                      if len(gaps) else float("inf"),
                      requests_unfinished=int(unfinished),
                      window_compiles=int(window_compiles))
        return {n: dict(value=v, limit=self.cfg["limits"][n])
                for n, v in values.items()}

    def tally(self, window: dict) -> tuple[int, int]:
        """Requests admitted in the window, and those of them that never
        finished."""
        *_, admitted = self._window()
        return (int(admitted.sum()), int(sum(
            not self._done(self.outputs[i])
            for i in np.flatnonzero(admitted))))

    def ctx(self) -> dict:
        """``window_flops``: the model operations (``model_flops.py``) of
        every prompt token of the requests admitted in the window and of
        every token emitted in it; ``window_tokens``: those tokens
        emitted; ``prompt_tokens`` of the requests admitted in the window,
        and ``prefill_skipped``, those of them the scheduler's prefix
        cache served from resident blocks (its ``ServeMetrics``)."""
        e0, e1, admitted = self._window()
        n = self.reqs.length[:self.submitted]
        flops = (model_flops.positions(self.m, np.zeros(int(admitted.sum())),
                                       n[admitted])
                 + model_flops.positions(self.m, n + e0, n + e1))
        timing = self.metrics.requests
        skipped = sum(timing[int(r)].prefill_tokens_skipped
                      for r in self.rid_of[:self.submitted][admitted])
        return dict(window_flops=flops, window_tokens=int((e1 - e0).sum()),
                    prompt_tokens=int(n[admitted].sum()),
                    prefill_skipped=int(skipped))
