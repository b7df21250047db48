"""Funnel analytics over session sequences (paper §5.3).

``Funnel('signup_page.*', 'signup_submit', ...)``: each stage is a set of
event codes (built by dictionary pattern expansion). A session reaches stage
k when stages 0..k match *in order* (subsequence semantics — the paper
translates the funnel into a regex over the session string; over symbol
tensors the equivalent is a stage-automaton advanced by one ``lax.scan``
pass). Output is the paper's per-stage reach table::

    (0, 490123)   # sessions entering the funnel
    (1, 297071)   # ... completing stage 1
    ...

The Pallas kernel (kernels/funnel_match) accelerates the same automaton with
blocked VMEM tiles; this module is the pure-JAX implementation and oracle
for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dictionary import EventDictionary
from ..core.sequences import SessionSequences


def build_stage_table(stages, alphabet_size: int) -> np.ndarray:
    """(n_stages, alphabet) bool: stage_table[k, c] = code c satisfies stage k."""
    table = np.zeros((len(stages), alphabet_size), bool)
    for k, codes in enumerate(stages):
        table[k, np.asarray(codes, np.int64)] = True
    return table


@functools.partial(jax.jit, static_argnames=("n_stages",))
def _deepest_stage(symbols, mask, stage_table, n_stages):
    """Per-session deepest stage reached (0 = none, n_stages = completed)."""
    s, l = symbols.shape
    alphabet = stage_table.shape[1]
    # Pad stage table with an always-false row so k == n_stages is absorbing.
    table = jnp.concatenate(
        [stage_table, jnp.zeros((1, alphabet), bool)], axis=0)
    sym = jnp.clip(symbols, 0, alphabet - 1)

    def step(k, t):
        advance = table[k, sym[:, t]] & mask[:, t]
        return k + advance.astype(jnp.int32), None

    k0 = jnp.zeros((s,), jnp.int32)
    k, _ = jax.lax.scan(step, k0, jnp.arange(l))
    return k


@functools.partial(jax.jit, static_argnames=("n_stages",))
@jax.named_scope("rollup")
@jax.named_scope("funnel")
def reach_histogram(symbols, mask, stage_table, n_stages):
    """(n_stages,) int32 reach counts — the shard-local half of the
    distributed funnel rollup.

    ``reach[j]`` = sessions whose deepest stage exceeds j (the paper's
    per-stage reach table as a fixed-shape vector, mergeable across shards
    with one ``psum``). Padded session rows have an all-False mask, never
    advance the automaton, and so count toward no stage.
    """
    k = _deepest_stage(symbols, mask, stage_table, n_stages)
    return jnp.sum((k[:, None] > jnp.arange(n_stages)[None, :])
                   .astype(jnp.int32), axis=0)


def funnel_reach(seqs: SessionSequences, stages, alphabet_size: int,
                 deepest_fn=None) -> list[tuple[int, int]]:
    """The paper's funnel output: [(stage, sessions reaching it), ...].

    ``deepest_fn`` lets callers swap in the Pallas kernel implementation.
    """
    table = jnp.asarray(build_stage_table(stages, alphabet_size))
    fn = deepest_fn if deepest_fn is not None else _deepest_stage
    k = np.asarray(fn(jnp.asarray(seqs.symbols), jnp.asarray(seqs.mask()),
                      table, len(stages)))
    return [(j, int((k > j).sum())) for j in range(len(stages))]


def funnel_reach_store(store, stages, alphabet_size: int, *,
                       time_range=None, users=None,
                       deepest_fn=None) -> list[tuple[int, int]]:
    """Funnel reach through the segment store's pruning scan.

    Prunes on the *stage-0* codes: a session that never enters the funnel
    contributes zero to every stage (deepest == 0), so restricting the
    scan to sessions containing a stage-0 event returns reach identical to
    an unpruned scan — segments without any entry event never decode.
    """
    seqs = store.sequences(time_range=time_range, users=users,
                          events=list(np.asarray(stages[0])))
    return funnel_reach(seqs, stages, alphabet_size, deepest_fn=deepest_fn)


def funnel_reach_users(seqs: SessionSequences, stages, alphabet_size: int):
    """Reach counted in unique *users* rather than sessions (§5.3: 'simply a
    matter of applying the unique operator prior to summing')."""
    table = jnp.asarray(build_stage_table(stages, alphabet_size))
    k = np.asarray(_deepest_stage(jnp.asarray(seqs.symbols),
                                  jnp.asarray(seqs.mask()), table, len(stages)))
    users = np.asarray(seqs.user_id)
    out = []
    for j in range(len(stages)):
        out.append((j, int(len(np.unique(users[k > j])))))
    return out


def abandonment(reach: list[tuple[int, int]]) -> list[float]:
    """Per-stage abandonment rate between consecutive stages."""
    out = []
    for (j0, c0), (_, c1) in zip(reach, reach[1:]):
        out.append(1.0 - (c1 / c0) if c0 else 0.0)
    return out


def funnel_from_patterns(seqs: SessionSequences, dictionary: EventDictionary,
                         *patterns: str):
    """The paper's UDF surface: ``Funnel('signup_page.*', ...)`` — stage
    specs as namespace globs, expanded through the dictionary."""
    stages = [dictionary.codes_matching(p) for p in patterns]
    for p, s in zip(patterns, stages):
        if len(s) == 0:
            raise ValueError(f"funnel stage pattern matched no events: {p!r}")
    return funnel_reach(seqs, stages, dictionary.alphabet_size)
