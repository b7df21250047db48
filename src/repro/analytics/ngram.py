"""n-gram language models over session sequences (paper §5.4).

Sessions are symbol sequences over a finite alphabet, so NLP machinery
applies directly. We reproduce the paper's program: n-gram models with the
Markov assumption, evaluated by cross entropy / perplexity to quantify the
"temporal signal" in user behaviour.

TPU-native counting: windows are packed into integer keys
(``sum code_j * alphabet^(n-1-j)``), sorted, and run-length encoded — the
sort-based group-by again, no host dicts in the hot path. Lookup at eval
time is a vectorized ``searchsorted`` against the sorted key table.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.compat import enable_x64
from ..core.sequences import SessionSequences


@functools.partial(jax.jit, static_argnames=("n", "alphabet_size"))
def _window_keys(symbols, mask, n, alphabet_size):
    """Pack all length-n windows into int64 keys; invalid windows -> -1."""
    s, l = symbols.shape
    sym = jnp.clip(symbols, 0, alphabet_size - 1).astype(jnp.int64)
    key = jnp.zeros((s, l - n + 1), jnp.int64)
    ok = jnp.ones((s, l - n + 1), bool)
    base = jnp.int64(alphabet_size)
    for j in range(n):
        key = key * base + jax.lax.dynamic_slice_in_dim(sym, j, l - n + 1, axis=1)
        ok = ok & jax.lax.dynamic_slice_in_dim(mask, j, l - n + 1, axis=1)
    return jnp.where(ok, key, jnp.int64(-1))


@jax.jit
def _sorted_unique_counts(keys_flat):
    """Sort keys; return (sorted keys, run-start flags, per-key counts at
    run starts). Invalid (-1) keys sort first and are excluded by callers."""
    ks = jnp.sort(keys_flat)
    n = ks.shape[0]
    idx = jnp.arange(n)
    is_start = (idx == 0) | (ks != jnp.roll(ks, 1))
    # run id per element, then counts per run scattered back to run starts
    run_id = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    counts = jax.ops.segment_sum(jnp.ones(n, jnp.int64), run_id, num_segments=n)
    return ks, is_start, counts[run_id]


@functools.partial(jax.jit, static_argnames=("n", "alphabet_size"))
@jax.named_scope("rollup")
@jax.named_scope("ngram")
def dense_ngram_counts(symbols, mask, n, alphabet_size):
    """Dense (alphabet_size**n,) count vector of order-n grams — the
    shard-local half of the distributed rollup.

    Unlike ``ngram_counts`` (sparse sort + RLE, host-side), this returns a
    fixed-shape dense histogram so a mesh of shards can merge with one
    ``psum`` — the ``make_distributed_histogram`` pattern applied to packed
    window keys. Intended for the small orders the paper evaluates (n <= 3);
    the table is materialized, so alphabet_size**n must fit in memory.
    ``mask`` is the per-position validity mask (rows past a session's stored
    length, padded session rows, and invalid shard rows are all False).
    """
    size = alphabet_size ** n
    assert size < 2 ** 31, (
        f"dense n-gram table has {size} cells; packed keys are bucketed as "
        "int32, so alphabet_size**n must stay below 2**31 — use the sparse "
        "ngram_counts path for higher orders")
    if symbols.shape[1] < n:
        return jnp.zeros(size, jnp.int32)
    keys = _window_keys(symbols, mask, n, alphabet_size)
    k = jnp.where(keys < 0, size, keys).reshape(-1)  # invalid -> drop bucket
    return jax.ops.segment_sum(
        jnp.ones_like(k, jnp.int32), k.astype(jnp.int32),
        num_segments=size + 1)[:size]


def ngram_counts(seqs: SessionSequences, n: int, alphabet_size: int):
    """(unique_keys int64 (U,), counts int64 (U,)) for all order-n grams."""
    if seqs.max_len < n:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    with enable_x64():
        keys = _window_keys(jnp.asarray(seqs.symbols), jnp.asarray(seqs.mask()),
                            int(n), int(alphabet_size))
        ks, is_start, cnts = _sorted_unique_counts(keys.reshape(-1))
    ks = np.asarray(ks)
    sel = np.asarray(is_start) & (ks >= 0)
    return ks[sel], np.asarray(cnts)[sel]


def ngram_counts_store(store, n: int, alphabet_size: int, *,
                       time_range=None, users=None):
    """N-gram table read through the segment store (no code pruning —
    every session contributes windows; time/user filters still prune)."""
    seqs = store.sequences(time_range=time_range, users=users)
    return ngram_counts(seqs, n, alphabet_size)


def unpack_key(key: int, n: int, alphabet_size: int) -> tuple[int, ...]:
    out = []
    for _ in range(n):
        out.append(int(key % alphabet_size))
        key //= alphabet_size
    return tuple(reversed(out))


@dataclass
class _OrderTable:
    keys: np.ndarray    # sorted unique int64
    counts: np.ndarray  # int64
    total: int

    def lookup(self, query: np.ndarray) -> np.ndarray:
        """Vectorized exact-count lookup (0 for unseen)."""
        pos = np.searchsorted(self.keys, query)
        pos = np.clip(pos, 0, max(len(self.keys) - 1, 0))
        if len(self.keys) == 0:
            return np.zeros(len(query), np.int64)
        hit = self.keys[pos] == query
        return np.where(hit, self.counts[pos], 0)


@dataclass
class NGramLM:
    """Jelinek-Mercer interpolated n-gram model (MLE orders interpolated
    down to uniform): P(w|h) = lam * c(hw)/c(h) + (1-lam) * P_{n-1}(w|h')."""
    n: int
    alphabet_size: int
    tables: list[_OrderTable]   # order 1..n
    lam: float = 0.8

    @staticmethod
    def fit(seqs: SessionSequences, n: int, alphabet_size: int,
            lam: float = 0.8) -> "NGramLM":
        tables = []
        for order in range(1, n + 1):
            keys, counts = ngram_counts(seqs, order, alphabet_size)
            tables.append(_OrderTable(keys, counts, int(counts.sum())))
        return NGramLM(n, alphabet_size, tables, lam)

    def _cond_prob(self, keys_by_order: dict[int, np.ndarray],
                   order: int) -> np.ndarray:
        """P(w|h) for every query position at a given order (vectorized)."""
        uniform = np.full(len(keys_by_order[1]), 1.0 / self.alphabet_size)
        if order == 0:
            return uniform
        gram = self.tables[order - 1].lookup(keys_by_order[order])
        if order == 1:
            hist_count = np.full(len(gram), self.tables[0].total, np.int64)
        else:
            hist = keys_by_order[order] // self.alphabet_size
            hist_count = self.tables[order - 2].lookup(hist)
        mle = np.where(hist_count > 0, gram / np.maximum(hist_count, 1), 0.0)
        lower = self._cond_prob(keys_by_order, order - 1)
        lam = np.where(hist_count > 0, self.lam, 0.0)
        return lam * mle + (1.0 - lam) * lower

    def cross_entropy(self, seqs: SessionSequences) -> float:
        """Bits per symbol under the model (predicting each symbol from its
        n-1 predecessors; the first n-1 symbols of a session use shorter
        histories)."""
        total_bits = 0.0
        total_syms = 0
        # Gather per-position keys for each order in one vectorized pass.
        sym = seqs.symbols
        mask = seqs.mask()
        s, l = sym.shape
        for start_order in range(1, self.n + 1):
            if l < start_order:
                continue
            if start_order < self.n:
                cols = [start_order - 1]  # only the position with short history
            else:
                cols = list(range(self.n - 1, l))
            col_idx = np.asarray(cols)
            keys_by_order = {}
            for order in range(1, start_order + 1):
                key = np.zeros((s, len(cols)), np.int64)
                for j in range(order):
                    key = key * self.alphabet_size + np.clip(
                        sym[:, col_idx - (order - 1) + j], 0,
                        self.alphabet_size - 1)
                keys_by_order[order] = key.reshape(-1)
            valid = mask[:, col_idx].reshape(-1)
            p = self._cond_prob(keys_by_order, start_order)
            p = np.maximum(p, 1e-12)
            total_bits += float(-(np.log2(p) * valid).sum())
            total_syms += int(valid.sum())
        return total_bits / max(total_syms, 1)

    def perplexity(self, seqs: SessionSequences) -> float:
        return float(2.0 ** self.cross_entropy(seqs))
