"""Frequency-ordered client-event dictionary (paper §4.2).

The paper maps each event name to a Unicode code point such that *more
frequent events get smaller code points* — a variable-length code, since
small code points need fewer bytes in UTF-8. We reproduce the bijection
exactly: ``code_of_name[name_id] -> code`` where codes 0..K-1 are assigned by
descending frequency (ties broken by name id for determinism). ``varint.py``
materializes the byte-level representation; in-memory analytics operate on
the int32 codes directly.

The histogram pass is the JAX analogue of the daily Oink job that scans the
client-event logs: a ``segment_sum`` over name ids (and, distributed, a
``psum`` across the data axis — see dist/collectives.py).
"""
from __future__ import annotations

import functools
import json
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.compat import enable_x64
from .events import NameTable


@functools.partial(jax.jit, static_argnames=("num_names",))
def _histogram(name_ids: jax.Array, valid: jax.Array, num_names: int) -> jax.Array:
    # Invalid rows route to an out-of-range drop segment.
    ids = jnp.where(valid, name_ids, num_names)
    ones = jnp.ones_like(ids, dtype=jnp.int64)
    return jax.ops.segment_sum(ones, ids, num_segments=num_names + 1)[:num_names]


def histogram(name_ids, num_names: int, valid=None) -> jax.Array:
    """Event-count histogram over name ids; invalid rows excluded.

    int64 counts (the daily volume is ~1e11 events at paper scale), so the
    pass runs under the scoped x64 context like the rest of the pipeline.
    """
    name_ids = jnp.asarray(name_ids, jnp.int32)
    if valid is None:
        valid = jnp.ones(name_ids.shape, bool)
    with enable_x64():
        return _histogram(name_ids, jnp.asarray(valid, bool), int(num_names))


def assign_codes(counts: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Assign codes by descending count, ties by ascending name id.

    Returns (code_of_name, name_of_code) — inverse permutations of each
    other. Names with zero observed count still receive (large) codes, so
    the mapping is total over the name universe, as in the paper where the
    dictionary covers every event in the daily catalog.
    """
    counts = np.asarray(counts, np.int64)
    k = len(counts)
    # np.lexsort: last key is primary. Primary: -counts; secondary: name id.
    name_of_code = np.lexsort((np.arange(k), -counts)).astype(np.int32)
    code_of_name = np.empty(k, np.int32)
    code_of_name[name_of_code] = np.arange(k, dtype=np.int32)
    return code_of_name, name_of_code


@dataclass
class EventDictionary:
    """Bijection between the event-name universe and frequency-ordered codes."""
    table: NameTable
    counts: np.ndarray          # int64 (K,) — per name id
    code_of_name: np.ndarray    # int32 (K,)
    name_of_code: np.ndarray    # int32 (K,)

    @staticmethod
    def build(table: NameTable, name_ids, valid=None) -> "EventDictionary":
        counts = np.asarray(histogram(name_ids, len(table), valid=valid))
        code_of_name, name_of_code = assign_codes(counts)
        return EventDictionary(table, counts, code_of_name, name_of_code)

    @property
    def alphabet_size(self) -> int:
        return len(self.counts)

    def encode_ids(self, name_ids):
        """name ids -> frequency codes (vectorized gather)."""
        return jnp.asarray(self.code_of_name)[jnp.asarray(name_ids, jnp.int32)]

    def decode_codes(self, codes):
        """frequency codes -> name ids."""
        return jnp.asarray(self.name_of_code)[jnp.asarray(codes, jnp.int32)]

    def code_of(self, name: str) -> int:
        return int(self.code_of_name[self.table.id_of(name)])

    def name_of(self, code: int) -> str:
        return self.table.name_of(int(self.name_of_code[code]))

    def codes_matching(self, pattern: str) -> np.ndarray:
        """Codes of all event names matching a namespace glob pattern.

        This is the dictionary-mediated regex expansion the paper's
        ``CountClientEvents('$EVENTS')`` UDF performs at init.
        """
        return self.code_of_name[self.table.match_ids(pattern)]

    def count_of_code(self, code: int) -> int:
        return int(self.counts[self.name_of_code[code]])

    def save(self, path: str) -> None:
        payload = dict(names=self.table.names, counts=self.counts.tolist())
        with open(path, "w") as f:
            json.dump(payload, f)

    @staticmethod
    def load(path: str) -> "EventDictionary":
        with open(path) as f:
            payload = json.load(f)
        table = NameTable(payload["names"])
        counts = np.asarray(payload["counts"], np.int64)
        code_of_name, name_of_code = assign_codes(counts)
        return EventDictionary(table, counts, code_of_name, name_of_code)

    def verify(self) -> None:
        """Invariants: bijection + monotone frequency ordering."""
        k = self.alphabet_size
        assert sorted(self.code_of_name.tolist()) == list(range(k))
        assert np.array_equal(self.code_of_name[self.name_of_code], np.arange(k))
        ordered = self.counts[self.name_of_code]
        assert np.all(ordered[:-1] >= ordered[1:]), "codes not frequency-ordered"
