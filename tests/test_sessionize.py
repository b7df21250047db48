import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sessionize, DEFAULT_GAP_MS, PAD_CODE
from repro.core.oracle import sessionize_oracle


def _events(draw_users, draw_sessions, n, rng):
    user = rng.integers(0, draw_users, n).astype(np.int64) * 1_000_003
    sess = rng.integers(0, draw_sessions, n).astype(np.int64)
    ts = (1_700_000_000_000 + rng.integers(0, 4 * 3600 * 1000, n)).astype(np.int64)
    code = rng.integers(0, 50, n).astype(np.int32)
    ip = rng.integers(0, 2**31, n).astype(np.int64)
    return user, sess, ts, code, ip


def _check_against_oracle(user, sess, ts, code, ip, gap_ms=DEFAULT_GAP_MS,
                          max_len=None):
    n = len(user)
    max_len = max_len or n
    got = sessionize(user, sess, ts, code, ip, gap_ms=gap_ms,
                     max_sessions=n, max_len=max_len).trimmed()
    want = sessionize_oracle(user, sess, ts, code, ip, gap_ms=gap_ms)
    assert int(got.num_sessions) == len(want)
    for i, o in enumerate(want):
        assert int(got.user_id[i]) == o["user_id"]
        assert int(got.session_id[i]) == o["session_id"]
        assert int(got.length[i]) == o["length"]
        assert int(got.duration_s[i]) == o["duration_s"]
        assert int(got.ip[i]) == o["ip"]
        assert int(got.start_ts[i]) == o["start_ts"]
        stored = got.symbols[i][got.symbols[i] != PAD_CODE]
        # ties in timestamps permit any order within equal-ts runs
        assert sorted(stored.tolist()) == sorted(o["symbols"][:max_len])
    return got, want


@given(st.integers(0, 2**31 - 1), st.integers(10, 300))
@settings(max_examples=25, deadline=None)
def test_matches_oracle_random(seed, n):
    rng = np.random.default_rng(seed)
    _check_against_oracle(*_events(8, 3, n, rng))


def test_event_conservation():
    rng = np.random.default_rng(0)
    user, sess, ts, code, ip = _events(5, 2, 500, rng)
    got = sessionize(user, sess, ts, code, ip, max_sessions=500, max_len=500)
    assert int(got.length.sum()) == 500          # every event in one session
    assert int(got.num_events) == 500


def test_gap_splits_sessions():
    # one user, one cookie, two bursts separated by > 30 min
    user = np.zeros(6, np.int64)
    sess = np.zeros(6, np.int64)
    ts = np.array([0, 1000, 2000, 2000 + DEFAULT_GAP_MS + 1,
                   2000 + DEFAULT_GAP_MS + 2000,
                   2000 + DEFAULT_GAP_MS + 3000], np.int64)
    code = np.arange(6, dtype=np.int32)
    got, want = _check_against_oracle(user, sess, ts, code,
                                      np.zeros(6, np.int64))
    assert int(got.num_sessions) == 2
    assert got.length.tolist() == [3, 3]


def test_gap_exactly_30min_does_not_split():
    user = np.zeros(2, np.int64)
    sess = np.zeros(2, np.int64)
    ts = np.array([0, DEFAULT_GAP_MS], np.int64)
    got = sessionize(user, sess, ts, np.zeros(2, np.int32),
                     max_sessions=2, max_len=2)
    assert int(got.num_sessions) == 1


def test_invalid_rows_dropped():
    rng = np.random.default_rng(1)
    user, sess, ts, code, ip = _events(4, 2, 100, rng)
    valid = rng.random(100) < 0.7
    got = sessionize(user, sess, ts, code, ip, valid=valid,
                     max_sessions=100, max_len=100)
    assert int(got.num_events) == int(valid.sum())
    want = sessionize_oracle(user, sess, ts, code, ip, valid=valid)
    assert int(got.num_sessions) == len(want)


def test_truncation_flags():
    user = np.zeros(10, np.int64)
    sess = np.zeros(10, np.int64)
    ts = np.arange(10, dtype=np.int64) * 1000
    code = np.arange(10, dtype=np.int32)
    got = sessionize(user, sess, ts, code, max_sessions=10, max_len=4)
    assert bool(got.truncated)        # length 10 > max_len 4
    assert int(got.length[0]) == 10   # true length still reported
    # session-capacity overflow
    user2 = np.arange(10, dtype=np.int64)
    got2 = sessionize(user2, sess, ts, code, max_sessions=3, max_len=10)
    assert bool(got2.truncated)
    assert int(got2.num_sessions) == 3  # clamped


def test_unordered_input_ok():
    # the warehouse guarantees only partial order (§2)
    rng = np.random.default_rng(2)
    user, sess, ts, code, ip = _events(6, 2, 200, rng)
    perm = rng.permutation(200)
    a = sessionize(user, sess, ts, code, ip, max_sessions=200,
                   max_len=200).trimmed()
    b = sessionize(user[perm], sess[perm], ts[perm], code[perm], ip[perm],
                   max_sessions=200, max_len=200).trimmed()
    assert np.array_equal(a.user_id, b.user_id)
    assert np.array_equal(a.length, b.length)
    assert np.array_equal(a.duration_s, b.duration_s)


@given(st.integers(0, 2**31 - 1), st.integers(1, 200))
@settings(max_examples=25, deadline=None)
def test_lexsort_perm_is_the_stable_lexicographic_order(seed, n):
    """``lexsort_perm`` (one stable 32-bit pass per key word) gives exactly
    ``np.lexsort``'s stable order — negative, extreme and tied int64 keys
    and an int32 key included."""
    import jax
    import jax.numpy as jnp
    from repro.core.sessionize import lexsort_perm
    from repro.dist.compat import enable_x64
    rng = np.random.default_rng(seed)
    pool = np.array([np.iinfo(np.int64).min, -(1 << 32), -1, 0, 1,
                     (1 << 32) - 1, 1 << 32, np.iinfo(np.int64).max])
    a = rng.choice(pool, n)
    b = rng.integers(-3, 3, n).astype(np.int64) << rng.integers(0, 40)
    c = rng.integers(-3, 3, n).astype(np.int32)
    with enable_x64():
        got = np.asarray(jax.jit(lexsort_perm)(
            (jnp.asarray(a), jnp.asarray(b), jnp.asarray(c))))
    assert np.array_equal(got, np.lexsort((c, b, a)))
