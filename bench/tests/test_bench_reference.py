"""The whole-array reference equals the copied pure-Python oracle, and the
feeds keep the properties the reference leans on; the served model's
requests are each user's day so far, and its plain reference computes
the program's logits."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import feed, loggen, requests
from bench.reference import dense_lm, fast, oracle

CFG = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                  "configs", "client-events-stream.json")))
COLS = feed.COLUMNS
# the served model's layers at small widths, in float32
SMALL = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
             head_dim=16, d_ff=128, vocab_size=116, tie_embeddings=True,
             rope_theta=10000.0, norm_eps=1e-5, dtype="float32",
             param_dtype="float32", max_cache_len=64)


def _day(n, seed):
    return loggen.generate_day(n, seed, CFG["day"])


def _oracle_rows(sessions):
    return sorted((s["user_id"], s["session_id"], s["start_ts"], s["ip"],
                   s["duration_s"], s["length"], tuple(s["symbols"]))
                  for s in sessions)


def _fast_rows(f):
    off = f["offsets"]
    return sorted((int(f["user_id"][j]), int(f["session_id"][j]),
                   int(f["start_ts"][j]), int(f["ip"][j]),
                   int(f["duration_s"][j]), int(f["length"][j]),
                   tuple(int(x) for x in f["symbols"][off[j]:off[j + 1]]))
                  for j in range(len(off) - 1))


@pytest.mark.parametrize("seed,dedup", [(1, True), (2 ** 31 + 7, True),
                                        (3, False)])
def test_fast_reference_equals_oracle(seed, dedup):
    d = _day(6000, seed)
    cols = [d[k] for k in COLS]
    keep = (oracle.dedup_events_oracle(*cols) if dedup
            else np.ones(len(cols[0]), bool))
    want = oracle.sessionize_oracle(*cols, valid=keep)
    got = fast.sessionize(*cols, dedup=dedup)
    assert _fast_rows(got) == _oracle_rows(want)
    A = len(loggen.name_table())
    dense = np.zeros(A * A, np.int64)
    for (a, b), k in oracle.ngram_counts_oracle(want, 2).items():
        dense[a * A + b] = k
    assert np.array_equal(fast.bigram_counts(got, A), dense)
    stages = loggen.stage_codes(
        CFG["funnel"], loggen.assign_codes(d["name_id"], A))
    assert list(fast.funnel_reach(got, stages, A)) == \
        oracle.funnel_oracle(want, stages)


def test_generator_draws_retries_and_keeps_sessions_tie_free():
    d = _day(20000, 9)
    keep = fast.sessionize(*(d[k] for k in COLS))
    assert len(d["user_id"]) == 20000
    assert 20000 - int(keep["length"].sum()) == 200      # 1% retries
    order = np.lexsort((d["timestamp"], d["session_id"], d["user_id"]))
    u, s, t, c = (d[k][order] for k in ("user_id", "session_id",
                                          "timestamp", "code"))
    tie = (u[1:] == u[:-1]) & (s[1:] == s[:-1]) & (t[1:] == t[:-1])
    assert np.all(c[1:][tie] == c[:-1][tie])     # ties are retries only


def test_same_seed_same_day():
    a, b = _day(5000, 2 ** 31 + 3), _day(5000, 2 ** 31 + 3)
    assert all(np.array_equal(a[k], b[k]) for k in a)


def test_session_hash_multiset_difference():
    f = fast.sessionize(*(_day(3000, 4)[k] for k in COLS))
    h = fast.session_hashes(f)
    assert fast.multiset_difference(h, h[::-1]) == 0
    assert fast.multiset_difference(h, h[1:]) == 1
    g = dict(f, symbols=f["symbols"].copy())
    g["symbols"][5] += 1
    assert fast.multiset_difference(h, fast.session_hashes(g)) == 2
    padded = np.full((len(f["length"]), 256), -1, np.int32)
    for j in range(len(f["length"])):
        a, b = f["offsets"][j], f["offsets"][j + 1]
        padded[j, :b - a] = f["symbols"][a:b]
    back = fast.from_padded(padded, f["length"], f["user_id"],
                            f["session_id"], f["ip"], f["start_ts"],
                            f["duration_s"])
    assert fast.multiset_difference(h, fast.session_hashes(back)) == 0


@pytest.mark.parametrize("period_ms", [loggen.DAY_MS, CFG["period_ms"]])
def test_stream_feed_is_time_ordered_and_days_are_moved_copies(period_ms):
    d = loggen.generate_day(8000, 5, CFG["day"], span_ms=period_ms)
    sf = feed.StreamFeed(d, CFG["day"]["start_ts_ms"], period_ms)
    n = 5 * sf.size + 123
    c = sf.take(0, n)
    assert np.all(np.diff(c["timestamp"]) >= 0)
    parts = [sf.take(a, b) for a, b in ((0, 1000), (1000, n - 1000))]
    assert all(np.array_equal(np.concatenate([p[k] for p in parts]), c[k])
               for k in COLS)
    day_of = sf.day_of(np.arange(n))
    base = fast.sessionize(*(d[k] for k in COLS))
    for day in np.flatnonzero(np.bincount(day_of) == sf.size):
        rows = {k: c[k][day_of == day] for k in COLS}
        got = fast.sessionize(*(rows[k] for k in COLS))
        want = feed.shift(base, day, sf.days_later(day), period_ms)
        assert fast.multiset_difference(fast.session_hashes(got),
                                        fast.session_hashes(want)) == 0
    users = [set(c["user_id"][day_of == k]) for k in np.unique(day_of)]
    assert sum(map(len, users)) == len(set().union(*users))


def test_a_period_thins_the_day_and_keeps_its_sessions():
    """A period of a quarter day starts its sessions in that quarter (but
    those a long gap splits off later), and its sessions look like a whole
    day's: the same events per session."""
    quarter = loggen.DAY_MS // 4
    start = CFG["day"]["start_ts_ms"]
    whole = fast.sessionize(*(_day(40000, 8)[k] for k in COLS))
    part = fast.sessionize(*(loggen.generate_day(
        10000, 8, CFG["day"], span_ms=quarter)[k] for k in COLS))
    assert part["start_ts"].min() >= start
    assert np.quantile(part["start_ts"] - start, 0.9) < quarter
    per = [len(f["symbols"]) / len(f["length"]) for f in (whole, part)]
    assert abs(per[0] - per[1]) < 0.1 * per[0]
    # the configuration's unit is one period of its day
    assert CFG["period_ms"] == quarter
    got = loggen.generate(dict(CFG, events_per_day=40000), 3)
    assert len(got["user_id"]) == 10000


def test_requests_are_each_users_day_so_far_in_order_of_session_end():
    sess = fast.sessionize(*(_day(6000, 2 ** 31 + 19)[k] for k in COLS))
    ends = sess["start_ts"] + 1000 * sess["duration_s"]
    from_ms = int(np.median(ends))
    got = requests.from_sessions(sess, 40, from_ms)
    off = sess["offsets"]
    by_user = np.lexsort((sess["start_ts"], sess["user_id"]))
    rank = np.empty(len(by_user), np.int64)
    rank[by_user] = np.arange(len(by_user))
    want = []
    for j in range(len(by_user)):
        mine = [i for i in by_user if sess["user_id"][i] ==
                sess["user_id"][j] and rank[i] <= rank[j]]
        toks = [t for i in mine for t in
                [requests.BOS_ID, *(sess["symbols"][off[i]:off[i + 1]]
                                    + requests.NUM_SPECIALS),
                 requests.EOS_ID]]
        if ends[j] >= from_ms:
            want.append((ends[j], rank[j], toks[:40], len(mine) > 1))
    want.sort(key=lambda w: w[:2])
    assert len(got) == len(want) < len(by_user)
    for i, (_, _, toks, earlier) in enumerate(want):
        assert got.prompt(i).tolist() == toks
        assert got.earlier[i] == earlier
    assert got.length.max() == 40
    assert 0 < got.earlier.mean() < 1


def test_reference_matches_the_programs_teacher_forced_logits():
    """At small widths in float32 the reference and the program's own
    fixed-batch path (``Server.score_batch``: a prefill and then decode
    through the cache) give the same logits."""
    import jax
    import jax.numpy as jnp
    from repro.configs.paper import FULL
    from repro.models import get_model
    from repro.serve import ServeConfig, Server

    api = get_model(FULL.with_(**SMALL))
    params = jax.jit(lambda k: dense_lm.init(k, SMALL))(
        dense_lm.key_of(2 ** 31 + 3))
    rng = np.random.default_rng(0)
    prompts = rng.integers(4, 116, (3, 9)).astype(np.int32)
    forced = rng.integers(4, 116, (3, 6)).astype(np.int32)
    got = Server(api, params, ServeConfig(max_new_tokens=6)).score_batch(
        prompts, forced)
    want = np.asarray(dense_lm.logits(
        params, jnp.asarray(np.concatenate([prompts, forced], 1)), SMALL))
    np.testing.assert_allclose(got, want[:, 8:14], atol=2e-4, rtol=1e-4)
    # the float8 control computes other logits
    low = np.asarray(dense_lm.logits(
        params, jnp.asarray(prompts), SMALL, quantize=True))
    assert np.abs(low - want[:, :9]).max() > 1e-2


def test_shortfall_scores_the_served_tokens():
    import jax
    import jax.numpy as jnp
    params = jax.jit(lambda k: dense_lm.init(k, SMALL))(dense_lm.key_of(7))
    prompt = np.array([1, 9, 40, 2], np.int32)
    lg = np.asarray(dense_lm.logits(params, jnp.asarray(prompt[None]),
                                    SMALL))[0]
    greedy = [int(np.argmax(lg[-1]))]
    worst = int(np.argmin(lg[-1]))
    got = dense_lm.shortfall(params, [(prompt, np.array(greedy)),
                                      (prompt, np.array([worst]))],
                             SMALL, length=16, rows=4)
    assert got[0] == pytest.approx(0.0, abs=1e-6)
    assert got[1] == pytest.approx(lg[-1].max() - lg[-1].min(), rel=1e-5)
    with pytest.raises(ValueError, match="does not fit"):
        dense_lm.shortfall(params, [(np.ones(15, np.int32),
                                     np.ones(3, np.int32))],
                           SMALL, length=16, rows=4)
