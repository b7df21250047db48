"""Distributed multi-stage log pipeline (repro.data.distpipe): shard-local
pieces against their oracles in-process, and full host-local 1xN mesh
equivalence (distributed sessionize -> dedup -> ngram/funnel rollups ==
single-host oracle path) in an 8-device subprocess, including ragged
(non-divisible) input sizes."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, {REPO_SRC!r})
        import numpy as np, jax, jax.numpy as jnp
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _events(n, seed, n_users=150, n_dupes=0):
    rng = np.random.default_rng(seed)
    user = rng.integers(0, n_users, n).astype(np.int64) * 7919
    sess = rng.integers(0, 3, n).astype(np.int64)
    ts = (1.7e12 + rng.integers(0, 2 * 3600 * 1000, n)).astype(np.int64)
    code = rng.integers(0, 64, n).astype(np.int32)
    ip = rng.integers(0, 1 << 32, n).astype(np.int64)
    if n_dupes:  # overwrite a prefix with copies of random rows (retries)
        src = rng.choice(n, n_dupes, replace=False)
        for col in (user, sess, ts, code, ip):
            col[:n_dupes] = col[src]
    return user, sess, ts, code, ip


# ---------------------------------------------------------------------------
# shard-local pieces vs oracles (in-process, fast)
# ---------------------------------------------------------------------------

def test_mark_duplicates_matches_oracle():
    from repro.dist.compat import enable_x64
    import jax.numpy as jnp
    from repro.core.sessionize import mark_duplicate_events
    from repro.core.oracle import dedup_events_oracle
    user, sess, ts, code, ip = _events(997, seed=3, n_dupes=200)
    valid = np.random.default_rng(4).random(997) > 0.1
    with enable_x64():
        got = np.asarray(mark_duplicate_events(
            jnp.asarray(user, jnp.int64), jnp.asarray(sess, jnp.int64),
            jnp.asarray(ts, jnp.int64), jnp.asarray(code, jnp.int32),
            jnp.asarray(ip, jnp.int64), jnp.asarray(valid, bool)))
    exp = dedup_events_oracle(user, sess, ts, code, ip, valid)
    # Same surviving multiset of rows; which exact copy survives is
    # irrelevant (duplicates are identical), but the count per row must
    # match and no invalid row may survive.
    assert got.sum() == exp.sum()
    assert not got[~valid].any()
    key = lambda m: sorted(zip(user[m], sess[m], ts[m], code[m], ip[m]))
    assert key(got) == key(exp)


def test_sessionize_dedup_kwarg():
    from repro.core import sessionize
    from repro.core.oracle import sessionize_oracle, dedup_events_oracle
    user, sess, ts, code, ip = _events(800, seed=7, n_dupes=150)
    s = sessionize(user, sess, ts, code, ip, dedup=True)
    keep = dedup_events_oracle(user, sess, ts, code, ip)
    ora = sessionize_oracle(user[keep], sess[keep], ts[keep], code[keep],
                            ip[keep])
    assert int(s.num_sessions) == len(ora)
    assert int(s.num_events) == int(keep.sum())


def test_dense_ngram_matches_sparse():
    import jax.numpy as jnp
    from repro.dist.compat import enable_x64
    from repro.analytics.ngram import dense_ngram_counts, ngram_counts
    from repro.core import SessionSequences, sessionize
    user, sess, ts, code, ip = _events(2048, seed=11)
    seqs = SessionSequences.from_sessionized(
        sessionize(user, sess, ts, code, ip, max_len=64))
    for n in (1, 2, 3):
        keys, counts = ngram_counts(seqs, n, 64)
        with enable_x64():
            dense = np.asarray(dense_ngram_counts(
                jnp.asarray(seqs.symbols), jnp.asarray(seqs.mask()), n, 64))
        expect = np.zeros(64 ** n, np.int64)
        expect[keys] = counts
        assert np.array_equal(dense, expect), f"order {n}"


def test_reach_histogram_matches_funnel_reach():
    import jax.numpy as jnp
    from repro.analytics.funnel import (build_stage_table, funnel_reach,
                                        reach_histogram)
    from repro.core import SessionSequences, sessionize
    user, sess, ts, code, ip = _events(2048, seed=13)
    seqs = SessionSequences.from_sessionized(
        sessionize(user, sess, ts, code, ip, max_len=64))
    stages = [np.array([1, 2]), np.array([5]), np.array([9, 10])]
    table = build_stage_table(stages, 64)
    got = np.asarray(reach_histogram(
        jnp.asarray(seqs.symbols), jnp.asarray(seqs.mask()),
        jnp.asarray(table), len(stages)))
    assert [(j, int(c)) for j, c in enumerate(got)] == \
        funnel_reach(seqs, stages, 64)


def test_bucket_by_destination_pytree_payload():
    """Nested payload trees route identically to flat column dicts."""
    import jax.numpy as jnp
    from repro.dist.collectives import bucket_by_destination
    rng = np.random.default_rng(17)
    n = 257
    dest = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    a = jnp.asarray(rng.integers(0, 1000, n).astype(np.int32))
    b = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    flat, _, _, _, d1 = bucket_by_destination(dict(a=a, b=b), dest, 4, 128)
    nested, _, _, _, d2 = bucket_by_destination(
        dict(cols=dict(a=a), payload=[b]), dest, 4, 128)
    assert int(d1) == int(d2)
    assert np.array_equal(flat["a"], nested["cols"]["a"])
    assert np.array_equal(flat["b"], nested["payload"][0])


# ---------------------------------------------------------------------------
# full pipeline on host-local meshes
# ---------------------------------------------------------------------------

def test_single_shard_pipeline_matches_oracle():
    """(1,) mesh, ragged n: the mesh plumbing with no real repartition."""
    import jax
    from repro.data.distpipe import (DistPipelineConfig,
                                     make_distributed_pipeline,
                                     single_host_pipeline)
    user, sess, ts, code, ip = _events(1023, seed=19, n_dupes=100)
    stages = [np.array([1, 2]), np.array([5])]
    cfg = DistPipelineConfig(alphabet_size=64, max_sessions_per_shard=2048,
                             max_len=64)
    pipe = make_distributed_pipeline(
        jax.make_mesh((1,), ("data",)), cfg, stages)
    res = pipe(user, sess, ts, code, ip)
    ora = single_host_pipeline(user, sess, ts, code, ip, cfg=cfg,
                               stages=stages)
    assert res.dropped == 0 and not res.truncated
    assert res.num_sessions() == ora.num_sessions()
    assert np.array_equal(res.ngram_counts, ora.ngram_counts)
    assert res.funnel_reach == ora.funnel_reach


def test_capacity_overflow_is_counted_never_silent():
    import jax
    from repro.data.distpipe import (DistPipelineConfig,
                                     make_distributed_pipeline)
    user, sess, ts, code, ip = _events(512, seed=23)
    cfg = DistPipelineConfig(alphabet_size=64, max_sessions_per_shard=512,
                             max_len=64, capacity_factor=0.25)
    pipe = make_distributed_pipeline(jax.make_mesh((1,), ("data",)), cfg)
    res = pipe(user, sess, ts, code, ip)
    assert res.dropped > 0
    assert res.funnel_reach is None  # built without stages


def test_loggen_corpus_pipeline_matches_oracle(loggen_corpus):
    """The shared loggen day (same fixture the streaming equivalence tests
    replay in test_streampipe.py) through the batch pipeline: mesh path ==
    single-host oracle on identical inputs, including the signup funnel."""
    import jax
    from repro.data.distpipe import (DistPipelineConfig,
                                     make_distributed_pipeline,
                                     single_host_pipeline)
    lc = loggen_corpus
    cfg = DistPipelineConfig(alphabet_size=lc.alphabet_size,
                             max_sessions_per_shard=lc.n_events,
                             max_len=128)
    pipe = make_distributed_pipeline(jax.make_mesh((1,), ("data",)), cfg,
                                     lc.stages)
    res = pipe(lc.user_id, lc.session_id, lc.timestamp, lc.code, lc.ip)
    ora = single_host_pipeline(lc.user_id, lc.session_id, lc.timestamp,
                               lc.code, lc.ip, cfg=cfg, stages=lc.stages)
    assert res.dropped == 0 and not res.truncated
    assert res.num_sessions() == ora.num_sessions() > 0
    assert np.array_equal(res.ngram_counts, ora.ngram_counts)
    assert res.funnel_reach == ora.funnel_reach
    # the funnel is actually populated in the corpus, not vacuously equal
    assert ora.funnel_reach[0][1] > 0


@pytest.mark.parametrize("n", [4096, 4093])  # divisible and ragged
def test_8shard_pipeline_matches_single_host(n):
    _run(f"""
    from repro.data.distpipe import (DistPipelineConfig,
                                     make_distributed_pipeline,
                                     single_host_pipeline)
    rng = np.random.default_rng(1)
    N = {n}
    user = rng.integers(0, 150, N).astype(np.int64) * 7919
    sess = rng.integers(0, 2, N).astype(np.int64)
    ts = (1.7e12 + rng.integers(0, 2*3600*1000, N)).astype(np.int64)
    code = rng.integers(0, 64, N).astype(np.int32)
    ip = rng.integers(0, 1 << 32, N).astype(np.int64)
    dup = rng.choice(N, 500, replace=False)
    for col in (user, sess, ts, code, ip):
        col[:500] = col[dup]
    stages = [np.array([1, 2]), np.array([5]), np.array([9, 10])]
    cfg = DistPipelineConfig(alphabet_size=64, max_sessions_per_shard=1024,
                             max_len=128, ngram_n=2)
    pipe = make_distributed_pipeline(jax.make_mesh((8,), ("data",)), cfg,
                                     stages)
    res = pipe(user, sess, ts, code, ip)
    ora = single_host_pipeline(user, sess, ts, code, ip, cfg=cfg,
                               stages=stages)
    assert res.dropped == 0
    assert res.num_sessions() == ora.num_sessions()
    assert np.array_equal(res.ngram_counts, ora.ngram_counts)
    assert res.funnel_reach == ora.funnel_reach
    got, exp = res.to_sequences(), ora.sequences
    gm, em = got.mask(), exp.mask()
    gs = sorted((int(got.user_id[i]), int(got.session_id[i]),
                 int(got.start_ts[i]), int(got.ip[i]),
                 int(got.duration_s[i]), tuple(got.symbols[i][gm[i]]))
                for i in range(len(got)))
    es = sorted((int(exp.user_id[i]), int(exp.session_id[i]),
                 int(exp.start_ts[i]), int(exp.ip[i]),
                 int(exp.duration_s[i]), tuple(exp.symbols[i][em[i]]))
                for i in range(len(exp)))
    assert gs == es
    print("OK")
    """)
