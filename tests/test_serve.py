"""Serving subsystem tests: decode-loop correctness fixes, ragged
prefill-mask equivalence, DecodeState family matrix, and
continuous-batching scheduler invariants.

Three kinds of model drive these:

* the real smoke behaviour LM (dense) for numerical properties — greedy
  determinism and the padded-vs-trimmed bit-equality the per-row position
  masking guarantees;
* one real smoke model per registry family (the 7-arch matrix) asserting
  the unified DecodeState contract: scheduler output bit-equal to the
  ``Server.generate_batch`` fixed-batch oracle, admit/evict/backfill
  invariants, and zero retraces after warmup on a host-local mesh;
* a deterministic stub ModelApi (an "echo+1, EOS after k steps" machine
  with a real KV-cache-shaped state) for machinery properties — exact
  decode-step counts, EOS freezing, admission accounting.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.models.registry import get_model, ModelApi, ServeCaps
from repro.data.pipeline import PAD_ID, EOS_ID
from repro.dist import make_host_mesh, REPLICATED
from repro.serve import (Server, ServeConfig, ContinuousScheduler,
                         ServeMetrics, prompt_lengths,
                         BlockPool, blocks_for)
from repro.serve import SchedulerConfig as _SchedulerConfig

VOCAB = 64


def SchedulerConfig(**kw):
    """Every scheduler test runs with ``debug=True``: the pool re-checks
    its allocator invariants after each evict/preempt, so a refcount or
    free-list corruption fails the test that caused it, not a later one."""
    kw.setdefault("debug", True)
    return _SchedulerConfig(**kw)

# one representative smoke arch per family (+ the paper LM): the 7-arch
# serving matrix every DecodeState implementation is exercised through
MATRIX_ARCHS = ("behavior-lm-100m", "qwen3-0.6b", "olmoe-1b-7b",
                "mamba2-370m", "zamba2-7b", "whisper-tiny",
                "llama-3.2-vision-11b")


@pytest.fixture(scope="module")
def dense():
    cfg = smoke_config("behavior-lm-100m").with_(vocab_size=VOCAB,
                                                 max_cache_len=64)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return api, params


@pytest.fixture(scope="module")
def family_model():
    """Per-arch (api, params) cache shared across the matrix tests."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = smoke_config(arch).with_(vocab_size=VOCAB,
                                           max_cache_len=64)
            api = get_model(cfg)
            cache[arch] = (api, api.init(jax.random.PRNGKey(0)))
        return cache[arch]
    return get


def _family_extra(cfg, rng):
    """One request's stub-frontend encoder inputs, or None."""
    if cfg.family == "encdec":
        return dict(frames=rng.standard_normal(
            (cfg.n_frames, cfg.d_model)).astype(np.float32))
    if cfg.family == "vlm":
        return dict(patches=rng.standard_normal(
            (cfg.n_patches, cfg.vision_dim)).astype(np.float32))
    return None


# ---------------------------------------------------------------------------
# Stub model: next token = clip(prev + 1), EOS after `eos_after` decodes.
# State leaves are (X, B, ...) so the scheduler's generic row insert works;
# k/v are KV-cache-shaped so the paged block scatter works too, and
# decode passes unknown state keys (the block table) through.
# ---------------------------------------------------------------------------

def _stub_api(eos_after: int = 3, family: str = "dense",
              caps: ServeCaps | None = None) -> ModelApi:
    cfg = smoke_config("behavior-lm-100m").with_(
        vocab_size=VOCAB, max_cache_len=64, family=family)

    def _next(tok):
        return jnp.clip(tok + 1, 4, VOCAB - 1).astype(jnp.int32)

    def prefill(p, b):
        toks = jnp.asarray(b["tokens"])
        bsz, l = toks.shape
        lengths = b.get("lengths")
        if lengths is None:
            last, idx = toks[:, -1], l
        else:
            li = jnp.asarray(lengths, jnp.int32)
            last, idx = toks[jnp.arange(bsz), li - 1], li
        state = dict(k=jnp.zeros((1, bsz, 1, cfg.max_cache_len, 1)),
                     v=jnp.zeros((1, bsz, 1, cfg.max_cache_len, 1)),
                     gen=jnp.zeros((1, bsz), jnp.int32))
        return 10.0 * jax.nn.one_hot(_next(last), VOCAB), state, idx

    def decode_step(p, tok, state, idx):
        gen = state["gen"] + 1
        nxt = jnp.where(gen[0] >= eos_after, EOS_ID, _next(tok))
        return 10.0 * jax.nn.one_hot(nxt, VOCAB), dict(state, gen=gen)

    api = ModelApi(cfg=cfg, rules=REPLICATED, mesh=None,
                   init=lambda key: {}, axes=lambda: {},
                   loss=None, prefill=prefill, decode_step=decode_step,
                   batch_keys=("tokens",))
    if caps is not None:
        api.caps = caps
    return api


def _stub_expected(prompt, budget, eos_after):
    """The stub's deterministic output for one request."""
    out = [min(int(prompt[-1]) + 1, VOCAB - 1)]
    for k in range(1, budget):
        if k >= eos_after:
            out.append(EOS_ID)
            break
        out.append(min(out[-1] + 1, VOCAB - 1))
    return np.array(out[:budget], np.int32)


def _rand_prompts(rng, n, lo=3, hi=15):
    return [rng.integers(4, VOCAB, int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# prompt length derivation
# ---------------------------------------------------------------------------

def test_prompt_lengths():
    p = np.array([[5, 6, 7, 0, 0],
                  [5, 6, 7, 8, 9],
                  [0, 0, 0, 0, 0]], np.int32)
    assert prompt_lengths(p).tolist() == [3, 5, 1]


# ---------------------------------------------------------------------------
# Server: greedy determinism + padded/trimmed bit-equality (real model)
# ---------------------------------------------------------------------------

def test_greedy_decode_deterministic(dense):
    api, params = dense
    srv = Server(api, params, ServeConfig(max_new_tokens=6))
    rng = np.random.default_rng(0)
    prompts = np.full((3, 12), PAD_ID, np.int32)
    for i, l in enumerate((12, 7, 4)):
        prompts[i, :l] = rng.integers(4, VOCAB, l)
    g1 = srv.generate(prompts)
    g2 = srv.generate(prompts)
    assert g1.shape == (3, 6)
    assert np.array_equal(g1, g2)


def test_padded_prompt_decodes_bit_equal_to_trimmed(dense):
    api, params = dense
    srv = Server(api, params, ServeConfig(max_new_tokens=6))
    rng = np.random.default_rng(1)
    for l in (3, 5, 9):
        prompts = np.full((2, 12), PAD_ID, np.int32)
        prompts[0] = rng.integers(4, VOCAB, 12)
        prompts[1, :l] = rng.integers(4, VOCAB, l)
        padded = srv.generate(prompts)
        trimmed = srv.generate(prompts[1:2, :l])
        assert np.array_equal(padded[1], trimmed[0]), l


def test_score_batch_teacher_forced_on_greedy_output(dense):
    """Teacher-forced on generate_batch's own greedy tokens, score_batch
    runs the same programs, so each step's argmax is the token drawn."""
    api, params = dense
    srv = Server(api, params, ServeConfig(max_new_tokens=5))
    rng = np.random.default_rng(5)
    prompts = np.full((3, 10), PAD_ID, np.int32)
    for i, l in enumerate((10, 6, 3)):
        prompts[i, :l] = rng.integers(4, VOCAB, l)
    toks = srv.generate_batch(prompts)
    logits = srv.score_batch(prompts, toks)
    assert logits.shape == (3, 5, api.cfg.vocab_size)
    assert np.array_equal(np.argmax(logits, -1), toks)


@pytest.mark.parametrize("arch", ["mamba2-370m", "zamba2-7b"])
def test_ragged_ssm_prefill_bit_equals_trimmed(arch):
    """The recurrent state must be frozen across right-padding: a padded
    ragged prefill hands decode the state of the trimmed prompt (dt masked
    to 0 + ragged-correct conv tails).

    The two prefills run matmuls of different contraction lengths (8 vs 5,
    the pads contributing exact zeros), and XLA does not promise the same
    summation order across shapes: on the CPU backend the results differ
    in the last float32 bit. So values compare at ``rtol=1e-5`` — a pad
    leaking into the state moves them by orders of magnitude more."""
    cfg = smoke_config(arch).with_(vocab_size=VOCAB, max_cache_len=64)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(12)
    n, S = 5, 8
    row = rng.integers(4, VOCAB, n).astype(np.int32)
    padded = np.zeros((1, S), np.int32)
    padded[0, :n] = row
    lg_p, st_p, idx_p = api.prefill(params, dict(
        tokens=jnp.asarray(padded), lengths=jnp.asarray([n], jnp.int32)))
    lg_t, st_t, idx_t = api.prefill(params, dict(
        tokens=jnp.asarray(row[None])))
    tol = dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_t), **tol)
    assert np.array_equal(np.argmax(lg_p, -1), np.argmax(lg_t, -1))
    assert int(np.asarray(idx_p)[0]) == idx_t == n
    # recurrent leaves (mamba conv tails + SSM heads) must agree;
    # attention KV (hybrid) only up to n — pads beyond are masked
    tree = st_p if arch == "mamba2-370m" else st_p["mamba"]
    oracle = st_t if arch == "mamba2-370m" else st_t["mamba"]
    for key in tree:
        np.testing.assert_allclose(np.asarray(tree[key]),
                                   np.asarray(oracle[key]), err_msg=key,
                                   **tol)
    l2p, _ = api.decode_step(params, jnp.argmax(lg_p, -1).astype(jnp.int32),
                             st_p, jnp.asarray(idx_p))
    l2t, _ = api.decode_step(params, jnp.argmax(lg_t, -1).astype(jnp.int32),
                             st_t, jnp.int32(n))
    np.testing.assert_allclose(np.asarray(l2p), np.asarray(l2t), **tol)


# ---------------------------------------------------------------------------
# RNG regression: the prefill-token draw must come from a split subkey,
# independent of later decode draws; different seeds differ at token 0.
# ---------------------------------------------------------------------------

def test_temperature_seeds_differ_at_token0(dense):
    api, params = dense
    rng = np.random.default_rng(2)
    prompts = rng.integers(4, VOCAB, (4, 8)).astype(np.int32)
    g0 = Server(api, params, ServeConfig(
        max_new_tokens=3, temperature=2.0, seed=0)).generate(prompts)
    g1 = Server(api, params, ServeConfig(
        max_new_tokens=3, temperature=2.0, seed=1)).generate(prompts)
    assert (g0[:, 0] != g1[:, 0]).any()
    # same seed stays reproducible
    g0b = Server(api, params, ServeConfig(
        max_new_tokens=3, temperature=2.0, seed=0)).generate(prompts)
    assert np.array_equal(g0, g0b)


def test_batch_path_first_sample_uses_split_subkey():
    # the ssm smoke model through the explicit fixed-batch oracle path
    cfg = smoke_config("mamba2-370m").with_(vocab_size=VOCAB)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(3).integers(
        4, VOCAB, (2, 8)).astype(np.int32)
    temp, seed = 2.0, 0
    srv = Server(api, params, ServeConfig(
        max_new_tokens=2, temperature=temp, seed=seed))
    got = srv.generate_batch(prompts)[:, 0]
    # same jitted prefill the server used, so logits match bitwise
    logits, _, _ = srv._prefill(params, dict(
        tokens=jnp.asarray(prompts),
        lengths=jnp.asarray(prompt_lengths(prompts))))
    _, sub = jax.random.split(jax.random.PRNGKey(seed))
    expected = jax.random.categorical(sub, logits / temp, axis=-1)
    assert np.array_equal(got, np.asarray(expected))
    # and NOT the pre-fix draw from the raw (reused) parent key
    buggy = jax.random.categorical(
        jax.random.PRNGKey(seed), logits / temp, axis=-1)
    if not np.array_equal(np.asarray(buggy), np.asarray(expected)):
        assert not np.array_equal(got, np.asarray(buggy))


# ---------------------------------------------------------------------------
# off-by-one + EOS short-circuit (exact decode counts via the stub)
# ---------------------------------------------------------------------------

def test_no_discarded_decode_step():
    api = _stub_api(eos_after=99)
    srv = Server(api, {}, ServeConfig(max_new_tokens=4))
    out = srv.generate_batch(np.full((1, 5), 7, np.int32))
    # 4 tokens = 1 prefill sample + exactly 3 decodes (the old loop ran 4)
    assert srv.decode_calls == 3
    assert out.tolist() == [[8, 9, 10, 11]]


def test_eos_short_circuits_batch_loop():
    api = _stub_api(eos_after=2)
    srv = Server(api, {}, ServeConfig(max_new_tokens=8))
    out = srv.generate_batch(np.full((1, 5), 7, np.int32))
    # tokens: 8, 9, EOS then frozen — only 2 decodes ever launched
    assert srv.decode_calls == 2
    assert out.tolist() == [[8, 9, EOS_ID] + [EOS_ID] * 5]


def test_scheduler_decode_step_counts():
    api = _stub_api(eos_after=99)
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=6))
    sched.submit(np.full(5, 7, np.int32))
    sched.run()
    assert sched.decode_steps == 5          # 6 tokens, first from prefill
    # budget 1: finished at admission, no decode at all
    before = sched.decode_steps
    sched.submit(np.full(5, 7, np.int32), max_new_tokens=1)
    out = sched.run()
    assert sched.decode_steps == before
    assert out[1].tolist() == [8]


# ---------------------------------------------------------------------------
# scheduler: admit/evict/backfill invariants + no recompilation after warmup
# ---------------------------------------------------------------------------

def test_scheduler_stream_invariants_and_jit_cache_hits():
    eos_after = 4
    api = _stub_api(eos_after=eos_after)
    mesh = make_host_mesh(1, 1)
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8, 16), max_new_tokens=6), mesh=mesh)
    rng = np.random.default_rng(4)

    # warmup: one request per bucket
    w1, w2 = np.full(6, 9, np.int32), np.full(12, 9, np.int32)
    sched.submit(w1), sched.submit(w2)
    sched.run()
    warm = dict(sched.trace_counts)
    assert warm["prefill"] == 2             # one trace per bucket
    assert warm["decode"] == 1
    assert warm["insert"] == 1

    # stream of 8 = 4x slot count, variable lengths across both buckets
    prompts = _rand_prompts(rng, 8, lo=3, hi=16)
    rids = [sched.submit(p) for p in prompts]
    max_active = 0
    while sched.num_active or sched.num_pending:
        sched.step()
        max_active = max(max_active, sched.num_active)
    outs = sched.run()

    assert dict(sched.trace_counts) == warm   # jit cache hits only
    assert max_active <= 2                    # never exceeds the slot table
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            outs[rid], _stub_expected(p, 6, eos_after), err_msg=str(rid))


def test_scheduler_metrics_lifecycle():
    api = _stub_api(eos_after=3)
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    m = ServeMetrics(clock=clock)
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=4), metrics=m)
    for p in _rand_prompts(np.random.default_rng(5), 4, lo=3, hi=8):
        sched.submit(p)
    sched.run()
    s = m.summary()
    assert s["requests"] == 4
    assert s["tokens"] == sum(r.tokens for r in m.requests.values())
    assert s["tokens_per_sec"] > 0
    assert s["p99_latency_s"] >= s["p50_latency_s"] > 0
    for r in m.requests.values():
        assert r.submit < r.admit <= r.first_token < r.finish


def test_scheduler_real_model_matches_single_request(dense):
    """Continuous slots vs one-request-at-a-time: greedy outputs agree."""
    api, params = dense
    sched = ContinuousScheduler(api, params, SchedulerConfig(
        batch=3, buckets=(8, 16), max_new_tokens=5))
    prompts = _rand_prompts(np.random.default_rng(6), 7, lo=3, hi=16)
    rids = [sched.submit(p) for p in prompts]
    outs = sched.run()
    solo = ContinuousScheduler(api, params, SchedulerConfig(
        batch=1, buckets=(8, 16), max_new_tokens=5))
    for rid, p in zip(rids[:3], prompts[:3]):
        srid = solo.submit(p)
        np.testing.assert_array_equal(solo.run()[srid], outs[rid])


def test_bounded_state_requires_positive_cache_len():
    """A position-bounded KV family misconfigured with max_cache_len=0
    must fail loudly at construction, not decode into an empty cache."""
    api = _stub_api()
    api.cfg = api.cfg.with_(max_cache_len=0)
    with pytest.raises(ValueError, match="max_cache_len"):
        ContinuousScheduler(api, {}, SchedulerConfig(batch=2, buckets=(8,)))


def test_scheduler_rejects_unknown_state_kind_loudly():
    """No silent fixed-batch fallback: a family whose registry caps name
    an unknown DecodeState kind fails at construction."""
    api = _stub_api(caps=ServeCaps(state_kind="mystery"))
    with pytest.raises(ValueError, match="unknown serving family"):
        ContinuousScheduler(api, {}, SchedulerConfig(batch=2, buckets=(8,)))


# ---------------------------------------------------------------------------
# DecodeState family matrix: all 7 registry architectures serve through the
# continuous scheduler — bit-equal to the fixed-batch oracle, admit/evict/
# backfill invariants, zero retraces after warmup on a host-local mesh.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", MATRIX_ARCHS)
def test_family_matrix_continuous_serving(arch, family_model):
    api, params = family_model(arch)
    cfg = api.cfg
    mesh = make_host_mesh(1, 1)
    budget = 4
    sched = ContinuousScheduler(api, params, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=budget), mesh=mesh)
    rng = np.random.default_rng(13)

    # warmup stream, then a 3x-slot-count backfill stream
    warm_prompts = _rand_prompts(rng, 2, lo=3, hi=9)
    stream_prompts = _rand_prompts(rng, 6, lo=3, hi=9)
    prompts = warm_prompts + stream_prompts
    extras = [_family_extra(cfg, rng) for _ in prompts]

    rids = [sched.submit(p, extra=e)
            for p, e in zip(warm_prompts, extras[:2])]
    outs = dict(sched.run())
    warm_traces = dict(sched.trace_counts)

    rids += [sched.submit(p, extra=e)
             for p, e in zip(stream_prompts, extras[2:])]
    max_active = 0
    while sched.num_active or sched.num_pending:
        sched.step()
        max_active = max(max_active, sched.num_active)
    outs.update(sched.run())

    # invariants: slot table never overflows, queue fully drained, every
    # request terminated by budget or EOS, zero retraces after warmup
    assert dict(sched.trace_counts) == warm_traces, arch
    assert max_active <= 2
    assert sched.num_active == 0 and sched.num_pending == 0
    for rid in rids:
        toks = outs[rid]
        assert len(toks) == budget or toks[-1] == EOS_ID

    # bit-equality against the fixed-batch oracle over the same rows
    srv = Server(api, params, ServeConfig(max_new_tokens=budget))
    width = max(len(p) for p in prompts)
    rect = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        rect[i, :len(p)] = p
    extra = None
    if extras[0] is not None:
        extra = {k: np.stack([e[k] for e in extras])
                 for k in extras[0]}
    oracle = srv.generate_batch(rect, extra)
    for i, rid in enumerate(rids):
        got = outs[rid]
        np.testing.assert_array_equal(
            got, oracle[i][:len(got)], err_msg=f"{arch} row {i}")


@pytest.mark.parametrize("arch", ["whisper-tiny", "llama-3.2-vision-11b"])
def test_cross_families_validate_request_extras(arch, family_model):
    api, params = family_model(arch)
    sched = ContinuousScheduler(api, params, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=2))
    with pytest.raises(ValueError, match="requires extras"):
        sched.submit(np.full(4, 7, np.int32))          # missing frames
    key = "frames" if api.cfg.family == "encdec" else "patches"
    with pytest.raises(ValueError, match="shape"):
        sched.submit(np.full(4, 7, np.int32),
                     extra={key: np.zeros((3, 3), np.float32)})


def test_token_family_rejects_stray_extras(dense):
    api, params = dense
    sched = ContinuousScheduler(api, params, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=2))
    with pytest.raises(ValueError, match="requires extras"):
        sched.submit(np.full(4, 7, np.int32),
                     extra=dict(frames=np.zeros((2, 2), np.float32)))


# ---------------------------------------------------------------------------
# paged KV: block pool allocator
# ---------------------------------------------------------------------------

def _tiny_pool(num_blocks=6, block_size=4):
    return BlockPool(num_blocks=num_blocks, block_size=block_size,
                     num_kv_heads=1, head_dim=2, num_layers=1)


def test_blocks_for():
    assert blocks_for(0, 8) == 0
    assert blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2


def test_block_pool_alloc_free_reuse_cycles():
    pool = _tiny_pool(num_blocks=6)
    assert (pool.capacity, pool.available, pool.live_blocks) == (6, 6, 0)
    pool.reserve(4)
    assert pool.available == 2            # reservation sets capacity aside
    ids = [pool.take() for _ in range(4)]
    assert len(set(ids)) == 4 and all(1 <= i <= 6 for i in ids)  # 0 = trash
    assert (pool.available, pool.live_blocks) == (2, 4)
    pool.free(ids[:2])
    assert (pool.available, pool.live_blocks) == (4, 2)
    pool.free(ids[2:])
    # mixed-length alloc/free cycles always reach full capacity again:
    # blocks are interchangeable, so there is no fragmentation to leak
    for k in (6, 1, 5, 2, 6, 3):
        pool.reserve(k)
        got = [pool.take() for _ in range(k)]
        assert len(set(got)) == k
        pool.free(got)
    assert (pool.available, pool.live_blocks) == (6, 0)


def test_block_pool_reservation_guards():
    pool = _tiny_pool(num_blocks=4)
    with pytest.raises(ValueError, match="reserve"):
        pool.reserve(5)
    with pytest.raises(ValueError, match="reservation"):
        pool.take()                        # take without a reservation
    pool.reserve(2)
    a = pool.take()
    pool.cancel(1)                         # evicted before using block 2
    assert pool.available == 3
    pool.free([a])
    assert pool.available == 4
    with pytest.raises(ValueError, match="trash block"):
        pool.free([0])                     # the trash block is never freed
    with pytest.raises(ValueError, match="out of range"):
        pool.free([9])


def test_block_pool_worst_case_accounting():
    pool = _tiny_pool(block_size=8)
    # prefill writes prompt_len, decode writes budget - 1 more positions
    assert pool.blocks_needed(5, 6) == 2       # positions 0..9
    assert pool.blocks_needed(8, 1) == 1       # budget 1: prompt only
    assert pool.blocks_needed(8, 9) == 2       # positions 0..15
    assert pool.blocks_needed(8, 10) == 3      # position 16 opens block 2


# ---------------------------------------------------------------------------
# paged KV: scheduler admission / lazy growth / eviction (stub machinery)
# ---------------------------------------------------------------------------

def test_paged_admission_blocked_at_exhaustion_then_unblocked():
    eos_after = 99                             # run every request to budget
    api = _stub_api(eos_after=eos_after)
    # each request: prompt 5 + budget 6 -> 2 blocks of 8; a 3-block pool
    # holds exactly one in flight even though the slot table has 4 rows
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=4, buckets=(8,), max_new_tokens=6,
        paged=True, block_size=8, num_blocks=3))
    prompts = [np.full(5, 7, np.int32) for _ in range(3)]
    rids = [sched.submit(p) for p in prompts]
    sched.step()
    assert sched.num_active == 1               # admission gated by blocks,
    assert sched.num_pending == 2              # not by the 4 free rows
    max_active = 1
    while sched.num_active or sched.num_pending:
        sched.step()
        max_active = max(max_active, sched.num_active)
    outs = sched.run()
    assert max_active == 1                     # pool exhaustion held
    assert sched.pool.live_blocks == 0 and sched.pool.available == 3
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid],
                                      _stub_expected(p, 6, eos_after))


def test_paged_lazy_block_growth():
    api = _stub_api(eos_after=99)
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=1, buckets=(8,), max_new_tokens=10,
        paged=True, block_size=4))
    sched.submit(np.full(3, 7, np.int32))      # needs ceil(12/4) = 3 blocks
    sched._admit()
    assert len(sched.state._blocks[0]) == 1    # prompt fits one block
    peak = 1
    while sched.num_active:
        sched.step()
        if sched._active[0]:
            peak = max(peak, len(sched.state._blocks[0]))
    assert peak == 3                           # grew lazily to worst case
    assert sched.pool.live_blocks == 0         # all freed on eviction


def test_paged_dead_row_table_is_cleared():
    api = _stub_api(eos_after=2)
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=6,
        paged=True, block_size=8))
    sched.submit(np.full(5, 7, np.int32))
    sched.run()
    assert (sched.state._table == 0).all()     # dead rows write to trash


def test_paged_scheduler_decode_step_counts():
    api = _stub_api(eos_after=99)
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=6,
        paged=True, block_size=16))
    sched.submit(np.full(5, 7, np.int32))
    sched.run()
    assert sched.decode_steps == 5             # same contract as dense


def test_paged_scheduler_metrics_report_kv_usage():
    api = _stub_api(eos_after=99)
    m = ServeMetrics()
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=6,
        paged=True, block_size=8, num_blocks=6), metrics=m)
    for p in _rand_prompts(np.random.default_rng(7), 4, lo=3, hi=8):
        sched.submit(p)
    sched.run()
    s = m.summary()
    assert s["kv_total_blocks"] == 6
    assert 0 < s["kv_live_blocks_peak"] <= 6
    assert s["kv_util_peak"] == s["kv_live_blocks_peak"] / 6
    assert s["kv_peak_resident_bytes"] == \
        s["kv_live_blocks_peak"] * sched.pool.block_bytes


def test_paged_rejects_bad_configs():
    api = _stub_api()
    with pytest.raises(ValueError, match="must divide"):
        ContinuousScheduler(api, {}, SchedulerConfig(
            batch=2, buckets=(8,), paged=True, block_size=7))
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=4,
        paged=True, block_size=8, num_blocks=2))
    # capacity error names the bucket and the blocks required
    with pytest.raises(ValueError, match=r"bucket 8.*requires 4 KV blocks"):
        sched.submit(np.full(8, 7, np.int32), max_new_tokens=20)
    api_ssm = _stub_api(family="ssm", caps=ServeCaps(
        state_kind="recurrent", positioned=False))
    with pytest.raises(ValueError, match="paged KV serves"):
        ContinuousScheduler(api_ssm, {}, SchedulerConfig(
            batch=2, buckets=(8,), paged=True))


def test_paged_prefill_writes_bucket_covering_blocks():
    """Paged prefill (ROADMAP item): the admission prefill runs against a
    bucket-covering cache — blocks_for(bucket) * block_size positions —
    not a max_cache_len stripe, and its K/V scatter straight into pool
    blocks."""
    api = _stub_api(eos_after=99)
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8, 16), max_new_tokens=4,
        paged=True, block_size=8))
    assert sched.state.prefill_cache_len(8) == 8
    assert sched.state.prefill_cache_len(16) == 16
    # block_size 16 covers a 8-bucket with one 16-token block
    sched16 = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8,), max_new_tokens=4,
        paged=True, block_size=16))
    assert sched16.state.prefill_cache_len(8) == 16
    for p in _rand_prompts(np.random.default_rng(14), 4, lo=3, hi=16):
        sched.submit(p)
    sched.run()
    # the compiled admission prefills are keyed by bucket-covering cache
    # lengths, never by max_cache_len (64)
    assert set(sched._prefill_fns) == {8, 16}


def test_paged_rejects_recurrent_state_families():
    """The paged slab replaces dict(k, v) KV stripes only; recurrent rows
    (caps.paged=False) keep their dense layout and say so loudly."""
    cfg = smoke_config("mamba2-370m").with_(vocab_size=VOCAB)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    srv = Server(api, params, ServeConfig(max_new_tokens=2, paged=True))
    with pytest.raises(ValueError, match="paged KV serves"):
        srv.generate(np.full((1, 5), 7, np.int32))


# ---------------------------------------------------------------------------
# paged KV: bit-equality with the dense path (real model, host-local mesh)
# ---------------------------------------------------------------------------

def test_paged_matches_dense_bit_equal_and_no_retrace(dense):
    api, params = dense
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(8)
    prompts = _rand_prompts(rng, 8, lo=3, hi=16)
    dense_s = ContinuousScheduler(api, params, SchedulerConfig(
        batch=3, buckets=(8, 16), max_new_tokens=5), mesh=mesh)
    paged_s = ContinuousScheduler(api, params, SchedulerConfig(
        batch=3, buckets=(8, 16), max_new_tokens=5,
        paged=True, block_size=8), mesh=mesh)
    rd = [dense_s.submit(p) for p in prompts]
    rp = [paged_s.submit(p) for p in prompts]
    outs_d, outs_p = dense_s.run(), paged_s.run()
    for a, b, p in zip(rd, rp, prompts):
        np.testing.assert_array_equal(outs_d[a], outs_p[b],
                                      err_msg=str(p))
    # zero retraces after warmup: a second stream hits the jit cache only
    warm = dict(paged_s.trace_counts)
    for p in _rand_prompts(rng, 6, lo=3, hi=16):
        paged_s.submit(p)
    paged_s.run()
    assert dict(paged_s.trace_counts) == warm


def test_paged_greedy_decode_deterministic(dense):
    api, params = dense
    srv = Server(api, params, ServeConfig(max_new_tokens=6, paged=True,
                                          block_size=8))
    rng = np.random.default_rng(9)
    prompts = np.full((3, 12), PAD_ID, np.int32)
    for i, l in enumerate((12, 7, 4)):
        prompts[i, :l] = rng.integers(4, VOCAB, l)
    g1 = srv.generate(prompts)
    g2 = srv.generate(prompts)
    assert g1.shape == (3, 6)
    assert np.array_equal(g1, g2)


def test_paged_padded_prompt_decodes_bit_equal_to_trimmed(dense):
    api, params = dense
    srv = Server(api, params, ServeConfig(max_new_tokens=6, paged=True,
                                          block_size=8))
    plain = Server(api, params, ServeConfig(max_new_tokens=6))
    rng = np.random.default_rng(10)
    for l in (3, 5, 9):
        prompts = np.full((2, 12), PAD_ID, np.int32)
        prompts[0] = rng.integers(4, VOCAB, 12)
        prompts[1, :l] = rng.integers(4, VOCAB, l)
        padded = srv.generate(prompts)
        trimmed = srv.generate(prompts[1:2, :l])
        assert np.array_equal(padded[1], trimmed[0]), l
        # and the paged Server agrees with the dense one bit-for-bit
        assert np.array_equal(padded, plain.generate(prompts)), l


def test_scheduler_rejects_oversized_prompt_and_cache():
    api = _stub_api()
    sched = ContinuousScheduler(api, {}, SchedulerConfig(
        batch=2, buckets=(8,)))
    with pytest.raises(ValueError, match="largest bucket"):
        sched.submit(np.full(9, 7, np.int32))
    with pytest.raises(ValueError, match="overflows"):
        # per-request budget that would decode past the KV cache
        sched.submit(np.full(8, 7, np.int32), max_new_tokens=1000)
    with pytest.raises(ValueError, match="max_cache_len"):
        ContinuousScheduler(api, {}, SchedulerConfig(batch=2, buckets=(64,)))
