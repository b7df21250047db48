"""Host spans and device scopes of the log tier (repro.core.spans): each
span once per step, nested as documented, with counts equal to the
program's own numbers; the same spans in a profiler trace; outputs
unchanged by the profiler; every scope in the compiled tick and day job;
the span buffer's fixed length."""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spans
from repro.data.distpipe import DistPipelineConfig, make_distributed_pipeline
from repro.data.store import Store
from repro.data.streampipe import (StreamConfig, make_stream_pipeline,
                                   single_host_stream)
from repro.dist.compat import enable_x64, use_mesh

GAP = 30 * 60 * 1000
A, L, STAGES = 16, 32, [[1], [2, 3], [4]]
TICK = ["streampipe.put", "streampipe.dispatch", "streampipe.wait",
        "streampipe.pull", "store.append_sessions", "streampipe.fold"]
STORE = ["store.encode_payloads", "store.encode_columns", "store.index"]
DAY = ["distpipe.put", "distpipe.dispatch", "distpipe.wait",
       "distpipe.pull"]
SCOPES = ["repartition", "dedup", "sessionize", "sort", "segments", "grid",
          "ring", "rollup/ngram", "rollup/funnel", "dedup/sort",
          "sessionize/sort"]


def _events(n, seed=0, n_users=10):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_users, n).astype(np.int64) * 7919,
            rng.integers(0, 2, n).astype(np.int64),
            np.sort(rng.integers(0, 4 * 10**7, n)).astype(np.int64),
            rng.integers(0, A, n).astype(np.int32),
            rng.integers(0, 1 << 32, n).astype(np.int64))


def _mesh():
    return jax.sharding.Mesh(jax.devices()[:1], ("data",))


def _stream_cfg():
    return StreamConfig(alphabet_size=A, max_open=32, max_len=L,
                        tick_capacity=64)


def _since(t0):
    return [r for r in spans.recent() if r.start_ns >= t0]


def _one(recs, name):
    got = [r for r in recs if r.name == name]
    assert len(got) == 1, (name, got)
    return got[0]


def _tick_args(sp):
    """The device tick's arguments for a tick of empty rows."""
    n = sp.cfg.tick_capacity
    z = np.zeros(n, np.int64)
    ev = dict(user_id=z, session_id=z, timestamp=z, code=z.astype(np.int32),
              ip=z, valid=np.ones(n, bool))
    wm = (jnp.asarray(0, jnp.int64), jnp.asarray(1, jnp.int64))
    if hasattr(sp, "mesh"):
        return (sp._ring, *ev.values(), *wm, sp._stage_tab)
    return (sp._ring, ev, *wm, sp._stage_tab)


def _day_args(dp, n=256):
    z = np.zeros(n, np.int64)
    return (z, z, z, z.astype(np.int32), z, np.ones(n, bool),
            jnp.asarray(dp.stage_table))


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("kind", ["mesh", "single_host"])
def test_tick_spans_nest_in_order_with_the_programs_counts(kind):
    cfg = _stream_cfg()
    sp = (make_stream_pipeline(_mesh(), cfg, STAGES) if kind == "mesh"
          else single_host_stream(cfg, STAGES))
    u, s, t, c, i = _events(50)
    traces = sp.trace_counts["tick"]
    t0 = time.perf_counter_ns()
    res = sp.tick(u, s, t, c, i, watermark=int(t.max()) + 2 * GAP)
    recs = _since(t0)
    assert res.closed_sessions > 0
    assert sorted(r.name for r in recs) == sorted(
        ["streampipe.tick"] + TICK + STORE)
    tick = _one(recs, "streampipe.tick")
    assert tick.parent is None and tick.counts == dict(events=50, flush=0)
    inner = sorted((r for r in recs if r.parent == "streampipe.tick"),
                   key=lambda r: r.start_ns)
    assert [r.name for r in inner] == TICK
    assert all(tick.start_ns <= r.start_ns <= r.end_ns <= tick.end_ns
               for r in recs)
    assert all(a.end_ns <= b.start_ns for a, b in zip(inner, inner[1:]))
    assert [r.name for r in sorted(
        (r for r in recs if r.parent == "store.append_sessions"),
        key=lambda r: r.start_ns)] == STORE
    assert _one(recs, "streampipe.dispatch").counts == dict(traces=traces)
    # the pull takes every output but the ring and the closed counts
    with enable_x64():
        _, cb, _, *rest = jax.eval_shape(sp._tick_jit, *_tick_args(sp))
    assert _one(recs, "streampipe.pull").counts == dict(
        bytes=_nbytes((cb, rest)), sessions=res.closed_sessions)
    stored = sp.store.segments[-1]
    assert _one(recs, "store.append_sessions").counts == dict(
        sessions=res.closed_sessions, events=stored.n_events)
    assert _one(recs, "store.encode_payloads").counts == dict(
        bytes=stored.col_bytes["payload"])


def test_flush_tick_is_marked_and_a_later_tick_reports_its_traces():
    sp = make_stream_pipeline(_mesh(), _stream_cfg(), STAGES)
    u, s, t, c, i = _events(40)
    sp.tick(u[:20], s[:20], t[:20], c[:20], i[:20])
    t0 = time.perf_counter_ns()
    sp.tick(u[20:], s[20:], t[20:], c[20:], i[20:])
    sp.flush()
    ticks = [r for r in _since(t0) if r.name == "streampipe.tick"]
    assert [r.counts for r in ticks] == [dict(events=20, flush=0),
                                         dict(events=0, flush=1)]
    assert [r.counts["traces"] for r in _since(t0)
            if r.name == "streampipe.dispatch"] == [1, 1]


def test_day_spans_nest_in_order_with_the_programs_counts():
    cfg = DistPipelineConfig(alphabet_size=A, max_sessions_per_shard=64,
                             max_len=L)
    dp = make_distributed_pipeline(_mesh(), cfg, STAGES)
    store = Store()
    u, s, t, c, i = _events(300)
    t0 = time.perf_counter_ns()
    res = dp(u, s, t, c, i)
    seqs = res.to_sequences()
    seg = store.append_sessions(seqs)
    recs = _since(t0)
    assert sorted(r.name for r in recs) == sorted(
        ["distpipe.call", "distpipe.gather", "store.append_sessions"]
        + DAY + STORE)
    call = _one(recs, "distpipe.call")
    assert call.parent is None and call.counts == dict(events=300)
    inner = sorted((r for r in recs if r.parent == "distpipe.call"),
                   key=lambda r: r.start_ns)
    assert [r.name for r in inner] == DAY
    assert all(a.end_ns <= b.start_ns for a, b in zip(inner, inner[1:]))
    with enable_x64(), use_mesh(dp.mesh):
        out = jax.eval_shape(dp._jitted, *_day_args(dp, 300))
    assert _one(recs, "distpipe.pull").counts == dict(bytes=_nbytes(out))
    gather = _one(recs, "distpipe.gather")
    assert gather.parent is None and gather.start_ns >= call.end_ns
    assert gather.counts == dict(sessions=res.num_sessions())
    assert _one(recs, "store.append_sessions").counts == dict(
        sessions=len(seqs), events=seg.n_events)
    assert _one(recs, "store.encode_payloads").counts == dict(
        bytes=seg.col_bytes["payload"])


def _replay(sp, ticks):
    out = [sp.tick(*cols) for cols in ticks] + [sp.flush()]
    seqs = sp.sessions()
    return ([r.closed_sessions for r in out], sp.ngram_totals.copy(),
            sp.reach_totals.copy(), seqs.symbols, seqs.user_id,
            seqs.start_ts)


def test_profiler_shows_the_spans_and_leaves_outputs_bit_equal(tmp_path):
    from jax.profiler import ProfileData
    u, s, t, c, i = _events(120, seed=3)
    ticks = [(u[a:a + 40], s[a:a + 40], t[a:a + 40], c[a:a + 40],
              i[a:a + 40]) for a in range(0, 120, 40)]
    off = _replay(make_stream_pipeline(_mesh(), _stream_cfg(), STAGES),
                  ticks)
    sp = make_stream_pipeline(_mesh(), _stream_cfg(), STAGES)
    t0 = time.perf_counter_ns()
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = _replay(sp, ticks)
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)

    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    traced = [(e.name, dict(e.stats))
              for p in ProfileData.from_file(path).planes
              if not p.name.startswith("/device:")
              for ln in p.lines for e in ln.events
              if e.name.startswith(("streampipe.", "store."))]
    recorded = [(r.name, r.counts) for r in _since(t0)]
    assert sorted(traced, key=repr) == sorted(recorded, key=repr)
    assert ("streampipe.tick", dict(events=0, flush=1)) in traced


def _op_names(compiled_text):
    """``(opcode, op_name)`` of every instruction carrying an op_name."""
    out = []
    for line in compiled_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r"\s([a-z][\w\-]*)\(", line.split(" = ", 1)[-1])
        if m and op and " = " in line:
            out.append((op.group(1), m.group(1)))
    return out


def _scopes(op_name):
    """The scope path of an op_name, without jit wrappers or the op."""
    parts = [p for p in op_name.split("/")[:-1]
             if not p.startswith("jit(")]
    return "/".join(parts)


def _check_scopes(text, expected):
    ops = _op_names(text)
    paths = {_scopes(n) for _, n in ops}
    for scope in expected:
        assert any(re.search(rf"(^|/){re.escape(scope)}(/|$)", p)
                   for p in paths), (scope, sorted(paths))
    sorts = [n for op, n in ops if op == "sort"]
    assert sorts
    assert all("sort" in _scopes(n).split("/") for n in sorts), sorts


def test_compiled_tick_and_day_carry_every_scope():
    cfg = _stream_cfg()
    sp = make_stream_pipeline(_mesh(), cfg, STAGES)
    with enable_x64(), use_mesh(sp.mesh):
        tick = sp._tick_jit.lower(*_tick_args(sp)).compile().as_text()
    _check_scopes(tick, SCOPES)

    dcfg = DistPipelineConfig(alphabet_size=A, max_sessions_per_shard=64,
                              max_len=L)
    dp = make_distributed_pipeline(_mesh(), dcfg, STAGES)
    with enable_x64(), use_mesh(dp.mesh):
        day = dp._jitted.lower(*_day_args(dp)).compile().as_text()
    _check_scopes(day, [sc for sc in SCOPES if sc != "ring"])


def test_buffer_keeps_its_fixed_length():
    t0 = time.perf_counter_ns()
    for j in range(spans.RECENT + 10):
        with spans.span("test.fill", j=j):
            pass
    recs = spans.recent()
    assert len(recs) == spans.RECENT
    assert [r.counts["j"] for r in recs] == list(range(10, spans.RECENT
                                                       + 10))
    assert all(r.start_ns >= t0 for r in recs)


def test_a_span_closes_and_records_when_its_block_raises():
    t0 = time.perf_counter_ns()
    with pytest.raises(ValueError):
        with spans.span("test.outer"):
            with spans.span("test.inner", rows=3):
                raise ValueError
    with spans.span("test.after"):
        pass
    recs = {r.name: r for r in _since(t0)}
    assert recs["test.inner"].parent == "test.outer"
    assert recs["test.inner"].counts == dict(rows=3)
    assert recs["test.after"].parent is None


def test_counts_set_inside_the_block_are_recorded(tmp_path):
    from jax.profiler import ProfileData
    t0 = time.perf_counter_ns()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("test.late", rows=2) as counts:
            counts["bytes"] = 9
    finally:
        jax.profiler.stop_trace()
    assert _one(_since(t0), "test.late").counts == dict(rows=2, bytes=9)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    traced = [dict(e.stats) for p in ProfileData.from_file(path).planes
              for ln in p.lines for e in ln.events if e.name == "test.late"]
    assert traced == [dict(rows=2, bytes=9)]
