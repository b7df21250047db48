"""Unified segment store (repro.data.store): varint + segment round trips,
time-based compaction vs the batch-pipeline oracle, metadata pruning
exactness (a filtered scan must equal the unfiltered scan post-filtered,
while decoding strictly fewer segments), and the consumers that read
through the store — streampipe, the LM batch pipeline, and the catalog."""
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.core import sessionize, varint, SessionSequences
from repro.core.sessionize import PAD_CODE
from repro.data.distpipe import single_host_pipeline
from repro.data.store import (Store, StoreConfig, concat_sequences,
                              SESSION_COLS, decode_event_segment,
                              decode_session_segment,
                              encode_event_segment, encode_session_segment,
                              scan_matches_sessions, user_shard_mask,
                              _take_rows)
from repro.data.streampipe import session_multiset, split_ticks

GAP = 30 * 60 * 1000  # DEFAULT_GAP_MS
U64 = (1 << 64) - 1
I64_MIN, I64_MAX = -(1 << 63), (1 << 63) - 1


def _events(n, seed, n_users=10, ts_hi=4 * GAP, dup_frac=0.25):
    """Random event columns with exact 5-tuple duplicates mixed in (the
    at-least-once retries the store's dedup must collapse)."""
    rng = np.random.default_rng(seed)
    user = rng.integers(0, n_users, n).astype(np.int64) * 7919
    sess = rng.integers(0, 3, n).astype(np.int64)
    ts = rng.integers(0, ts_hi, n).astype(np.int64)
    code = rng.integers(0, 16, n).astype(np.int32)
    ip = rng.integers(0, 1 << 32, n).astype(np.int64)
    dup = rng.integers(0, n, max(1, int(n * dup_frac)))
    cols = tuple(np.concatenate([a, a[dup]])
                 for a in (user, sess, ts, code, ip))
    perm = rng.permutation(len(cols[0]))
    return tuple(a[perm] for a in cols)


def _write(store, cols, n_writes=4):
    u, s, t, c, i = cols
    for ix in split_ticks(t, n_writes):
        store.append_events(u[ix], s[ix], t[ix], c[ix], i[ix])
    return store


def _oracle(cols, *, max_len=64, dedup=True):
    u, s, t, c, i = cols
    sz = sessionize(u, s, t, c, i, gap_ms=GAP, dedup=dedup,
                    max_sessions=len(u), max_len=max_len)
    return SessionSequences.from_sessionized(sz)


# ---------------------------------------------------------------------------
# varint codecs
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, U64), max_size=40))
def test_uvarint_round_trip(vals):
    a = np.array(vals, np.uint64)
    buf = varint.encode_uvarint(a)
    out, end = varint.decode_uvarint(buf, len(a))
    assert end == len(buf)
    assert np.array_equal(out, a)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(I64_MIN, I64_MAX), max_size=40))
def test_ivarint_round_trip(vals):
    a = np.array(vals, np.int64)
    buf = varint.encode_ivarint(a)
    out, end = varint.decode_ivarint(buf, len(a))
    assert end == len(buf)
    assert np.array_equal(out, a)


def test_varint_extremes_and_truncation():
    a = np.array([0, 1, 127, 128, 255, U64, U64 - 1], np.uint64)
    buf = varint.encode_uvarint(a)
    assert np.array_equal(varint.decode_uvarint(buf, len(a))[0], a)
    b = np.array([I64_MIN, I64_MAX, 0, -1, 1], np.int64)
    assert np.array_equal(
        varint.decode_ivarint(varint.encode_ivarint(b), len(b))[0], b)
    with pytest.raises(ValueError):
        varint.decode_uvarint(buf[:-1], len(a))
    with pytest.raises(ValueError):
        varint.decode_uvarint(b"\x80\x80", 1)  # no terminator byte


# ---------------------------------------------------------------------------
# segment round trips + metadata
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(1, 200), st.integers(0, 10_000))
def test_event_segment_round_trip(n, seed):
    u, s, t, c, i = _events(n, seed)
    seg = encode_event_segment(7, u, s, t, c, i)
    cols = decode_event_segment(seg)
    order = np.argsort(t, kind="stable")  # rows store time-sorted
    assert np.array_equal(cols["timestamp"], t[order])
    assert np.array_equal(cols["user_id"], u[order])
    assert np.array_equal(cols["session_id"], s[order])
    assert np.array_equal(cols["code"], c[order])
    assert np.array_equal(cols["ip"], i[order])
    assert seg.min_ts == int(t.min()) and seg.max_ts == int(t.max())
    assert seg.n == len(t) and seg.n_events == len(t)


def test_event_segment_metadata():
    u, s, t, c, i = _events(300, seed=5)
    seg = encode_event_segment(0, u, s, t, c, i)
    codes, counts = np.unique(c, return_counts=True)
    assert seg.code_counts == {int(k): int(v)
                               for k, v in zip(codes, counts)}
    for uid in np.unique(u):  # every present user sets its shard bit
        assert seg.user_mask & user_shard_mask(np.array([uid]))
    # ip=None stores zeros
    seg0 = encode_event_segment(1, u, s, t, c, None)
    assert not decode_event_segment(seg0)["ip"].any()


@settings(max_examples=15, deadline=None)
@given(st.integers(4, 300), st.integers(0, 10_000))
def test_session_segment_round_trip(n, seed):
    seqs = _oracle(_events(n, seed))
    seg = encode_session_segment(3, seqs)
    got = decode_session_segment(seg)
    # row order is preserved exactly (streampipe's readback contract),
    # only the padded width may shrink to the longest stored row
    assert np.array_equal(got.user_id, seqs.user_id)
    assert np.array_equal(got.start_ts, seqs.start_ts)
    assert session_multiset(got) == session_multiset(seqs)
    assert seg.n == len(seqs)
    assert seg.n_events == int(seqs.stored_length().sum())
    wide = decode_session_segment(seg, min_width=512)
    assert wide.symbols.shape[1] == 512
    assert session_multiset(wide) == session_multiset(seqs)


# largest code whose code point chr accepts (codes skip the surrogates)
MAX_CODE = 0x10FFFF - 0x800


def _grid(rows, lengths=None, width=None):
    """SessionSequences over explicit rows; ``lengths`` may exceed the
    grid's ``width`` (a truncated session keeps its true length)."""
    lengths = [len(r) for r in rows] if lengths is None else lengths
    width = max([len(r) for r in rows], default=0) if width is None \
        else width
    s = len(rows)
    symbols = np.full((s, width), PAD_CODE, np.int32)
    for j, r in enumerate(rows):
        symbols[j, : min(len(r), width)] = r[:width]
    rng = np.random.default_rng(s)
    return SessionSequences(
        symbols=symbols, length=np.asarray(lengths, np.int32),
        user_id=rng.integers(-(1 << 40), 1 << 40, s).astype(np.int64),
        session_id=rng.integers(0, 1 << 20, s).astype(np.int64),
        ip=rng.integers(0, 1 << 32, s).astype(np.int64),
        start_ts=np.sort(rng.integers(0, 1 << 41, s)).astype(np.int64),
        duration_s=rng.integers(0, 7200, s).astype(np.int32))


def _rows(seed, lo, hi, n=40, min_len=1, max_len=12):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(lo, hi, rng.integers(min_len, max_len + 1)))
            for _ in range(n)]


# each case: a width-bounded grid, built from (rows, lengths, width)
SEGMENT_CASES = {
    "one_byte": lambda: (_rows(1, 0, 0x80), None, None),
    "two_byte": lambda: (_rows(2, 0x80, 0x800), None, None),
    "three_byte": lambda: (_rows(3, 0x800, 0x10000 - 0x800), None, None),
    "four_byte": lambda: (_rows(4, 0x10000 - 0x800, MAX_CODE + 1), None,
                          None),
    "mixed_widths": lambda: (_rows(5, 0, MAX_CODE + 1), None, None),
    "surrogate_shift": lambda: ([[0xD800 - 1, 0xD800], [0xD800],
                                 [0xD800 - 1], [0, MAX_CODE]], None, None),
    "clamped_to_max_len": lambda: (_rows(6, 0, 0x900, min_len=8,
                                         max_len=30), [45] * 40, 8),
    "zero_length": lambda: ([[], [7, 300], [], [0x20000], []], None, None),
    "zero_sessions": lambda: ([], None, 4),
    "one_session": lambda: ([[3, 0x7FF, 0xFFFF - 0x800, 0x10000]], None,
                            None),
}


def _reference_segment(seqs):
    """The session blob built session by session from
    ``varint.encode_session``, and its column sizes."""
    stored = seqs.stored_length()
    payloads = [varint.encode_session(seqs.symbols[j, : stored[j]])
                for j in range(len(seqs))]
    blocks = [
        varint.encode_ivarint(np.diff(seqs.start_ts, prepend=np.int64(0))),
        varint.encode_ivarint(seqs.user_id),
        varint.encode_ivarint(seqs.session_id),
        varint.encode_ivarint(seqs.ip),
        varint.encode_uvarint(seqs.duration_s),
        varint.encode_uvarint(seqs.length),
        varint.encode_uvarint([len(p) for p in payloads])]
    col_bytes = dict(zip(SESSION_COLS, map(len, blocks)),
                     payload=sum(map(len, payloads)))
    return b"".join(blocks + payloads), col_bytes, payloads


@pytest.mark.parametrize("case", sorted(SEGMENT_CASES))
def test_session_blob_is_byte_identical_to_per_session_encoding(case):
    seqs = _grid(*SEGMENT_CASES[case]())
    blob, col_bytes, payloads = _reference_segment(seqs)
    seg = encode_session_segment(3, seqs)
    assert seg.blob == blob
    assert seg.col_bytes == col_bytes
    appended = Store().append_sessions(seqs)
    assert (appended.blob, appended.col_bytes) == (blob, col_bytes)
    assert varint.encode_store(seqs) == payloads
    stored = seqs.stored_length()
    codes = [int(c) for j in range(len(seqs))
             for c in seqs.symbols[j, : stored[j]]]
    assert seg.code_counts == appended.code_counts == \
        {c: codes.count(c) for c in sorted(set(codes))}
    back = decode_session_segment(seg)
    assert session_multiset(back) == session_multiset(seqs)
    assert np.array_equal(back.length, seqs.length)


@pytest.mark.parametrize("bad_code", [PAD_CODE, MAX_CODE + 1])
def test_session_code_outside_unicode_raises_like_chr(bad_code):
    seqs = _grid([[1, 2, 3], [], [bad_code, 4, 5]])
    with pytest.raises(ValueError):
        varint.encode_session(seqs.symbols[2])
    with pytest.raises(ValueError, match="session 2"):
        encode_session_segment(0, seqs)
    store = Store()
    with pytest.raises(ValueError):
        store.append_sessions(seqs)
    assert not store.segments
    # a symbol past its row's stored length is never encoded
    seqs.length[2] = 0
    seqs.symbols[0, 2] = bad_code
    seqs.length[0] = 2
    back = decode_session_segment(encode_session_segment(0, seqs))
    assert back.symbols[0, :2].tolist() == [1, 2]
    assert back.length.tolist() == [2, 0, 0]


@pytest.mark.parametrize("lo,hi", [(0x80, 0x800), (0x800, 0xF800),
                                   (0xF800, MAX_CODE + 1), (0, MAX_CODE + 1)])
def test_multibyte_sessions_round_trip_through_segment_and_disk(lo, hi,
                                                               tmp_path):
    seqs = _grid(*SEGMENT_CASES["clamped_to_max_len"]())
    seqs.symbols[seqs.symbols != PAD_CODE] = np.random.default_rng(
        lo).integers(lo, hi, int((seqs.symbols != PAD_CODE).sum()))
    seg = encode_session_segment(3, seqs)
    got = decode_session_segment(seg)
    assert np.array_equal(got.user_id, seqs.user_id)
    assert session_multiset(got) == session_multiset(seqs)
    store = Store()
    store.append_sessions(seqs)
    store.save(str(tmp_path / "store"))
    back = Store.load(str(tmp_path / "store"))
    assert [g.blob for g in back.segments] == [seg.blob]
    assert session_multiset(back.sequences()) == session_multiset(seqs)


# ---------------------------------------------------------------------------
# compaction vs the batch oracle
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(8, 400), st.integers(0, 10_000), st.integers(1, 6))
def test_compaction_equals_batch_oracle(n, seed, n_writes):
    cols = _events(n, seed)
    store = _write(Store(StoreConfig(max_len=64)), cols, n_writes)
    assert store.events_appended == len(cols[0])
    # pre-compaction, a full scan returns every raw event bit-equal
    ev = store.scan().events
    got = sorted(zip(*(ev[k].tolist() for k in
                       ("user_id", "session_id", "timestamp", "code", "ip"))))
    assert got == sorted(zip(*(a.tolist() for a in cols)))
    store.compact()
    assert session_multiset(store.sequences()) == \
        session_multiset(_oracle(cols))
    assert all(g.kind == "sessions" for g in store.segments)


def test_incremental_watermarks_equal_full_compact():
    cols = _events(600, seed=11)
    t = cols[2]
    inc = _write(Store(StoreConfig(max_len=64)), cols, 8)
    for q in (20, 40, 60, 80):
        inc.compact(int(np.percentile(t, q)))
    inc.compact()
    full = _write(Store(StoreConfig(max_len=64)), cols, 8)
    full.compact()
    assert session_multiset(inc.sequences()) == \
        session_multiset(full.sequences())
    assert len(inc.segments) > len(full.segments)  # hourly folds, not one
    # compacting again at the same watermark is a no-op
    again = inc.compact()
    assert again.segments_in == 0 and again.sessions_out == 0


def test_watermark_only_folds_closed_prefix():
    cols = _events(400, seed=3)
    t = cols[2]
    store = _write(Store(StoreConfig(max_len=64)), cols, 4)
    st1 = store.compact(int(np.percentile(t, 50)))
    assert st1.residual_events > 0  # open tail survives as events
    kinds = {g.kind for g in store.segments}
    assert kinds == {"sessions", "events"}
    # the open tail is still queryable as raw events, and sequences()
    # refuses to serve while matching events are un-materialized
    with pytest.raises(ValueError):
        store.sequences()
    store.compact()
    assert session_multiset(store.sequences()) == \
        session_multiset(_oracle(cols))


def test_late_append_after_compaction():
    cols = _events(300, seed=9)
    store = _write(Store(StoreConfig(max_len=64)), cols, 4)
    store.compact()
    assert store.late_appended == 0
    u, s, t, c, i = _events(50, seed=10)
    u = u + 13  # disjoint users: late rows cannot extend closed sessions
    store.append_events(u, s, t, c, i)  # all behind the final watermark
    assert store.late_appended == len(t)
    store.compact()  # watermark is clamped monotone; late rows fold now
    assert session_multiset(store.sequences()) == sorted(
        session_multiset(_oracle(cols))
        + session_multiset(_oracle((u, s, t, c, i))))


# ---------------------------------------------------------------------------
# the pruning query path
# ---------------------------------------------------------------------------

def _staged_store(cols, n_writes=8):
    store = _write(Store(StoreConfig(max_len=64)), cols, n_writes)
    for q in (25, 50, 75):
        store.compact(int(np.percentile(cols[2], q)))
    store.compact()
    return store


def test_scan_time_pruning_exact_and_strict():
    cols = _events(800, seed=21)
    store = _staged_store(cols)
    full = store.scan()
    lo = int(np.percentile(cols[2], 40))
    hi = int(np.percentile(cols[2], 60))
    scan = store.scan(time_range=(lo, hi))
    keep = scan_matches_sessions(full.sequences, (lo, hi), None, None)
    assert session_multiset(scan.sequences) == \
        session_multiset(_take_rows(full.sequences, keep))
    # pruning must skip segments, not just rows (the acceptance criterion)
    assert scan.stats.segments_decoded < full.stats.segments_decoded
    assert scan.stats.pruned_time == scan.stats.segments_pruned > 0
    assert scan.stats.segments_total == \
        scan.stats.segments_decoded + scan.stats.segments_pruned


@settings(max_examples=10, deadline=None)
@given(st.integers(50, 500), st.integers(0, 10_000))
def test_scan_filters_equal_post_filtering(n, seed):
    cols = _events(n, seed)
    store = _staged_store(cols, n_writes=4)
    full = store.scan()
    uids = np.unique(cols[0])[::3]
    codes = np.arange(0, 16, 5)
    lo, hi = (int(np.percentile(cols[2], 30)),
              int(np.percentile(cols[2], 70)))
    for tr, users, events in [((lo, hi), None, None),
                              (None, uids, None),
                              (None, None, codes),
                              ((lo, hi), uids, codes)]:
        got = store.scan(time_range=tr,
                         users=None if users is None else list(users),
                         events=None if events is None else list(events))
        keep = scan_matches_sessions(
            full.sequences, tr,
            None if users is None else np.asarray(users, np.int64),
            None if events is None else np.asarray(events, np.int64))
        assert session_multiset(got.sequences) == \
            session_multiset(_take_rows(full.sequences, keep))


def test_analytics_read_through_store():
    from repro.analytics import (count_events, count_events_store,
                                 funnel_reach, funnel_reach_store,
                                 ngram_counts, ngram_counts_store)
    cols = _events(600, seed=31)
    store = _staged_store(cols)
    seqs = store.sequences()
    targets = np.array([2, 7])
    stages = [np.array([1, 2]), np.array([5])]
    assert count_events_store(store, targets, 16) == \
        count_events(seqs, targets, 16)
    assert funnel_reach_store(store, stages, 16) == \
        funnel_reach(seqs, stages, 16)
    got_k, got_c = ngram_counts_store(store, 2, 16)
    want_k, want_c = ngram_counts(seqs, 2, 16)
    assert np.array_equal(got_k, want_k) and np.array_equal(got_c, want_c)


def test_pipeline_from_store():
    from repro.data.pipeline import PipelineConfig, SessionBatchPipeline
    cols = _events(400, seed=41)
    store = _staged_store(cols)
    cfg = PipelineConfig(seq_len=32, global_batch=4, seed=7)
    a = SessionBatchPipeline.from_store(store, cfg)
    b = SessionBatchPipeline(store.sequences(), cfg)
    assert a.batches_per_epoch() == b.batches_per_epoch()
    for x, y in zip(a, b):
        for k in x:
            assert np.array_equal(x[k], y[k])
        break


# ---------------------------------------------------------------------------
# consumers: streaming tier + catalog + persistence
# ---------------------------------------------------------------------------

def test_stream_writes_segments_at_every_watermark():
    from repro.data.streampipe import (StreamConfig, replay,
                                      single_host_stream)
    cols = _events(300, seed=51, dup_frac=0.0)
    u, s, t, c, i = cols
    cfg = StreamConfig(alphabet_size=16, max_open=128, max_len=64,
                       tick_capacity=512)
    stream = single_host_stream(cfg)
    replay(stream, u, s, t, c, i, n_ticks=6, assert_closed_prefix=True)
    # every closed block became an immutable session segment; sessions()
    # reads back through the store's scan, bit-equal to the oracle
    assert all(g.kind == "sessions" for g in stream.store.segments)
    assert len(stream.store.segments) >= 1
    assert session_multiset(stream.sessions()) == \
        session_multiset(_oracle(cols, dedup=cfg.dedup))


def test_catalog_builder_incremental_equals_scratch():
    from repro.core import CatalogBuilder, EventDictionary
    from repro.data import LogGenConfig, generate
    log = generate(LogGenConfig(n_users=40, seed=7))
    b = log.batch
    d = EventDictionary.build(b.table, b.name_id)
    codes = np.asarray(d.encode_ids(b.name_id), np.int32)
    store = Store(StoreConfig(dedup=False))
    builder = CatalogBuilder(d)
    ip = b.ip.astype(np.int64)
    for ix in split_ticks(b.timestamp, 4):
        store.append_events(b.user_id[ix], b.session_id[ix],
                            b.timestamp[ix], codes[ix], ip[ix])
        builder.refresh(store)
    store.compact(int(np.percentile(b.timestamp, 50)))
    store.compact()
    inc = builder.refresh(store)
    assert builder.segments_retracted > 0  # compaction consumed segments
    scratch = CatalogBuilder(d).refresh(store)
    assert {n: e.count for n, e in inc.entries.items()} == \
        {n: e.count for n, e in scratch.entries.items()}
    total = sum(e.count for e in inc.entries.values())
    assert total == int(store.sequences().stored_length().sum())


def test_save_load_round_trip(tmp_path):
    cols = _events(300, seed=61)
    store = _staged_store(cols)
    store.save(str(tmp_path / "store"))
    back = Store.load(str(tmp_path / "store"))
    assert back.cfg == store.cfg
    assert [(g.seg_id, g.kind, g.blob) for g in back.segments] == \
        [(g.seg_id, g.kind, g.blob) for g in store.segments]
    assert session_multiset(back.sequences()) == \
        session_multiset(store.sequences())
    assert back.summary() == store.summary()


def test_user_shard_mask_matches_jax_sharding():
    from repro.dist.compat import enable_x64
    from repro.dist.collectives import shard_of_user
    uids = np.arange(0, 5000, 37, dtype=np.int64) * 7919
    with enable_x64():
        shards = np.asarray(shard_of_user(uids, 64))
    want = 0
    for sh in np.unique(shards):
        want |= 1 << int(sh)
    assert user_shard_mask(uids, 64) == want


# ---------------------------------------------------------------------------
# the full loggen day (acceptance criterion)
# ---------------------------------------------------------------------------

def test_loggen_day_through_store_equals_batch_pipeline(loggen_corpus):
    lc = loggen_corpus
    from repro.data.distpipe import DistPipelineConfig
    cfg = DistPipelineConfig(alphabet_size=lc.alphabet_size,
                             max_sessions_per_shard=lc.n_events,
                             max_len=2048)
    store = Store(StoreConfig(dedup=cfg.dedup, max_len=cfg.max_len,
                              gap_ms=cfg.gap_ms))
    for ix in split_ticks(lc.timestamp, 16):
        store.append_events(lc.user_id[ix], lc.session_id[ix],
                            lc.timestamp[ix], lc.code[ix], lc.ip[ix])
    for q in (33, 66):
        store.compact(int(np.percentile(lc.timestamp, q)))
    store.compact()
    oracle = single_host_pipeline(lc.user_id, lc.session_id, lc.timestamp,
                                  lc.code, lc.ip, cfg=cfg,
                                  max_sessions=lc.n_events)
    assert session_multiset(store.sequences()) == \
        session_multiset(oracle.sequences)
    assert not store.truncated
