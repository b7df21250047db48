import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import SessionSequences, code_to_codepoint, codepoint_to_code
from repro.core import varint
from repro.core.sessionize import PAD_CODE


def _seqs(rows):
    s = len(rows)
    max_len = max(len(r) for r in rows)
    symbols = np.full((s, max_len), PAD_CODE, np.int32)
    for i, r in enumerate(rows):
        symbols[i, :len(r)] = r
    return SessionSequences(
        symbols=symbols, length=np.array([len(r) for r in rows], np.int32),
        user_id=np.arange(s, dtype=np.int64),
        session_id=np.arange(s, dtype=np.int64),
        ip=np.zeros(s, np.int64), start_ts=np.zeros(s, np.int64),
        duration_s=np.zeros(s, np.int32))


@given(st.lists(st.lists(st.integers(0, 70_000), min_size=1, max_size=20),
                min_size=1, max_size=10))
@settings(max_examples=50, deadline=None)
def test_unicode_string_roundtrip(rows):
    seqs = _seqs(rows)
    strs = seqs.as_unicode_strings()
    back = SessionSequences.from_unicode_strings(strs)
    for i, r in enumerate(rows):
        assert back.session_symbols(i).tolist() == r


def test_surrogate_range_is_skipped():
    # codes near the surrogate block must map to VALID code points
    codes = np.array([0xD7FF, 0xD800, 0xDFFF, 0xE000], np.int64)
    cps = code_to_codepoint(codes)
    assert all(not (0xD800 <= int(c) <= 0xDFFF) for c in cps)
    assert np.array_equal(codepoint_to_code(cps), codes)
    # and every produced char is encodable
    "".join(chr(int(c)) for c in cps).encode("utf-8")


@given(st.lists(st.integers(0, 70_000), min_size=1, max_size=50))
@settings(max_examples=50, deadline=None)
def test_varint_roundtrip(codes):
    data = varint.encode_session(np.asarray(codes))
    assert np.array_equal(varint.decode_session(data), np.asarray(codes))


def test_variable_length_coding_property():
    """Paper §4.2: smaller code points need fewer bytes — so frequent
    (small) codes compress better than rare (large) ones."""
    small = varint.encode_session(np.zeros(100, np.int64))       # code 0
    large = varint.encode_session(np.full(100, 60_000, np.int64))
    assert len(small) == 100      # 1 byte each
    assert len(large) == 300      # 3 bytes each
    assert len(small) < len(large)


def test_session_symbols_clamp_each_row_to_the_grid():
    seqs = _seqs([[1, 2, 3], [4], [0x80, 0x800, 0x10000]])
    seqs.length[:] = [3, 9, 1]     # row 1 truncated: longer than the grid
    assert [seqs.session_symbols(i).tolist() for i in range(3)] == \
        [[1, 2, 3], [4, PAD_CODE, PAD_CODE], [0x80]]
    seqs.symbols[1, 1:] = [5, 6]
    assert seqs.as_unicode_strings() == ["\x01\x02\x03", "\x04\x05\x06",
                                         "\x80"]
    assert varint.encode_store(seqs) == [
        varint.encode_session(seqs.session_symbols(i)) for i in range(3)]


def test_encoded_size_accounts_masks():
    seqs = _seqs([[0, 1, 2], [5]])
    assert varint.encoded_size_bytes(seqs) == 4  # 4 symbols x 1 byte


def test_save_load_atomic(tmp_path):
    seqs = _seqs([[1, 2, 3], [4, 5]])
    path = str(tmp_path / "seqs.npz")
    seqs.save(path)
    back = SessionSequences.load(path)
    assert np.array_equal(back.symbols, seqs.symbols)
    assert np.array_equal(back.length, seqs.length)
    # no stray temp files (atomic rename)
    assert sorted(p.name for p in tmp_path.iterdir()) == ["seqs.npz"]
