"""Byte-level variable-length materialization of session sequences (§4.2).

The paper's coding trick: frequent events get small unicode code points,
which need fewer bytes in UTF-8 — variable-length coding for free. We
reproduce it exactly: codes -> (surrogate-skipping) code points -> UTF-8.
The compression benchmark (benchmarks/compression.py) measures this against
the raw client-event log representation to validate the ~50x claim.

Also here: the vectorized LEB128 codecs the segment store
(``repro.data.store``) builds its columnar blobs from — unsigned varints
for counts/deltas and zigzag varints for signed id columns. Both encoder
and decoder are numpy-vectorized over the whole column (a python loop only
over the <=10 byte positions of the widest value), so encoding a segment
costs a handful of array passes, not a per-value interpreter loop.
"""
from __future__ import annotations

import numpy as np

from .sequences import SessionSequences, code_to_codepoint, codepoint_to_code

_U64_ONE = np.uint64(1)


def encode_uvarint(values) -> bytes:
    """LEB128-encode a non-negative int column (vectorized).

    Each value takes ``ceil(bit_length / 7)`` bytes, low 7 bits first, high
    bit of every byte but the last set (the protobuf/Thrift wire format).
    """
    v = np.ascontiguousarray(np.asarray(values).astype(np.uint64))
    if v.ndim != 1:
        v = v.reshape(-1)
    if v.size == 0:
        return b""
    n_bytes = np.ones(v.shape, np.int64)
    for k in range(1, 10):
        n_bytes += (v >= (_U64_ONE << np.uint64(7 * k))).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(n_bytes)[:-1]])
    out = np.zeros(int(starts[-1] + n_bytes[-1]), np.uint8)
    for k in range(int(n_bytes.max())):
        m = n_bytes > k
        byte = ((v[m] >> np.uint64(7 * k)) & np.uint64(0x7F)).astype(np.uint8)
        cont = (n_bytes[m] > k + 1).astype(np.uint8) << 7
        out[starts[m] + k] = byte | cont
    return out.tobytes()


def decode_uvarint(buf: bytes | np.ndarray, count: int,
                   offset: int = 0) -> tuple[np.ndarray, int]:
    """Decode ``count`` LEB128 values from ``buf[offset:]`` (vectorized).

    Returns ``(values uint64, next_offset)`` so column blocks can be read
    back to back from one segment blob.
    """
    if count == 0:
        return np.zeros(0, np.uint64), offset
    b = np.frombuffer(buf, np.uint8, offset=0)[offset:]
    ends = np.flatnonzero((b & 0x80) == 0)
    if len(ends) < count:
        raise ValueError(f"uvarint blob truncated: {len(ends)} terminators "
                         f"< {count} values")
    ends = ends[:count]
    starts = np.concatenate([[0], ends[:-1] + 1])
    widths = ends - starts + 1
    v = np.zeros(count, np.uint64)
    for k in range(int(widths.max())):
        m = widths > k
        v[m] |= ((b[starts[m] + k].astype(np.uint64)) & np.uint64(0x7F)) \
            << np.uint64(7 * k)
    return v, offset + int(ends[-1]) + 1


def zigzag(values) -> np.ndarray:
    """int64 -> uint64 zigzag map (small magnitudes -> small uvarints)."""
    v = np.asarray(values).astype(np.int64)
    return ((v << 1) ^ (v >> 63)).view(np.uint64)


def unzigzag(values: np.ndarray) -> np.ndarray:
    u = np.asarray(values, np.uint64)
    return ((u >> _U64_ONE).view(np.int64)) ^ -((u & _U64_ONE).view(np.int64))


def encode_ivarint(values) -> bytes:
    """Zigzag + LEB128 for signed columns (user/session ids)."""
    return encode_uvarint(zigzag(values))


def decode_ivarint(buf, count: int, offset: int = 0
                   ) -> tuple[np.ndarray, int]:
    u, offset = decode_uvarint(buf, count, offset)
    return unzigzag(u), offset


def utf8_length(codepoints: np.ndarray) -> np.ndarray:
    """Bytes per code point under UTF-8 (vectorized)."""
    cp = np.asarray(codepoints, np.int64)
    return np.where(cp < 0x80, 1,
                    np.where(cp < 0x800, 2,
                             np.where(cp < 0x10000, 3, 4))).astype(np.int64)


def encoded_size_bytes(seqs: SessionSequences) -> int:
    """Total UTF-8 bytes to store all session_sequence strings."""
    mask = seqs.mask()
    cps = code_to_codepoint(np.where(mask, seqs.symbols, 0))
    return int((utf8_length(cps) * mask).sum())


def encode_session(symbols: np.ndarray) -> bytes:
    """One session's symbols -> UTF-8 bytes (a valid unicode string)."""
    cps = code_to_codepoint(np.asarray(symbols, np.int64))
    return "".join(chr(int(c)) for c in cps).encode("utf-8")


# UTF-8 lead-byte marks by encoded width (index 0 unused)
_UTF8_LEAD = np.array([0, 0x00, 0xC0, 0xE0, 0xF0], np.int64)
_MAX_CODEPOINT = 0x10FFFF


def encode_sessions(seqs: SessionSequences
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Every session's UTF-8 string in one array pass over the grid.

    Returns ``(codes, payload, payload_len)``: the stored codes in
    row-major order, the sessions' UTF-8 bytes back to back (uint8), and
    the bytes of each session. Row ``j``'s slice of ``payload`` equals
    ``encode_session`` of its stored symbols, byte for byte; a code whose
    code point ``chr`` would refuse (``PAD_CODE`` inside a stored length)
    raises ``ValueError`` here too.
    """
    stored = seqs.stored_length().astype(np.int64)
    row_end = np.cumsum(stored)
    # flat index of each stored symbol: its row's offset in the grid, minus
    # the symbols stored before the row, plus its rank among all of them
    skip = np.arange(len(stored)) * seqs.max_len - (row_end - stored)
    flat = np.arange(int(stored.sum())) + np.repeat(skip, stored)
    codes = np.asarray(seqs.symbols).reshape(-1)[flat]
    cps = code_to_codepoint(codes.astype(np.int64))
    bad = (cps < 0) | (cps > _MAX_CODEPOINT)
    if bad.any():
        k = int(np.argmax(bad))
        row = int(np.searchsorted(row_end, k, side="right"))
        raise ValueError(f"session {row}: code {int(codes[k])} maps to code "
                         f"point {int(cps[k])}, not in range(0x110000)")
    width = utf8_length(cps)
    ends = np.cumsum(width)
    start = ends - width
    payload = np.empty(int(width.sum()), np.uint8)
    tail = 6 * (width - 1)          # bits below the lead byte
    payload[start] = (_UTF8_LEAD[width] | (cps >> tail)).astype(np.uint8)
    for k in range(1, int(width.max(initial=1))):
        m = width > k
        payload[start[m] + k] = (0x80 | ((cps[m] >> (tail[m] - 6 * k)) & 0x3F)
                                 ).astype(np.uint8)
    payload_len = np.diff(np.concatenate([[0], ends])[row_end],
                          prepend=np.int64(0))
    return codes, payload, payload_len


def decode_session(data: bytes) -> np.ndarray:
    cps = np.array([ord(ch) for ch in data.decode("utf-8")], np.int64)
    return codepoint_to_code(cps).astype(np.int32)


def encode_store(seqs: SessionSequences) -> list[bytes]:
    """Each session's UTF-8 bytes, split from ``encode_sessions``."""
    _, payload, payload_len = encode_sessions(seqs)
    ends = np.cumsum(payload_len)
    return [payload[a:b].tobytes() for a, b in zip(ends - payload_len, ends)]


def raw_log_size_bytes(num_events: int, mean_name_len: float,
                       mean_details_len: float = 64.0) -> int:
    """Model of the raw client-event Thrift record footprint, per §3.2
    Table 2: initiator(1) + name(string) + user_id(8) + session_id(8) +
    ip(4) + timestamp(8) + details(string) + Thrift field headers (~3 bytes
    per field x 7 fields).
    """
    per_event = 1 + mean_name_len + 8 + 8 + 4 + 8 + mean_details_len + 21
    return int(num_events * per_event)
