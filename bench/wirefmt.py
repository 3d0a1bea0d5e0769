"""The gRPC door's bytes, written and read without the program's proto module.

`GetRateLimitsReq`/`GetRateLimitsResp` (gubernator.proto) are small enough to
serialize by hand, and doing so keeps the yardstick out of the program's
reach: the benchmark's parent process imports nothing of `gubernator_tpu`.
`key_ids` and `request_bytes` are copied from `chip_smoke.py` (commit
846d0923, `bulk_key_ids` / `bulk_request_bytes`) and generalised over the
limit; bench/tests/test_wirefmt.py pins the bytes against protobuf's own.

RateLimitReq fields: name=1 unique_key=2 hits=3 limit=4 duration=5
algorithm=6 behavior=7 created_at=10. RateLimitResp: status=1 limit=2
remaining=3 reset_time=4 error=5 metadata=6. proto3 leaves out zero values.
"""

from __future__ import annotations

import re
from typing import NamedTuple

import numpy as np

METHOD = "/pb.gubernator.V1/GetRateLimits"
TOKEN, LEAKY = 0, 1
GLOBAL, RESET_REMAINING, DRAIN_OVER_LIMIT = 2, 8, 32
ALGORITHMS = {"token": TOKEN, "leaky": LEAKY}  # a keyspace's `algorithm`
# upstream's names (gubernator.proto, enum Behavior), as a keyspace's or a
# traffic mix's `behavior` lists them; BATCHING is 0, the default
BEHAVIORS = {
    "NO_BATCHING": 1, "GLOBAL": GLOBAL, "DURATION_IS_GREGORIAN": 4,
    "RESET_REMAINING": RESET_REMAINING, "MULTI_REGION": 16,
    "DRAIN_OVER_LIMIT": DRAIN_OVER_LIMIT,
}
UNDER, OVER = 0, 1
_U64 = (1 << 64) - 1


def keyspec_algorithm(keyspec: dict) -> int:
    """The wire number of a configuration's `keyspace.algorithm` ("token"
    when the key is absent)."""
    name = keyspec.get("algorithm", "token")
    if name not in ALGORITHMS:
        raise ValueError(f"keyspace.algorithm {name!r}: one of {sorted(ALGORITHMS)}")
    return ALGORITHMS[name]


def behavior_bits(names) -> int:
    """The wire number of a list of upstream's behavior names (a
    configuration's `keyspace.behavior`, an entry of a traffic mix); no name,
    or no list, is 0."""
    bits = 0
    for name in names or ():
        if name not in BEHAVIORS:
            raise ValueError(f"behavior {name!r}: one of {sorted(BEHAVIORS)}")
        bits |= BEHAVIORS[name]
    return bits


def keyspec_behavior(keyspec: dict) -> int:
    return behavior_bits(keyspec.get("behavior"))


def varint(v: int) -> bytes:
    v &= _U64  # a negative int64 is ten bytes on the wire
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def key_ids(seed: int, idx: np.ndarray) -> np.ndarray:
    """64-bit ids of the keys with indices `idx`: an odd multiplier is a
    bijection mod 2^64, so distinct indices never collide; the seed moves
    the whole set."""
    return idx.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + np.uint64(
        seed * 0xD1B54A32D192ED03 % 2**64
    )


_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
_SHIFTS = np.arange(60, -4, -4, dtype=np.uint64)


def request_bytes(
    ids: np.ndarray, hits: int, limit: int, duration: int,
    created_at: int | None = None, name: bytes = b"bulk", algorithm: int = TOKEN,
    behavior: int = 0,
) -> bytes:
    """Serialized GetRateLimitsReq of checks on the keys `ids` (unique_key =
    16 hex digits of the id), built as one fixed-width byte matrix instead of
    n messages. `algorithm` is field 6 of every row and `behavior` field 7:
    proto3 leaves out 0 (TOKEN, BATCHING), so a plain token row's bytes do
    not know either field exists."""
    head = b"\x0a" + varint(len(name)) + name + b"\x12\x10"
    tail = b""
    if hits:
        tail += b"\x18" + varint(hits)
    tail += b"\x20" + varint(limit) + b"\x28" + varint(duration)
    if algorithm:
        tail += b"\x30" + varint(algorithm)
    if behavior:
        tail += b"\x38" + varint(behavior)
    if created_at is not None:
        tail += b"\x50" + varint(created_at)
    frame = b"\x0a" + varint(len(head) + 16 + len(tail))
    row = np.frombuffer(frame + head + bytes(16) + tail, dtype=np.uint8)
    mat = np.tile(row, (ids.shape[0], 1))
    k0 = len(frame) + len(head)
    mat[:, k0 : k0 + 16] = _HEX[((ids[:, None] >> _SHIFTS) & np.uint64(0xF)).astype(np.intp)]
    return mat.tobytes()


def encode_item(
    name: str, key: str, hits: int, limit: int, duration: int,
    algorithm: int = TOKEN, behavior: int = 0, created_at: int | None = None,
) -> bytes:
    """One framed RateLimitReq of any kind (the scripted scenarios)."""
    nb, kb = name.encode(), key.encode()
    m = b"\x0a" + varint(len(nb)) + nb + b"\x12" + varint(len(kb)) + kb
    for tag, v in ((0x18, hits), (0x20, limit), (0x28, duration),
                   (0x30, algorithm), (0x38, behavior)):
        if v:
            m += bytes([tag]) + varint(v)
    if created_at is not None:
        m += b"\x50" + varint(created_at)
    return b"\x0a" + varint(len(m)) + m


def response_bytes(rows) -> bytes:
    """GetRateLimitsResp of (status, limit, remaining, reset_time) rows, as
    the server would serialize them without error or metadata."""
    out = []
    for row in rows:
        m = b"".join(
            bytes([tag]) + varint(int(v))
            for tag, v in zip((0x08, 0x10, 0x18, 0x20), row) if v
        )
        out.append(b"\x0a" + varint(len(m)) + m)
    return b"".join(out)


class Answers(NamedTuple):
    """Decoded responses, one entry per item, in wire order. `n_items` and
    `n_errors` have one entry per response: how many items it held, and how
    many of them carried an error string."""

    n_items: np.ndarray
    n_errors: np.ndarray
    status: np.ndarray
    limit: np.ndarray
    remaining: np.ndarray
    reset_time: np.ndarray

    @property
    def errors(self) -> int:
        return int(self.n_errors.sum())


def _read_varint(data: bytes, p: int):
    v = shift = 0
    while True:
        b = data[p]
        p += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return (v - (1 << 64) if v >> 63 else v), p
        shift += 7


def decode_response_slow(data: bytes):
    """One GetRateLimitsResp by the book: a list of [status, limit,
    remaining, reset_time, error] per item. Unknown fields are skipped by
    wire type. Raises ValueError on bytes that are not such a message."""
    items, p, n = [], 0, len(data)
    try:
        while p < n:
            if data[p] != 0x0A:
                raise ValueError(f"top-level tag {data[p]:#x}")
            size, p = _read_varint(data, p + 1)
            end = p + size
            if end > n:
                raise ValueError("item runs past the message")
            row = [0, 0, 0, 0, ""]
            while p < end:
                tag, p = _read_varint(data, p)
                field, wt = tag >> 3, tag & 7
                if wt == 0:
                    v, p = _read_varint(data, p)
                    if 1 <= field <= 4:
                        row[field - 1] = v
                elif wt == 2:
                    ln, p = _read_varint(data, p)
                    if field == 5:
                        row[4] = data[p : p + ln].decode("utf-8", "replace")
                    p += ln
                elif wt == 1:
                    p += 8
                elif wt == 5:
                    p += 4
                else:
                    raise ValueError(f"wire type {wt}")
            if p != end:
                raise ValueError("item length does not match its fields")
            items.append(row)
    except IndexError:
        raise ValueError("truncated message") from None
    return items


# a denied row carries metadata {"retry_after_ms": "<digits>"}: the one
# length-delimited field a healthy answer holds. It is cut out so that what
# is left is varints only (the item's length prefix is then stale, and is
# not used).
_RETRY_META = re.compile(rb"\x32[\x00-\x7f]\x0a\x0eretry_after_ms\x12[\x00-\x7f][0-9]*")
_ITEM_TAG = 0x0A
_FIELD_TAGS = (0x08, 0x10, 0x18, 0x20)


def _decode_varint_stream(buf: bytes, offsets: np.ndarray) -> Answers:
    """`buf` is several responses end to end, each a run of (tag, varint)
    pairs; `offsets` are the byte offsets at which responses start. Raises
    ValueError when the bytes are anything else."""
    b = np.frombuffer(buf, dtype=np.uint8)
    is_end = b < 0x80
    if not is_end[-1]:
        raise ValueError("truncated varint")
    ends = np.flatnonzero(is_end)
    starts = np.empty_like(ends)
    starts[0] = 0
    starts[1:] = ends[:-1] + 1
    lens = ends - starts + 1
    if lens.max() > 10 or ends.size % 2:
        raise ValueError("not (tag, varint) pairs")
    vals = (b[starts] & 0x7F).astype(np.uint64)
    for j in range(1, int(lens.max())):
        m = np.flatnonzero(lens > j)
        vals[m] |= (b[starts[m] + j] & 0x7F).astype(np.uint64) << np.uint64(7 * j)
    tags, values = vals[0::2], vals[1::2].view(np.int64)
    if (lens[0::2] != 1).any():
        raise ValueError("a tag of more than one byte")
    is_item = tags == _ITEM_TAG
    if not np.isin(tags[~is_item], _FIELD_TAGS).all():
        raise ValueError("a field that is not status/limit/remaining/reset_time")
    # pair index at which each response starts: pairs that end before it
    pair_ends = ends[1::2]
    first = np.searchsorted(pair_ends, offsets, side="left")
    if not is_item[first].all():
        raise ValueError("a response that does not start with an item")
    item_of = np.cumsum(is_item) - 1
    n = int(is_item.sum())
    cols = []
    for tag in _FIELD_TAGS:
        col = np.zeros(n, dtype=np.int64)
        m = tags == tag
        col[item_of[m]] = values[m]
        cols.append(col)
    n_items = np.diff(np.append(item_of[first], n))
    return Answers(n_items, np.zeros_like(n_items), *cols)


def decode_responses(datas: list, chunk_bytes: int = 8 << 20) -> Answers:
    """Decode many GetRateLimitsResp at once. The quick way reads a chunk of
    responses as one stream of varints; a chunk that holds anything else (an
    error string, an unknown field) is read again message by message."""
    parts, chunk, size = [], [], 0
    for i, d in enumerate(datas):
        chunk.append(d)
        size += len(d)
        if size >= chunk_bytes or i == len(datas) - 1:
            parts.append(_decode_chunk(chunk))
            chunk, size = [], 0
    if not parts:
        parts = [Answers(*(np.zeros(0, dtype=np.int64),) * 6)]
    return Answers(*(np.concatenate(cols) for cols in zip(*parts)))


def _decode_chunk(chunk: list) -> Answers:
    clean = [
        _RETRY_META.sub(b"", d) if b"retry_after_ms" in d else d for d in chunk
    ]
    lens = np.fromiter((len(d) for d in clean), dtype=np.int64, count=len(clean))
    if lens.all():  # an empty response has no item to start with
        try:
            offsets = np.concatenate([[0], np.cumsum(lens)[:-1]])
            return _decode_varint_stream(b"".join(clean), offsets)
        except ValueError:
            pass
    rows, n_items, n_errors = [], [], []
    for d in chunk:
        items = decode_response_slow(d)
        n_items.append(len(items))
        n_errors.append(sum(1 for it in items if it[4]))
        rows.extend(it[:4] for it in items)
    a = np.asarray(rows, dtype=np.int64).reshape(-1, 4)
    return Answers(
        np.asarray(n_items, dtype=np.int64), np.asarray(n_errors, dtype=np.int64),
        a[:, 0], a[:, 1], a[:, 2], a[:, 3],
    )
