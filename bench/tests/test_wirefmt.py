"""The hand-written wire bytes against protobuf's own, and the quick
decoder against the one by the book."""

import numpy as np
import pytest

import wirefmt


@pytest.fixture(scope="module")
def pb():
    from gubernator_tpu.proto import gubernator_pb2

    return gubernator_pb2


def test_request_bytes_are_what_protobuf_serializes(pb):
    ids = wirefmt.key_ids(3_000_000_019, np.arange(5, 12))
    for hits, created in ((1, None), (0, 1_790_000_000_123), (1, 1_790_000_000_123)):
        ours = wirefmt.request_bytes(ids, hits, 100, 3_600_000, created_at=created)
        req = pb.GetRateLimitsReq()
        for i in ids:
            r = req.requests.add(name="bulk", unique_key=f"{int(i):016x}", hits=hits,
                                 limit=100, duration=3_600_000)
            if created is not None:
                r.created_at = created
        assert ours == req.SerializeToString()


def test_encode_item_is_what_protobuf_serializes(pb):
    ours = wirefmt.encode_item("leak", "s7-3", 9, 10, 60_000, wirefmt.LEAKY,
                               wirefmt.DRAIN_OVER_LIMIT, 1_790_000_000_123)
    req = pb.GetRateLimitsReq(requests=[pb.RateLimitReq(
        name="leak", unique_key="s7-3", hits=9, limit=10, duration=60_000,
        algorithm=pb.LEAKY_BUCKET, behavior=pb.DRAIN_OVER_LIMIT, created_at=1_790_000_000_123,
    )])
    assert ours == req.SerializeToString()


def _responses(pb, rng, n_rpcs, with_meta=False, with_error=False):
    datas, rows, counts = [], [], []
    for _ in range(n_rpcs):
        resp = pb.GetRateLimitsResp()
        n = int(rng.integers(1, 40))
        counts.append(n)
        for _ in range(n):
            status = int(rng.integers(0, 2))
            remaining = 0 if status else int(rng.integers(0, 100))
            reset = 1_790_000_000_000 + int(rng.integers(0, 10**7))
            r = resp.responses.add(status=status, limit=100, remaining=remaining,
                                   reset_time=reset)
            if with_meta and status:
                r.metadata["retry_after_ms"] = str(int(rng.integers(0, 3_600_000)))
            if with_error and rng.random() < 0.05:
                r.error = "bad request"
            rows.append((status, 100, remaining, reset))
        datas.append(resp.SerializeToString())
    return datas, np.asarray(rows), counts


@pytest.mark.parametrize("with_meta,with_error", [(False, False), (True, False), (True, True)])
def test_quick_decoder_agrees_with_protobuf(pb, with_meta, with_error):
    rng = np.random.default_rng(5)
    datas, rows, counts = _responses(pb, rng, 300, with_meta, with_error)
    ans = wirefmt.decode_responses(datas, chunk_bytes=4096)
    assert ans.n_items.tolist() == counts
    got = np.stack([ans.status, ans.limit, ans.remaining, ans.reset_time], axis=1)
    assert np.array_equal(got, rows)
    assert (ans.errors > 0) == with_error


def test_response_bytes_round_trip(pb):
    rows = [(0, 100, 99, 1_790_000_003_600), (1, 100, 0, 1_790_000_003_601)]
    data = wirefmt.response_bytes(rows)
    parsed = pb.GetRateLimitsResp.FromString(data).responses
    assert [(r.status, r.limit, r.remaining, r.reset_time) for r in parsed] == rows
    assert [tuple(r[:4]) for r in wirefmt.decode_response_slow(data)] == rows


def test_garbage_is_refused():
    with pytest.raises(ValueError):
        wirefmt.decode_response_slow(b"\x0a\x05\x08\x01")
