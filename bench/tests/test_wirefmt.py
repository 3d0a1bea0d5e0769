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


def test_leaky_request_bytes_are_what_protobuf_serializes(pb):
    ids = wirefmt.key_ids(3_000_000_019, np.arange(5, 12))
    for hits, created in ((1, None), (0, 1_790_000_000_123), (1, 1_790_000_000_123)):
        ours = wirefmt.request_bytes(ids, hits, 100, 134_000_000, created_at=created,
                                     algorithm=wirefmt.LEAKY)
        req = pb.GetRateLimitsReq()
        for i in ids:
            r = req.requests.add(name="bulk", unique_key=f"{int(i):016x}", hits=hits,
                                 limit=100, duration=134_000_000, algorithm=pb.LEAKY_BUCKET)
            if created is not None:
                r.created_at = created
        assert ours == req.SerializeToString()
        assert b"\x30\x01" in ours


# what `request_bytes` gave at b3e17fb, before it knew of field 6: two keys
# of seed 3,000,000,019 (indices 5, 6), limit 100 per 3,600,000 ms
_TOKEN_ROWS_BEFORE = {
    (1, None): "0a210a0462756c6b121064376361666238356135643331646132180120642880dddb01"
               "0a210a0462756c6b121037363032373533663235316439396237180120642880dddb01",
    (0, 1_790_000_000_123):
        "0a260a0462756c6b12106437636166623835613564333164613220642880dddb0150fbd8c1a28c34"
        "0a260a0462756c6b12103736303237353366323531643939623720642880dddb0150fbd8c1a28c34",
    (1, 1_790_000_000_123):
        "0a280a0462756c6b121064376361666238356135643331646132180120642880dddb0150fbd8c1a28c34"
        "0a280a0462756c6b121037363032373533663235316439396237180120642880dddb0150fbd8c1a28c34",
}


@pytest.mark.parametrize("hits,created", list(_TOKEN_ROWS_BEFORE))
def test_token_request_bytes_are_the_bytes_they_were(hits, created):
    ids = wirefmt.key_ids(3_000_000_019, np.arange(5, 7))
    want = bytes.fromhex(_TOKEN_ROWS_BEFORE[hits, created])
    assert wirefmt.request_bytes(ids, hits, 100, 3_600_000, created_at=created) == want
    assert wirefmt.request_bytes(ids, hits, 100, 3_600_000, created_at=created,
                                 algorithm=wirefmt.TOKEN) == want


# what `request_bytes` gave a leaky keyspace at e445c33, before it knew of
# field 7: the same two keys, limit 100 per 134,000,000 ms
_LEAKY_ROWS_BEFORE = {
    (1, None): "0a230a0462756c6b121064376361666238356135643331646132180120642880dbf23f3001"
               "0a230a0462756c6b121037363032373533663235316439396237180120642880dbf23f3001",
    (0, 1_790_000_000_123):
        "0a280a0462756c6b12106437636166623835613564333164613220642880dbf23f300150fbd8c1a28c34"
        "0a280a0462756c6b12103736303237353366323531643939623720642880dbf23f300150fbd8c1a28c34",
}


@pytest.mark.parametrize("hits,created", list(_LEAKY_ROWS_BEFORE))
def test_request_bytes_without_a_behavior_are_the_bytes_they_were(hits, created):
    ids = wirefmt.key_ids(3_000_000_019, np.arange(5, 7))
    for algorithm, dur, rows in ((wirefmt.LEAKY, 134_000_000, _LEAKY_ROWS_BEFORE),
                                 (wirefmt.TOKEN, 3_600_000, _TOKEN_ROWS_BEFORE)):
        want = bytes.fromhex(rows[hits, created])
        for kw in ({}, {"behavior": 0}):
            assert wirefmt.request_bytes(ids, hits, 100, dur, created_at=created,
                                         algorithm=algorithm, **kw) == want


@pytest.mark.parametrize("behavior", [0, wirefmt.GLOBAL, wirefmt.GLOBAL | wirefmt.DRAIN_OVER_LIMIT])
@pytest.mark.parametrize("algorithm", [wirefmt.TOKEN, wirefmt.LEAKY])
def test_request_bytes_are_encode_item_row_by_row(algorithm, behavior):
    """With and without a behavior: field 7 sits where `encode_item` has
    always put it, and is left out when 0."""
    ids = wirefmt.key_ids(3_000_000_019, np.arange(5, 12))
    for hits, created in ((1, None), (0, 1_790_000_000_123), (3, 1_790_000_000_123)):
        ours = wirefmt.request_bytes(ids, hits, 100, 3_600_000, created_at=created,
                                     algorithm=algorithm, behavior=behavior)
        assert ours == b"".join(
            wirefmt.encode_item("bulk", f"{int(i):016x}", hits, 100, 3_600_000, algorithm,
                                behavior, created) for i in ids)
        assert (b"\x38" + wirefmt.varint(behavior) in ours) == bool(behavior)


def test_a_global_row_is_what_protobuf_serializes(pb):
    ids = wirefmt.key_ids(3_000_000_019, np.arange(5, 12))
    ours = wirefmt.request_bytes(ids, 1, 100, 3_600_000, created_at=1_790_000_000_123,
                                 behavior=wirefmt.keyspec_behavior({"behavior": ["GLOBAL"]}))
    req = pb.GetRateLimitsReq()
    for i in ids:
        req.requests.add(name="bulk", unique_key=f"{int(i):016x}", hits=1, limit=100,
                         duration=3_600_000, behavior=pb.GLOBAL, created_at=1_790_000_000_123)
    assert ours == req.SerializeToString()


def test_behavior_names_are_upstreams(pb):
    assert wirefmt.BEHAVIORS == {
        name: number for name, number in pb.Behavior.items() if number}
    assert wirefmt.keyspec_behavior({}) == 0
    with pytest.raises(ValueError, match="behavior 'GLOBL'"):
        wirefmt.behavior_bits(["GLOBL"])


def test_a_window_rpc_of_a_token_cell_is_the_bytes_it_was():
    import hashlib

    import loadgen

    keyspec = {"keys": 1000, "hits": 1, "limit": 100, "duration_ms": 3_600_000}
    tr = loadgen.Traffic({"loop": "closed", "inflight": 1, "items_per_rpc": {"fixed": 1000},
                          "keys": {"dist": "uniform"}}, keyspec, 2654435761, 1.0)
    body = tr._body(np.arange(0, 1000))
    assert hashlib.sha256(body).hexdigest() == (
        "93682c192d7134ab36e71453a790822f976d5b436fd53cfdc4b690f767f01d72")
    leaky = loadgen.Traffic(tr.spec, dict(keyspec, algorithm="leaky"), 2654435761, 1.0)
    assert len(leaky._body(np.arange(0, 1000))) == len(body) + 2 * 1000
    glob = loadgen.Traffic(tr.spec, dict(keyspec, behavior=["GLOBAL"]), 2654435761, 1.0)
    assert len(glob._body(np.arange(0, 1000))) == len(body) + 2 * 1000
    assert glob._body(np.arange(0, 1000), 0) == body


def test_an_unknown_algorithm_is_refused():
    with pytest.raises(ValueError, match="keyspace.algorithm"):
        wirefmt.keyspec_algorithm({"algorithm": "gcra"})


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
