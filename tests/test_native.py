"""Native ingress/egress (gubernator_tpu/native) parity tests: wire parsing,
hashing, and response encoding must match the pure-Python pb path exactly."""

import asyncio
import functools
import random

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.proto import gubernator_pb2 as pb

m = native.load()
pytestmark = pytest.mark.skipif(m is None, reason="native toolchain unavailable")


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def random_req(rng, i):
    r = pb.RateLimitReq(
        name=rng.choice(["svc", "üñïçødé-svc", "a" * 40, "x"]),
        unique_key=f"key-{i}-{rng.randrange(1000)}",
        hits=rng.choice([0, 1, 5, -3, 1 << 40]),
        limit=rng.choice([0, 10, 1 << 31, -7]),
        duration=rng.choice([1000, 60_000, 3]),  # 3 = a Gregorian enum value
        algorithm=rng.choice([0, 1]),
        behavior=rng.choice([0, 1, 2, 8, 32, 34]),
        burst=rng.choice([0, 5]),
    )
    if rng.random() < 0.5:
        r.created_at = rng.randrange(1, 1 << 45)
    if rng.random() < 0.3:
        r.metadata["traceparent"] = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        r.metadata["other"] = "värde"
    return r


def test_parse_matches_pb_path():
    from gubernator_tpu.hashing import fingerprint
    from gubernator_tpu.peers.hash_ring import fnv1a_32
    from gubernator_tpu.service.wire import columns_from_pb, columns_from_wire

    rng = random.Random(7)
    items = [random_req(rng, i) for i in range(200)]
    items.append(pb.RateLimitReq(name="no-key"))  # ERR_EMPTY_KEY
    items.append(pb.RateLimitReq(unique_key="no-name"))  # ERR_EMPTY_NAME
    data = pb.GetRateLimitsReq(requests=items).SerializeToString()

    got = columns_from_wire(data)
    assert got is not None
    cols, ring, spans, traceparent = got
    # at least one random item carried the traceparent metadata
    assert traceparent == "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    ref_cols, hash_keys = columns_from_pb(items)

    for field in ("fp", "algo", "behavior", "hits", "burst", "created_at", "err"):
        np.testing.assert_array_equal(
            getattr(cols, field), getattr(ref_cols, field), err_msg=field
        )
    # limit/duration are clipped by columns_from_pb only beyond ±2^62 —
    # unclipped here, so compare raw
    np.testing.assert_array_equal(cols.limit, [it.limit for it in items])
    np.testing.assert_array_equal(cols.duration, [it.duration for it in items])
    # ring points match the python ring hash of the hash key
    for i, hk in enumerate(hash_keys):
        if hk:
            assert int(ring[i]) == fnv1a_32(hk.encode()), hk
    # spans re-materialize the exact item
    from gubernator_tpu.service.wire import item_from_span

    for i in (0, 57, 199):
        assert item_from_span(data, spans[i]) == items[i]


def test_encode_matches_pb():
    from gubernator_tpu.service.wire import encode_response_columns

    n = 50
    rng = np.random.default_rng(3)
    status = rng.integers(0, 2, n).astype(np.int64)
    limit = rng.integers(0, 1 << 40, n)
    remaining = rng.integers(0, 1 << 40, n)
    reset = rng.integers(0, 1 << 45, n)
    errors = {0: "boom", 17: "fält-fel: üñï"}
    data = encode_response_columns(status, limit, remaining, reset, errors)
    resp = pb.GetRateLimitsResp.FromString(data)
    assert len(resp.responses) == n
    for i, r in enumerate(resp.responses):
        assert r.status == status[i]
        assert r.limit == limit[i]
        assert r.remaining == remaining[i]
        assert r.reset_time == reset[i]
        assert r.error == errors.get(i, "")


def test_malformed_wire_raises():
    with pytest.raises(ValueError):
        m.parse_get_rate_limits(b"\x0a\xff\xff\xff\xff\xff")  # truncated len


@async_test
async def test_raw_path_serves_cluster_traffic():
    """The raw gRPC path end-to-end on a 3-daemon cluster: local, forwarded,
    and GLOBAL items all answered from the native ingress."""
    from gubernator_tpu.client import V1Client
    from gubernator_tpu.types import Behavior

    from tests.cluster import Cluster, wait_for

    c = await Cluster.start(3)
    try:
        non_owner = c.non_owning_daemons("nat", "k1")[0]
        owner = c.find_owning_daemon("nat", "k1")
        client = V1Client(non_owner.conf.grpc_address)
        try:
            resp = await client.get_rate_limits(
                [
                    dict(name="nat", unique_key="k1", hits=2, limit=10, duration=60_000),
                    dict(name="nat", unique_key="k2", hits=1, limit=10, duration=60_000),
                    dict(name="", unique_key="bad", hits=1, limit=1, duration=1000),
                    dict(
                        name="nat", unique_key="g1", hits=3, limit=10,
                        duration=60_000, behavior=int(Behavior.GLOBAL),
                    ),
                ]
            )
            r = resp.responses
            assert r[0].error == "" and r[0].remaining == 8
            assert r[1].error == "" and r[1].remaining == 9
            assert "namespace" in r[2].error
            assert r[3].error == "" and r[3].remaining == 7

            # the GLOBAL hit reaches the owner asynchronously
            async def owner_saw_hits():
                ro = await owner.get_rate_limits(
                    [pb.RateLimitReq(name="nat", unique_key="g1", hits=0,
                                     limit=10, duration=60_000)]
                )
                return ro[0].remaining == 7

            await wait_for(owner_saw_hits, timeout_s=15)
        finally:
            await client.close()
    finally:
        await c.stop()


@async_test
async def test_raw_path_force_global():
    """GUBER_FORCE_GLOBAL on the native raw path: requests flip to GLOBAL,
    serve locally, and the owner broadcast still fires (the forced bit must
    survive lazy pb materialization)."""
    from gubernator_tpu.client import V1Client

    from tests.cluster import Cluster, daemon_config, metric_value, scrape, wait_for

    from gubernator_tpu.config import BehaviorConfig

    behaviors = BehaviorConfig(
        batch_wait_ms=1.0, global_sync_wait_ms=50.0,
        batch_timeout_ms=5000.0, global_timeout_ms=5000.0, force_global=True,
    )
    c = await Cluster.start(2, behaviors=behaviors)
    try:
        owner = c.find_owning_daemon("fg", "k1")
        client = V1Client(owner.conf.grpc_address)
        try:
            resp = await client.get_rate_limits(
                [dict(name="fg", unique_key="k1", hits=2, limit=10, duration=60_000)]
            )
            assert resp.responses[0].error == ""
            assert resp.responses[0].remaining == 8
        finally:
            await client.close()

        # forced-GLOBAL owner hits must broadcast to the peer
        async def broadcasted():
            s = await scrape(owner)
            return metric_value(
                s, "gubernator_broadcast_counter_total", condition="broadcast"
            )

        await wait_for(broadcasted, timeout_s=15)
        other = c.non_owning_daemons("fg", "k1")[0]

        async def installed():
            s = await scrape(other)
            return metric_value(
                s, "gubernator_update_peer_globals_installed_total"
            )

        await wait_for(installed, timeout_s=15)
    finally:
        await c.stop()


# ------------------------------------------------ encode_responses_many
# A dispatch's answers in one call (service/batcher.py's encode link):
# byte for byte what encode_response_columns gives for each entry's slice
# with the error strings of its `err` codes, and the entry's OVER_LIMIT rows.


def _response_columns(n, seed, wide=True, over=0.0, err=0.0, leaky=False):
    """A chunk's response columns as an engine hands them over: status
    int32, err int8, the rest int64 (`wide`) or all of them int32."""
    from gubernator_tpu.ops.batch import ERROR_STRINGS, ResponseColumns

    rng = np.random.default_rng(seed)
    big = np.int64 if wide else np.int32
    top = 1 << (45 if wide else 30)
    reset = rng.integers(1, top, n)
    if leaky:  # a leaky bucket's reset_time: the next token, often past
        reset = MANY_NOW + rng.integers(-5_000, 5_000, n)
    return ResponseColumns(
        status=(rng.random(n) < over).astype(np.int32),
        limit=rng.integers(0, top, n).astype(big),
        remaining=rng.integers(0, top, n).astype(big),
        reset_time=reset.astype(big),
        err=np.where(
            rng.random(n) < err, rng.integers(1, len(ERROR_STRINGS), n), 0
        ).astype(np.int8),
    )


MANY_NOW = 1_700_000_000_000
MANY_CASES = {
    # name: (columns' arguments, offsets, now_ms)
    "one_entry": (dict(n=40, seed=1), [0, 40], None),
    "many_entries": (dict(n=3000, seed=2), [0, 1000, 1001, 2000, 3000], None),
    "empty_entry": (dict(n=10, seed=3), [0, 4, 4, 10], None),
    "no_entry": (dict(n=10, seed=3), [], None),
    "int32_columns": (dict(n=64, seed=4, wide=False, over=0.3), [0, 1, 64], 77),
    "over_limit_without_now": (dict(n=200, seed=5, over=0.5), [0, 120, 200], None),
    "over_limit_with_now": (dict(n=200, seed=5, over=0.5), [0, 120, 200], 1 << 44),
    "err_codes": (dict(n=300, seed=6, over=0.2, err=0.3), [0, 7, 150, 300], 9),
    "leaky_reset_below_now": (
        dict(n=500, seed=7, over=0.5, leaky=True), [0, 250, 500], MANY_NOW
    ),
    "offsets_not_from_zero": (dict(n=100, seed=8, over=0.4), [17, 30, 90], 5),
}


@pytest.mark.parametrize("case", sorted(MANY_CASES))
def test_encode_many_matches_encode_of_each_slice(case):
    from gubernator_tpu.ops.batch import ERROR_STRINGS
    from gubernator_tpu.service.wire import (
        encode_response_columns,
        encode_responses_many,
    )

    kw, offsets, now = MANY_CASES[case]
    rc = _response_columns(**kw)
    bodies, over = encode_responses_many(rc, offsets, now)
    assert len(bodies) == len(over) == max(len(offsets) - 1, 0)
    for k, (lo, hi) in enumerate(zip(offsets, offsets[1:])):
        errors = {
            int(i): ERROR_STRINGS[int(rc.err[lo + i])]
            for i in np.flatnonzero(rc.err[lo:hi])
        }
        want = encode_response_columns(
            rc.status[lo:hi], rc.limit[lo:hi], rc.remaining[lo:hi],
            rc.reset_time[lo:hi], errors, now,
        )
        assert bodies[k] == want, (case, k)
        assert over[k] == int((rc.status[lo:hi] == pb.OVER_LIMIT).sum())
        assert len(pb.GetRateLimitsResp.FromString(bodies[k]).responses) == hi - lo
    if now is not None and kw.get("over"):
        assert b"retry_after_ms" in b"".join(bodies)
    if kw.get("leaky"):  # a reset_time behind the clock waits 0 ms
        waits = {
            r.metadata["retry_after_ms"]
            for b in bodies
            for r in pb.GetRateLimitsResp.FromString(b).responses
            if r.status == pb.OVER_LIMIT
        }
        assert "0" in waits and len(waits) > 1


def test_encode_many_takes_strided_columns_and_refuses_bad_input():
    """A column that is a view with a stride is read where it lies; offsets
    that leave the columns or descend, columns of unlike length, a code
    with no error string and a column that holds no integers are refused."""
    from gubernator_tpu.service.wire import encode_responses_many

    rc = _response_columns(n=64, seed=9, over=0.5, err=0.2)
    strided = type(rc)(*(np.repeat(c, 2)[::2] for c in rc))
    assert not strided.limit.flags.c_contiguous
    assert encode_responses_many(strided, [0, 30, 64], 5) == (
        encode_responses_many(rc, [0, 30, 64], 5)
    )
    for bad in ([0, 65], [10, 5], [-1, 3]):
        with pytest.raises(ValueError):
            encode_responses_many(rc, bad)
    with pytest.raises(ValueError):
        encode_responses_many(rc._replace(limit=rc.limit[:-1]), [0, 1])
    with pytest.raises(ValueError):
        encode_responses_many(rc._replace(err=np.full(64, 99, np.int8)), [0, 64])
    with pytest.raises(TypeError):
        encode_responses_many(rc._replace(limit=rc.limit.astype(float)), [0, 1])
