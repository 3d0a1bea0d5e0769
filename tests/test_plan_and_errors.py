"""Pass planner + per-request error isolation tests."""

import numpy as np
import pytest

from gubernator_tpu.ops.batch import pack_requests
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.ops.plan import plan_passes
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    Gregorian,
    RateLimitRequest,
    Status,
    MINUTE,
)


def req(key, hits=1, limit=100, behavior=0, algorithm=Algorithm.TOKEN_BUCKET, name="t"):
    return RateLimitRequest(
        name=name, unique_key=key, hits=hits, limit=limit, duration=MINUTE,
        algorithm=algorithm, behavior=behavior,
    )


def test_invalid_items_do_not_fail_the_batch(frozen_now):
    # reference gubernator.go:215-224 answers per-item errors
    eng = LocalEngine(capacity=256)
    out = eng.check(
        [
            req("ok1"),
            RateLimitRequest(name="t", unique_key="", hits=1, limit=5, duration=MINUTE),
            RateLimitRequest(name="", unique_key="k", hits=1, limit=5, duration=MINUTE),
            req("ok2"),
        ],
        now_ms=frozen_now,
    )
    assert out[0].error == "" and out[0].remaining == 99
    assert out[1].error == "field 'unique_key' cannot be empty"
    assert out[2].error == "field 'namespace' cannot be empty"
    assert out[3].error == "" and out[3].remaining == 99


def test_bad_gregorian_is_per_request_error(frozen_now):
    eng = LocalEngine(capacity=256)
    out = eng.check(
        [
            req("good"),
            req("bad", behavior=Behavior.DURATION_IS_GREGORIAN),  # duration=MINUTE: invalid enum
            req("also-good"),
        ],
        now_ms=frozen_now,
    )
    assert out[0].error == "" and out[1].error != "" and out[2].error == ""
    assert "gregorian" in out[1].error.lower()


def test_hot_key_aggregation_merges_only_reset_remaining(frozen_now):
    # behaviors of aggregated duplicates must not leak into the carrier row
    # (only RESET_REMAINING merges, reference global.go:117-121)
    b, errs = pack_requests(
        [req("hot", behavior=Behavior.DRAIN_OVER_LIMIT) for _ in range(10)]
        + [req("hot", behavior=Behavior.RESET_REMAINING)]
        + [req("hot")],  # newest: carrier, no flags
        frozen_now,
    )
    passes = plan_passes(b, max_exact=2)
    assert len(passes) == 2
    agg = passes[-1]
    assert agg.batch.behavior[0] == int(Behavior.RESET_REMAINING)
    assert agg.batch.hits[0] == 11  # everything after occurrence 0 summed
    assert agg.member_counts.tolist() == [11] and agg.members.tolist() == list(range(1, 12))


def test_aggregated_members_share_response(frozen_now):
    eng = LocalEngine(capacity=256, max_exact_passes=2)
    out = eng.check([req("hk", hits=1, limit=100) for _ in range(50)], now_ms=frozen_now)
    # pass 0: first occurrence consumes 1 → 99; aggregate pass: 49 more → 50
    assert out[0].remaining == 99
    assert all(r.remaining == 50 for r in out[1:])
    assert all(r.status == Status.UNDER_LIMIT for r in out)


def test_planner_skips_inactive_rows(frozen_now):
    b, errs = pack_requests(
        [req("a"), RateLimitRequest(name="t", unique_key="", limit=1, duration=1), req("b")],
        frozen_now,
    )
    passes = plan_passes(b)
    assert len(passes) == 1
    assert list(passes[0].rows) == [0, 2]


def test_drain_over_limit_keeps_predrain_reset_time(frozen_now):
    # reference algorithms.go:372-377,406-419: the drained rejection reports
    # the reset_time computed from the PRE-drain remaining
    eng = LocalEngine(capacity=256)
    t = frozen_now
    lk = RateLimitRequest(
        name="t", unique_key="lk", hits=5, limit=10, duration=10_000,
        algorithm=Algorithm.LEAKY_BUCKET, created_at=t,
    )
    (r,) = eng.check([lk], now_ms=t)
    assert r.remaining == 5
    drain = RateLimitRequest(
        name="t", unique_key="lk", hits=8, limit=10, duration=10_000,
        algorithm=Algorithm.LEAKY_BUCKET, behavior=Behavior.DRAIN_OVER_LIMIT,
        created_at=t,
    )
    (r,) = eng.check([drain], now_ms=t)
    assert r.status == Status.OVER_LIMIT
    assert r.remaining == 0
    # rate = 1000 ms/token; pre-drain remaining 5 → reset = t + (10-5)*1000
    assert r.reset_time == t + 5_000


def test_oversized_limit_burst_rejected(frozen_now):
    # table stores int32 carriers; the front door must reject larger values
    # with a per-request error instead of silently saturating device state
    eng = LocalEngine(capacity=256)
    out = eng.check(
        [
            req("big", limit=2**31 + 1000),
            RateLimitRequest(
                name="t", unique_key="bb", hits=1, limit=10, burst=2**40,
                duration=MINUTE, algorithm=Algorithm.LEAKY_BUCKET,
            ),
            req("fine", limit=2**31 - 1),
        ],
        now_ms=frozen_now,
    )
    assert out[0].error == "field 'limit' must fit int32"
    assert out[1].error == "field 'burst' must fit int32"
    assert out[2].error == "" and out[2].status == Status.UNDER_LIMIT


def test_created_at_clamped_to_ingress_tolerance(frozen_now):
    # a client-supplied created_at far in the future must not renew/expire
    # live buckets (the reference checks expiry against the server clock,
    # lrucache.go GetItem); deviations clamp to now ± tolerance
    from gubernator_tpu.ops.batch import CREATED_AT_TOLERANCE_MS

    b, errs = pack_requests(
        [
            RateLimitRequest(
                name="t", unique_key="skew", hits=1, limit=10, duration=MINUTE,
                created_at=frozen_now + 10 * CREATED_AT_TOLERANCE_MS,
            ),
            RateLimitRequest(
                name="t", unique_key="stale", hits=1, limit=10, duration=MINUTE,
                created_at=frozen_now - 10 * CREATED_AT_TOLERANCE_MS,
            ),
            RateLimitRequest(
                name="t", unique_key="ok", hits=1, limit=10, duration=MINUTE,
                created_at=frozen_now + 1000,
            ),
        ],
        frozen_now,
    )
    assert errs == [None, None, None]
    assert b.created_at[0] == frozen_now + CREATED_AT_TOLERANCE_MS
    assert b.created_at[1] == frozen_now - CREATED_AT_TOLERANCE_MS
    assert b.created_at[2] == frozen_now + 1000  # within tolerance: untouched


def test_peers_package_imports():
    # regression: peers/__init__ imported a module that didn't exist, leaving
    # the whole subpackage dead on arrival
    from gubernator_tpu.peers import RegionPicker, ReplicatedConsistentHash
    from gubernator_tpu.types import PeerInfo

    rp = RegionPicker()
    rp.add(PeerInfo(grpc_address="10.0.0.1:81", data_center="dc-a"))
    rp.add(PeerInfo(grpc_address="10.0.0.2:81", data_center="dc-a"))
    rp.add(PeerInfo(grpc_address="10.0.1.1:81", data_center="dc-b"))
    owners = rp.get_clients("some_key")
    assert len(owners) == 2  # one owner per region
    assert {o.data_center for o in owners} == {"dc-a", "dc-b"}
    assert rp.get_by_address("10.0.1.1:81").data_center == "dc-b"
    assert rp.size() == 3


def test_clamp_is_counted_and_configurable(frozen_now):
    from gubernator_tpu.ops import batch as batch_mod
    from gubernator_tpu.ops.batch import (
        columns_from_requests,
        created_at_tolerance_ms,
        set_created_at_tolerance_ms,
    )

    eng = LocalEngine(capacity=256)
    skewed = RateLimitRequest(
        name="t", unique_key="skew", hits=1, limit=10, duration=MINUTE,
        created_at=frozen_now - 10 * batch_mod.CREATED_AT_TOLERANCE_MS,
    )
    eng.check_columns(columns_from_requests([req("ok"), skewed]), now_ms=frozen_now)
    assert eng.stats.created_at_clamped == 1

    # widening the tolerance stops the clamping (GUBER_CREATED_AT_TOLERANCE)
    old = created_at_tolerance_ms()
    try:
        set_created_at_tolerance_ms(20 * batch_mod.CREATED_AT_TOLERANCE_MS)
        eng.check_columns(
            columns_from_requests([skewed]), now_ms=frozen_now
        )
        assert eng.stats.created_at_clamped == 1  # unchanged
    finally:
        set_created_at_tolerance_ms(old)
    with pytest.raises(ValueError):
        set_created_at_tolerance_ms(0)
