"""route="device" / dedup="device" parity suite on the 8-device CPU mesh.

The TPU serving default (arrival-order rows + on-mesh a2a exchange +
in-trace duplicate aggregation) must be semantically interchangeable with
the host-planned paths it replaces:

* dedup="device" ≍ the host planner's aggregate-everything plan
  (plan_passes with max_exact=1 — the reference's GLOBAL hot-key
  aggregation, global.go:109-123) for responses, live state, and stats;
* route="device" ≍ route="host" under either dedup mode, including
  Zipf-skewed batches that force per-pair exchange overflow (retries +
  terminal host fallback);
* the GLOBAL owner/replica fork (GlobalShardedEngine) behaves identically
  whichever side of the mesh does routing and dedup;
* route="device" on a 2-, 4- and 8-device mesh answers like ONE LocalEngine
  fed the same traffic — the mesh and its all_to_all exchange are invisible,
  through a hash-concentrated batch that overflows a pair's capacity too.

Tables are compared CANONICALLY (slots sorted within each bucket): lane
assignment follows batch row order, and the dedup paths legitimately place
a key's carrier at a different row position than the host oracle — slot
order inside a bucket is internal state, not an API surface.
"""

import numpy as np
import pytest

import jax

from gubernator_tpu.ops.batch import columns_from_requests
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.global_sync import GlobalShardedEngine
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, MINUTE


def req(key, hits=1, limit=100, duration=MINUTE,
        algorithm=Algorithm.TOKEN_BUCKET, behavior=Behavior.BATCHING,
        created_at=None):
    return RateLimitRequest(
        name="rd", unique_key=key, hits=hits, limit=limit, duration=duration,
        algorithm=algorithm, behavior=behavior, created_at=created_at,
    )


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "tests require the 8-device CPU mesh"
    return make_mesh(8)


def canon(rows: np.ndarray) -> np.ndarray:
    """Sort each bucket's slots by fingerprint — canonical live state."""
    from gubernator_tpu.ops.table2 import F, K

    D, NB, _ = rows.shape
    s = rows.reshape(D, NB, K, F)
    key = (s[..., 1].astype(np.int64) << 32) | (
        s[..., 0].astype(np.int64) & 0xFFFFFFFF
    )
    order = np.argsort(key, axis=2, kind="stable")
    return np.take_along_axis(s, order[..., None], axis=2)


def assert_resp_equal(want, got, ctx=""):
    for i, (a, b) in enumerate(zip(want, got)):
        assert (a.status, a.remaining, a.reset_time, a.error) == (
            b.status, b.remaining, b.reset_time, b.error,
        ), f"{ctx} row {i}: {a} != {b}"


def mixed_corpus(rng, t, step, n=200, keys=70):
    """Token/leaky mix with duplicates, varying hits, RESET flags."""
    ks = rng.integers(0, keys, size=n)
    return [
        req(
            f"m{k}",
            hits=1 + int(k) % 3,
            limit=1000,
            algorithm=(Algorithm.TOKEN_BUCKET if k % 3
                       else Algorithm.LEAKY_BUCKET),
            behavior=(Behavior.RESET_REMAINING if k % 11 == 1
                      else Behavior.BATCHING),
            created_at=t + step,
        )
        for k in ks
    ]


@pytest.mark.parametrize("route", ["host", "device"])
def test_device_dedup_matches_host_aggregate_oracle(mesh, frozen_now, route):
    """In-trace dedup vs the host aggregation oracle, per route: responses,
    stats, and canonical live state all equal across multi-step mixed
    traffic."""
    t = frozen_now
    oracle = ShardedEngine(mesh, capacity_per_shard=2048, route=route,
                           dedup="host", max_exact_passes=1)
    dev = ShardedEngine(mesh, capacity_per_shard=2048, route=route,
                        dedup="device")
    rng = np.random.default_rng(5)
    for step in range(3):
        reqs = mixed_corpus(rng, t, step)
        want = oracle.check(reqs, now_ms=t + step)
        got = dev.check(reqs, now_ms=t + step)
        assert_resp_equal(want, got, f"route={route} step={step}")
    np.testing.assert_array_equal(canon(oracle.snapshot()),
                                  canon(dev.snapshot()))
    assert oracle.stats.cache_hits == dev.stats.cache_hits
    assert oracle.stats.cache_misses == dev.stats.cache_misses
    assert oracle.stats.over_limit == dev.stats.over_limit
    assert oracle.stats.checks == dev.stats.checks


def test_route_parity_zipf_overflow(mesh, frozen_now):
    """Zipf-skewed duplicate-heavy batches through route="device" vs
    route="host" (both dedup="device"): skew concentrates rows on hot
    owners and forces per-pair exchange overflow; the retry chain plus the
    terminal host-grid fallback must make routing invisible — identical
    responses, zero errors, identical per-key totals."""
    t = frozen_now
    host_eng = ShardedEngine(mesh, capacity_per_shard=4096, route="host",
                             dedup="device")
    dev_eng = ShardedEngine(mesh, capacity_per_shard=4096, route="device",
                            dedup="device")
    rng = np.random.default_rng(13)
    z = np.minimum(rng.zipf(1.1, size=2048) - 1, 1023)
    reqs = [req(f"z{k}", hits=1, limit=1 << 20, created_at=t) for k in z]
    want = host_eng.check(reqs, now_ms=t)
    got = dev_eng.check(reqs, now_ms=t)
    assert_resp_equal(want, got, "zipf")
    assert all(r.error == "" for r in got)
    # per-key consumption identical on both engines (hits=0 probe)
    uniq, counts = np.unique(z, return_counts=True)
    probe = [req(f"z{k}", hits=0, limit=1 << 20, created_at=t) for k in uniq]
    again_h = host_eng.check(probe, now_ms=t)
    again_d = dev_eng.check(probe, now_ms=t)
    assert_resp_equal(again_h, again_d, "zipf probe")
    for k, c, r in zip(uniq, counts, again_d):
        assert r.remaining == (1 << 20) - c, f"key z{k}"
    np.testing.assert_array_equal(canon(host_eng.snapshot()),
                                  canon(dev_eng.snapshot()))


def _local_oracle(dedup, capacity):
    """One table, no mesh: the host pass planner for dedup="host", its
    aggregate-everything plan (max_exact=1) for the in-trace dedup."""
    return LocalEngine(
        capacity=capacity, max_exact_passes=8 if dedup == "host" else 1
    )


@pytest.mark.parametrize("D", [2, 4, 8])
@pytest.mark.parametrize("dedup", ["host", "device"])
def test_device_route_matches_local_engine(D, dedup, frozen_now):
    """route="device" at every mesh width × dedup mode against one
    LocalEngine: responses and stats over multi-step mixed traffic, then
    every key's stored state read back with a hits=0 probe."""
    t = frozen_now
    dev = ShardedEngine(make_mesh(D), capacity_per_shard=2048,
                        route="device", dedup=dedup)
    local = _local_oracle(dedup, 2048 * D)
    rng = np.random.default_rng(D * 7 + (dedup == "device"))
    for step in range(3):
        reqs = mixed_corpus(rng, t, step, n=160)
        want = local.check(reqs, now_ms=t + step)
        got = dev.check(reqs, now_ms=t + step)
        assert_resp_equal(want, got, f"D={D} dedup={dedup} step={step}")
    assert local.stats.cache_hits == dev.stats.cache_hits
    assert local.stats.cache_misses == dev.stats.cache_misses
    assert local.stats.over_limit == dev.stats.over_limit
    probe = [
        req(f"m{k}", hits=0, limit=1000,
            algorithm=(Algorithm.TOKEN_BUCKET if k % 3
                       else Algorithm.LEAKY_BUCKET),
            created_at=t + 3)
        for k in range(70)
    ]
    assert_resp_equal(local.check(probe, now_ms=t + 3),
                      dev.check(probe, now_ms=t + 3), "probe")
    assert dev.a2a_overflow == 0


def test_device_route_overflow_matches_local_engine(mesh, frozen_now):
    """Distinct keys all OWNED BY SHARD 0: every source block concentrates
    on one destination, far past pair_capacity's 5σ bound. The retry chain
    makes the overflow invisible in the answers (LocalEngine's, zero
    errors) and OBSERVABLE in the engine's a2a_overflow counter, which
    take_a2a_overflow_delta (the gubernator_tpu_a2a_overflow_total source)
    drains exactly once."""
    from gubernator_tpu.hashing import fingerprint
    from gubernator_tpu.parallel.mesh import shard_of

    t = frozen_now
    dev = ShardedEngine(mesh, capacity_per_shard=4096, route="device",
                        dedup="device")
    local = _local_oracle("device", 4096 * 8)
    hot = []
    i = 0
    while len(hot) < 800:
        if shard_of(np.int64(fingerprint("rd", f"h{i}")), 8) == 0:
            hot.append(f"h{i}")
        i += 1
    reqs = [req(k, hits=1, limit=1 << 20, created_at=t) for k in hot]
    got = dev.check(reqs, now_ms=t)
    assert_resp_equal(local.check(reqs, now_ms=t), got, "hot-shard")
    assert all(r.error == "" for r in got)
    assert dev.a2a_overflow > 0
    assert dev.take_a2a_overflow_delta() == dev.a2a_overflow
    assert dev.take_a2a_overflow_delta() == 0


def test_global_fork_parity_device_route_and_dedup(mesh, frozen_now):
    """The GLOBAL owner/replica fork through the device-routed, in-trace
    dedup path vs the host-planned aggregate oracle: replica answers, owner
    applies, queued hits, and the post-sync converged state must all agree
    (same rotating home sequence — one GLOBAL batch per check call)."""
    t = frozen_now
    oracle = GlobalShardedEngine(mesh, capacity_per_shard=2048, route="host",
                                 dedup="host", max_exact_passes=1,
                                 sync_out=256)
    dev = GlobalShardedEngine(mesh, capacity_per_shard=2048, route="device",
                              dedup="device", sync_out=256)
    rng = np.random.default_rng(23)
    for step in range(3):
        ks = rng.integers(0, 40, size=120)
        reqs = [
            req(
                f"g{k}",
                hits=1 + int(k) % 2,
                limit=500,
                behavior=(Behavior.GLOBAL if k % 2 else Behavior.BATCHING),
                created_at=t + step,
            )
            for k in ks
        ]
        cols = columns_from_requests(reqs)
        want = oracle.check_columns(cols, now_ms=t + step)
        got = dev.check_columns(cols, now_ms=t + step)
        np.testing.assert_array_equal(want.status, got.status, f"step {step}")
        np.testing.assert_array_equal(want.remaining, got.remaining)
        np.testing.assert_array_equal(want.reset_time, got.reset_time)
        np.testing.assert_array_equal(want.err, got.err)
    assert (
        oracle.global_stats.send_queue_length
        == dev.global_stats.send_queue_length
    )
    oracle.sync(now_ms=t + 3)
    dev.sync(now_ms=t + 3)
    # post-sync convergence: the owner-reconciled authoritative tables agree
    np.testing.assert_array_equal(canon(oracle.snapshot()),
                                  canon(dev.snapshot()))
    probe = columns_from_requests(
        [req(f"g{k}", hits=0, limit=500, behavior=Behavior.GLOBAL,
             created_at=t + 3) for k in range(0, 40, 2)]
    )
    want = oracle.check_columns(probe, now_ms=t + 3)
    got = dev.check_columns(probe, now_ms=t + 3)
    np.testing.assert_array_equal(want.remaining, got.remaining)


def test_pipelined_dedup_matches_serial(mesh, frozen_now):
    """The prepare/issue/finish split with in-trace dedup (member rows
    decoded through finish_staged's FLAG_MEMBER accounting) must equal the
    serial dedup path — responses, stats, and state."""
    from gubernator_tpu.ops.engine import (
        finish_check_columns,
        issue_check_columns,
        prepare_check_columns,
    )

    t = frozen_now
    rng = np.random.default_rng(31)
    serial = ShardedEngine(mesh, capacity_per_shard=2048, route="device",
                           dedup="device")
    piped = ShardedEngine(mesh, capacity_per_shard=2048, route="device",
                          dedup="device")
    for step in range(3):
        cols = columns_from_requests(mixed_corpus(rng, t, step, n=160))
        want = serial.check_columns(cols, now_ms=t + step)
        pending = issue_check_columns(
            piped, prepare_check_columns(piped, cols, now_ms=t + step)
        )
        # in-trace dedup plans exactly ONE pass — the host group-by is gone
        assert len(pending.passes) == 1
        got, delta = finish_check_columns(piped, pending, fixup=lambda fn: fn())
        piped.stats.merge(delta)
        np.testing.assert_array_equal(got.status, want.status)
        np.testing.assert_array_equal(got.remaining, want.remaining)
        np.testing.assert_array_equal(got.err, want.err)
    assert serial.stats.cache_hits == piped.stats.cache_hits
    assert serial.stats.cache_misses == piped.stats.cache_misses
    np.testing.assert_array_equal(canon(serial.snapshot()),
                                  canon(piped.snapshot()))


def test_stage_timing_and_egress_recycling(mesh, frozen_now):
    """The ingress accounting the shard_* metrics read: the mesh engine's
    host stages are parts of the dispatch stage that is open on the thread
    (tracing.stage.within), each a sample of the stage histogram of the
    metrics that stage was given and nothing added to the dispatch's work
    time; and fetched egress buffers are banked for donation reuse."""
    from gubernator_tpu import tracing
    from gubernator_tpu.service.metrics import DaemonMetrics

    t = frozen_now
    eng = ShardedEngine(mesh, capacity_per_shard=1024, route="device",
                        dedup="device")
    reqs = [req(f"s{i}", created_at=t) for i in range(64)]
    metrics = DaemonMetrics()
    disp = tracing.Dispatch(seq=7, rows=len(reqs))

    def samples():
        return {
            k: (c._sum.get(), sum(b.get() for b in c._buckets))
            for k, c in metrics._stage_children.items()
        }

    with tracing.stage("put", metrics, disp=disp) as outer:
        eng.check(reqs, now_ms=t)
    got = samples()
    packs = {"shard_pack", "wire_pack"} & set(got)
    assert len(packs) == 1  # one pass, one wire format
    assert set(got) - packs - {"wire_decode"} == {
        "put", "shard_put", "shard_unroute"}  # route=device: no shard_route
    assert all(n == 1 and s > 0 for s, n in got.values())
    parts = sum(s for k, (s, _n) in got.items() if k != "put")
    assert parts <= got["put"][0]
    assert disp.work_s == outer.dt  # the parts are already inside it
    # under no dispatch stage the parts are clocks only: nothing is sampled
    eng.check(reqs, now_ms=t)
    assert samples() == got
    # egress bank primed by the fetch; the next same-shape dispatch pops it
    assert any(len(v) for v in eng._egress.values())
    banked = {k: len(v) for k, v in eng._egress.items()}
    eng.check(reqs, now_ms=t)
    assert {k: len(v) for k, v in eng._egress.items()} == banked
