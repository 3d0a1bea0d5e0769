"""Pod-scale mesh suite: the (host, device) topology and the hierarchical
GLOBAL sync.

Re-meshing the same devices into (host, device) rows moves no keys, the
in-mesh GLOBAL reconcile is topology-invariant, and the inter-slice compact
sync codec (service/wire.sync_wire_pb) round-trips exactly and engages on
the real gRPC peer plane.
"""

import asyncio
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gubernator_tpu.ops.batch import columns_from_requests
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.global_sync import GlobalShardedEngine
from gubernator_tpu.parallel.mesh import (
    devices_per_host,
    host_of_shard,
    mesh_hosts,
    shard_axes,
)
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, MINUTE


def req(key, hits=1, limit=100, duration=MINUTE,
        algorithm=Algorithm.TOKEN_BUCKET, behavior=Behavior.BATCHING,
        created_at=None):
    return RateLimitRequest(
        name="ring", unique_key=key, hits=hits, limit=limit,
        duration=duration, algorithm=algorithm, behavior=behavior,
        created_at=created_at,
    )


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


# --------------------------------------------------------------- topology


def test_make_mesh_topology():
    """(host, device) addressing: axes, host-major linearization, helper
    introspection, and the simulated-host env knob."""
    m1 = make_mesh(8)
    assert m1.axis_names == ("shard",)
    assert mesh_hosts(m1) == 1 and devices_per_host(m1) == 8
    assert shard_axes(m1) == "shard"

    m2 = make_mesh(8, hosts=2)
    assert m2.axis_names == ("host", "device")
    assert mesh_hosts(m2) == 2 and devices_per_host(m2) == 4
    assert shard_axes(m2) == ("host", "device")
    # host-major: shard s lives at grid position (s // dl, s % dl), and the
    # flat device order matches the 1-D mesh's — re-meshing moves no keys
    assert list(m2.devices.flat) == list(m1.devices.flat)
    np.testing.assert_array_equal(
        host_of_shard(m2, np.arange(8)), np.arange(8) // 4
    )

    with pytest.raises(ValueError):
        make_mesh(6, hosts=4)  # uneven split

    import os

    os.environ["GUBER_MESH_HOSTS"] = "4"
    try:
        m4 = make_mesh(8)
        assert mesh_hosts(m4) == 4 and devices_per_host(m4) == 2
    finally:
        del os.environ["GUBER_MESH_HOSTS"]


def test_multihost_mesh_state_parity(frozen_now):
    """Re-meshing the same 8 devices from 1 host to 2 (host, device) rows
    moves no keys: identical responses and canonical state — the
    ownership-stability contract of the host-major layout."""
    t = frozen_now
    one = ShardedEngine(make_mesh(8), capacity_per_shard=2048,
                        route="device", dedup="device")
    two = ShardedEngine(make_mesh(8, hosts=2), capacity_per_shard=2048,
                        route="device", dedup="device")
    assert two.n_hosts == 2 and two.devices_per_host == 4
    rng = np.random.default_rng(29)
    for step in range(2):
        reqs = mixed_corpus(rng, t, step, n=160)
        want = one.check(reqs, now_ms=t + step)
        got = two.check(reqs, now_ms=t + step)
        assert_resp_equal(want, got, f"hosts step={step}")
    np.testing.assert_array_equal(canon(one.snapshot()), canon(two.snapshot()))


def test_multihost_global_sync_convergence(frozen_now):
    """The hierarchical GLOBAL plane on a 2-host mesh: replica answers, the
    collective sync, and the converged authoritative state all match the
    1-D mesh — in-mesh reconcile is topology-invariant."""
    t = frozen_now
    one = GlobalShardedEngine(make_mesh(8), capacity_per_shard=2048,
                              sync_out=64, route="device", dedup="device")
    two = GlobalShardedEngine(make_mesh(8, hosts=2), capacity_per_shard=2048,
                              sync_out=64, route="device", dedup="device")
    rng = np.random.default_rng(31)
    for step in range(2):
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
        want = one.check_columns(cols, now_ms=t + step)
        got = two.check_columns(cols, now_ms=t + step)
        np.testing.assert_array_equal(want.status, got.status, f"step {step}")
        np.testing.assert_array_equal(want.remaining, got.remaining)
        np.testing.assert_array_equal(want.err, got.err)
    one.sync(now_ms=t + 2)
    two.sync(now_ms=t + 2)
    assert not one.has_pending() and not two.has_pending()
    np.testing.assert_array_equal(canon(one.snapshot()), canon(two.snapshot()))
    probe = columns_from_requests(
        [req(f"g{k}", hits=0, limit=500, behavior=Behavior.GLOBAL,
             created_at=t + 2) for k in range(0, 40, 2)]
    )
    want = one.check_columns(probe, now_ms=t + 2)
    got = two.check_columns(probe, now_ms=t + 2)
    np.testing.assert_array_equal(want.remaining, got.remaining)


# ------------------------------------------- inter-slice compact sync codec


def test_sync_wire_codec_roundtrip(frozen_now):
    """sync_wire_pb → sync_wire_items is exact for encodable batches, and
    the host lane decode agrees with the in-trace decode field-for-field."""
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.service.wire import sync_wire_items, sync_wire_pb

    t = frozen_now
    pairs = []
    for i in range(6):
        it = pb.RateLimitReq(
            name="glob", unique_key=f"k{i}", hits=(1 << 20) + i,
            limit=100 + i, duration=60_000, algorithm=i % 2,
            behavior=int(Behavior.GLOBAL)
            | (int(Behavior.RESET_REMAINING) if i == 3 else 0),
            created_at=t + i,
        )
        if it.algorithm == 1:
            it.burst = it.limit  # leaky default — encodable
        pairs.append((f"glob_k{i}", it))
    req_pb = sync_wire_pb(pairs, "src:1")
    assert req_pb is not None
    items = sync_wire_items(req_pb)
    for (_k, a), b in zip(pairs, items):
        assert (a.name, a.unique_key, a.hits, a.limit, a.duration,
                a.algorithm, a.created_at) == (
            b.name, b.unique_key, b.hits, b.limit, b.duration,
            b.algorithm, b.created_at,
        )
        assert b.behavior & int(Behavior.GLOBAL)
        assert (a.behavior & int(Behavior.RESET_REMAINING)) == (
            b.behavior & int(Behavior.RESET_REMAINING)
        )
    # host decode vs in-trace decode on one lane image
    from gubernator_tpu.ops.wire import WIRE_LANES, decode_wire_block, decode_wire_host

    n = len(pairs)
    lanes = np.frombuffer(req_pb.lanes, dtype="<i4").reshape(WIRE_LANES, n)
    host = decode_wire_host(lanes, int(req_pb.base))
    blk = np.zeros((WIRE_LANES, n + 1), dtype=np.int32)
    blk[:, :n] = lanes
    from gubernator_tpu.ops.wire import stamp_base

    stamp_base(blk, int(req_pb.base))
    arr12, base = jax.jit(decode_wire_block)(jnp.asarray(blk))
    arr12 = np.asarray(arr12)
    assert int(base) == int(req_pb.base)
    np.testing.assert_array_equal(arr12[0], host["fp"])
    np.testing.assert_array_equal(arr12[1], host["algo"])
    np.testing.assert_array_equal(arr12[2], host["behavior"])
    np.testing.assert_array_equal(arr12[4], host["limit"])
    np.testing.assert_array_equal(arr12[6], host["duration"])
    np.testing.assert_array_equal(arr12[7], host["created_at"])


def test_sync_wire_codec_fallbacks(frozen_now):
    """Every non-representable shape returns None (→ proto path), never a
    lossy encoding."""
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.service.wire import sync_wire_pb

    t = frozen_now

    def item(**kw):
        base = dict(name="g", unique_key="k", hits=1, limit=10,
                    duration=60_000, behavior=int(Behavior.GLOBAL),
                    created_at=t)
        base.update(kw)
        return pb.RateLimitReq(**base)

    ok = item()
    assert sync_wire_pb([("g_k", ok)], "s") is not None
    cases = {
        "multi_region": item(
            behavior=int(Behavior.GLOBAL | Behavior.MULTI_REGION)
        ),
        "gregorian": item(
            behavior=int(Behavior.GLOBAL | Behavior.DURATION_IS_GREGORIAN)
        ),
        "no_created_at": pb.RateLimitReq(
            name="g", unique_key="k", hits=1, limit=10, duration=60_000,
            behavior=int(Behavior.GLOBAL),
        ),
        "big_duration": item(duration=1 << 31),
        "big_limit": item(limit=1 << 33),
        "negative_limit": item(limit=-1),
        "token_burst": item(burst=5),
        "skew": None,  # below
    }
    for label, bad in cases.items():
        if bad is None:
            continue
        assert sync_wire_pb([("g_k", bad)], "s") is None, label
    # created_at skew beyond the ±511 ms delta budget of the batch base
    far = item(created_at=t + 5_000)
    assert sync_wire_pb([("g_k", ok), ("g_k2", far)], "s") is None
    # metadata (trace propagation) has no compact lane
    md = item()
    md.metadata["traceparent"] = "00-xyz"
    assert sync_wire_pb([("g_k", md)], "s") is None


def test_sync_globals_wire_over_grpc(frozen_now):
    """The compact inter-slice sync on the REAL peer plane: a non-owner
    accumulates ≥ _WIRE_MIN GLOBAL hits with created_at set, the sync round
    ships ONE SyncGlobalsWireReq, the owner applies + broadcasts, and every
    peer converges — with the wire/fallback split visible in /metrics."""
    from tests.cluster import Cluster, metric_value, scrape, wait_for

    async def run():
        c = await Cluster.start(3)
        from gubernator_tpu.client import V1Client

        clients = [V1Client(d.conf.grpc_address) for d in c.daemons]
        try:
            owner = c.find_owning_daemon("glob", "wk0")
            # keys co-owned by one daemon so the batch groups onto one peer
            keys = [f"wk{i}" for i in range(60)
                    if c.find_owning_daemon("glob", f"wk{i}") is owner][:6]
            assert len(keys) >= 4, "need >= _WIRE_MIN co-owned keys"
            na = c.non_owning_daemons("glob", keys[0])[0]
            cl = clients[c.daemons.index(na)]
            t = frozen_now
            reqs = [
                RateLimitRequest(
                    name="glob", unique_key=k, hits=2, limit=100,
                    duration=60_000, behavior=Behavior.GLOBAL, created_at=t,
                )
                for k in keys
            ]
            resp = await cl.get_rate_limits(reqs)
            assert all(r.error == "" and r.remaining == 98
                       for r in resp.responses)

            async def wire_sent():
                s = await scrape(na)
                return metric_value(
                    s, "gubernator_global_wire_sync_entries_total",
                    direction="sent",
                )

            async def wire_recv():
                s = await scrape(owner)
                return metric_value(
                    s, "gubernator_global_wire_sync_entries_total",
                    direction="recv",
                )

            await wait_for(wire_sent, timeout_s=15)
            await wait_for(wire_recv, timeout_s=15)
            assert await wire_sent() == len(keys)
            assert await wire_recv() == len(keys)

            # convergence: the owner applied the synced hits and broadcast;
            # every daemon's local answer agrees
            async def converged():
                for d, dcl in zip(c.daemons, clients):
                    r = await dcl.get_rate_limits(
                        [RateLimitRequest(
                            name="glob", unique_key=keys[0], hits=0,
                            limit=100, duration=60_000,
                            behavior=Behavior.GLOBAL, created_at=t,
                        )]
                    )
                    if r.responses[0].remaining != 98:
                        return 0
                return 1

            await wait_for(converged, timeout_s=15)
        finally:
            for cl in clients:
                await cl.close()
            await c.stop()

    asyncio.run(run())


def test_sync_globals_wire_disabled_falls_back(frozen_now):
    """GUBER_GLOBAL_WIRE_SYNC=false (behaviors.global_wire_sync) keeps the
    classic proto path: convergence is identical and no wire entries are
    recorded — the parity oracle for the codec."""
    from gubernator_tpu.config import BehaviorConfig
    from tests.cluster import Cluster, metric_value, scrape, wait_for

    async def run():
        beh = BehaviorConfig(
            batch_wait_ms=1.0, global_sync_wait_ms=50.0,
            batch_timeout_ms=5000.0, global_timeout_ms=5000.0,
            global_wire_sync=False,
        )
        c = await Cluster.start(2, behaviors=beh)
        from gubernator_tpu.client import V1Client

        clients = [V1Client(d.conf.grpc_address) for d in c.daemons]
        try:
            owner = c.find_owning_daemon("glob", "fb0")
            keys = [f"fb{i}" for i in range(60)
                    if c.find_owning_daemon("glob", f"fb{i}") is owner][:5]
            na = c.non_owning_daemons("glob", keys[0])[0]
            cl = clients[c.daemons.index(na)]
            t = frozen_now
            await cl.get_rate_limits([
                RateLimitRequest(
                    name="glob", unique_key=k, hits=1, limit=100,
                    duration=60_000, behavior=Behavior.GLOBAL, created_at=t,
                )
                for k in keys
            ])

            async def owner_applied():
                s = await scrape(owner)
                return metric_value(
                    s, "gubernator_broadcast_counter_total",
                    condition="broadcast",
                )

            await wait_for(owner_applied, timeout_s=15)
            s = await scrape(na)
            assert metric_value(
                s, "gubernator_global_wire_sync_entries_total",
                direction="sent",
            ) == 0
        finally:
            for cl in clients:
                await cl.close()
            await c.stop()

    asyncio.run(run())
