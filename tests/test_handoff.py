"""Topology-change survivability: device-side ownership handoff.

Covers the handoff stack bottom-up: the extract/merge/tombstone device ops
(ops/table2, kernel2.merge2) and their conservative-merge invariant, the
fp→ring-point ownership sidecar (peers/ownership.py), the vectorized
ring-successor lookup, the set_peers churn satellites (breaker preservation,
dropped-client drain leak), the TransferState RPC's idempotency, and the
cluster-level flows: scale-out rebalance, graceful drain + hand-back on a
rolling restart, and breaker-gated chunk retry against real injected faults
(tests/chaos.py). The long multi-restart chaos scenario is tier-1; see
test_chaos.py for the PR-1 fault-tolerance suite it builds on.
"""

import asyncio
import functools
import time

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401  (x64 on)
from gubernator_tpu.client import V1Client
from gubernator_tpu.config import BehaviorConfig, ConfigError, DaemonConfig
from gubernator_tpu.ops.batch import RequestColumns
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.ops.table2 import F, LIMIT, REM_I, STAMP_HI, STAMP_LO
from gubernator_tpu.peers.hash_ring import ReplicatedConsistentHash
from gubernator_tpu.peers.ownership import OwnershipIndex
from gubernator_tpu.service.breaker import BreakerState
from gubernator_tpu.types import PeerInfo, RateLimitRequest

from tests.cluster import Cluster, metric_value, scrape, wait_for

NOW = 1_700_000_000_000


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def req(key, name="ho", hits=1, limit=10, burst=0, duration=600_000):
    return RateLimitRequest(
        name=name, unique_key=key, hits=hits, limit=limit, burst=burst,
        duration=duration,
    )


def cols(fp, hits=3, limit=10, algo=None, duration=600_000, now=NOW):
    n = fp.shape[0]
    if algo is None:
        algo = (np.arange(n) % 2).astype(np.int32)  # token + leaky mix
    return RequestColumns(
        fp=fp.astype(np.int64),
        algo=algo,
        behavior=np.zeros(n, dtype=np.int32),
        hits=np.full(n, hits, dtype=np.int64),
        limit=np.full(n, limit, dtype=np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, duration, dtype=np.int64),
        created_at=np.full(n, now, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


# ------------------------------------------------- device ops: extract/merge


def test_extract_merge_tombstone_roundtrip():
    """Extract packs exactly the live rows; merging them into a fresh table
    reproduces the counters (token AND leaky); tombstone removes them at the
    source. The no-fault row-parity the chaos acceptance asserts, at the
    engine level."""
    src = LocalEngine(capacity=4096, write_mode="xla")
    n = 200
    fp = np.arange(1, n + 1, dtype=np.int64) * 7919
    src.check_columns(cols(fp), now_ms=NOW)
    fps, slots = src.extract_live(NOW)
    assert fps.shape == (n,) and slots.shape == (n, F)
    assert set(fps.tolist()) == set(fp.tolist())

    dst = LocalEngine(capacity=4096, write_mode="xla")
    assert dst.merge_rows(fps, slots, now_ms=NOW) == n
    rc = dst.check_columns(cols(fp, hits=0), now_ms=NOW)
    assert (rc.remaining == 7).all()

    assert src.tombstone_fps(fps) == n
    assert src.live_count(NOW) == 0
    fps2, _ = src.extract_live(NOW)
    assert fps2.shape[0] == 0
    # tombstoning missing fps is a no-op, not an eviction
    assert dst.tombstone_fps(np.asarray([999_999_999], dtype=np.int64)) == 0
    assert dst.live_count(NOW) == n


def test_conservative_merge_never_grants_capacity():
    """The invariant that makes transfers retry-safe: remaining=min. A
    duplicated chunk, a crossed transfer, or a stale source row can never
    raise remaining above the receiver's current state."""
    eng = LocalEngine(capacity=1024, write_mode="xla")
    fp = np.asarray([1234567], dtype=np.int64)
    eng.check_columns(cols(fp, hits=3, algo=np.zeros(1, np.int32)), now_ms=NOW)
    stale_fps, stale_slots = eng.extract_live(NOW)  # remaining = 7

    # spend 4 more → remaining 3; merging the stale (remaining 7) snapshot
    # back must NOT resurrect capacity
    eng.check_columns(cols(fp, hits=4, algo=np.zeros(1, np.int32)), now_ms=NOW)
    eng.merge_rows(stale_fps, stale_slots, now_ms=NOW)
    rc = eng.check_columns(cols(fp, hits=0, algo=np.zeros(1, np.int32)), now_ms=NOW)
    assert int(rc.remaining[0]) == 3

    # idempotent replay: merging twice is the same as once
    eng.merge_rows(stale_fps, stale_slots, now_ms=NOW)
    rc = eng.check_columns(cols(fp, hits=0, algo=np.zeros(1, np.int32)), now_ms=NOW)
    assert int(rc.remaining[0]) == 3


def test_merge_duplicate_fps_single_slot():
    """A crossed transfer can carry the same fingerprint twice in one chunk:
    duplicates must merge sequentially (the claim machinery's unique-fp
    contract) — never land in two slots, where the stale copy could later
    resurrect capacity."""
    src = LocalEngine(capacity=1024, write_mode="xla")
    fp = np.asarray([555], dtype=np.int64)
    src.check_columns(cols(fp, algo=np.zeros(1, np.int32)), now_ms=NOW)
    fps, slots = src.extract_live(NOW)
    dst = LocalEngine(capacity=1024, write_mode="xla")
    assert dst.merge_rows(
        np.concatenate([fps, fps]), np.concatenate([slots, slots]), now_ms=NOW
    ) == 2
    assert dst.live_count(NOW) == 1
    rc = dst.check_columns(cols(fp, hits=0, algo=np.zeros(1, np.int32)), now_ms=NOW)
    assert int(rc.remaining[0]) == 7


def test_merge_newest_config_wins_and_expired_dropped():
    eng = LocalEngine(capacity=1024, write_mode="xla")
    fp = np.asarray([42424242], dtype=np.int64)
    eng.check_columns(cols(fp, hits=2, limit=10, algo=np.zeros(1, np.int32)), now_ms=NOW)
    fps, slots = eng.extract_live(NOW)

    # incoming row with a NEWER stamp and a different limit: config follows
    # the newer stamp, remaining stays min (read back via the stored slot —
    # response `limit` always echoes the request's)
    newer = slots.copy()
    newer[0, LIMIT] = 50
    stamp = NOW + 5_000
    newer[0, STAMP_LO] = np.int64(stamp).astype(np.int32)  # low 32, wrapped
    newer[0, STAMP_HI] = np.int32(stamp >> 32)
    eng.merge_rows(fps, newer, now_ms=NOW)
    _, stored = eng.extract_live(NOW)
    assert int(stored[0, LIMIT]) == 50
    assert int(stored[0, REM_I]) == 8  # min(8, 8): capacity not re-granted

    # an OLDER stamp must not roll the config back
    older = slots.copy()
    older[0, LIMIT] = 5
    eng.merge_rows(fps, older, now_ms=NOW)
    _, stored = eng.extract_live(NOW)
    assert int(stored[0, LIMIT]) == 50
    assert int(stored[0, REM_I]) == 8

    # fully expired incoming rows are dropped, not resurrected
    dst = LocalEngine(capacity=1024, write_mode="xla")
    assert dst.merge_rows(fps, slots, now_ms=NOW + 700_000) == 0
    assert dst.live_count(NOW + 700_000) == 0


def test_sharded_extract_merge_tombstone_parity():
    """Same surface on the 8-device CPU mesh: extract from a sharded source,
    conservative-merge into a sharded destination, tombstone at the source
    — zero rows lost."""
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.sharded import ShardedEngine

    mesh = make_mesh(8)
    src = ShardedEngine(mesh, capacity_per_shard=1 << 12, write_mode="xla")
    rng = np.random.default_rng(11)
    n = 700
    fp = rng.integers(1, (1 << 63) - 1, size=n, dtype=np.int64)
    src.check_columns(cols(fp), now_ms=NOW)
    fps, slots = src.extract_live(NOW)
    assert set(fps.tolist()) == set(fp.tolist())

    dst = ShardedEngine(mesh, capacity_per_shard=1 << 12, write_mode="xla")
    assert dst.merge_rows(fps, slots, now_ms=NOW) == n
    rc = dst.check_columns(cols(fp, hits=0), now_ms=NOW)
    assert (rc.remaining == 7).all()
    # replay (idempotent) + conservative floor after further spend
    dst.check_columns(cols(fp, hits=2), now_ms=NOW)
    dst.merge_rows(fps, slots, now_ms=NOW)
    rc = dst.check_columns(cols(fp, hits=0), now_ms=NOW)
    assert (rc.remaining == 5).all()
    assert src.tombstone_fps(fps) == n
    assert src.live_count(NOW) == 0


# -------------------------------------------------- sidecar + ring successor


def test_ownership_index_record_lookup_prune():
    idx = OwnershipIndex()
    fps = np.asarray([3, 1, 2], dtype=np.int64)
    pts = np.asarray([30, 10, 20], dtype=np.uint32)
    idx.record(fps, pts)
    assert len(idx) == 3
    points, found = idx.points_for(np.asarray([2, 9, 1], dtype=np.int64))
    assert found.tolist() == [True, False, True]
    assert points.tolist() == [20, 0, 10]
    idx.discard(np.asarray([1], dtype=np.int64))
    assert len(idx) == 2
    assert idx.prune(np.asarray([3], dtype=np.int64)) == 1
    assert len(idx) == 1
    # record_keys matches the picker's own hash function
    ring = ReplicatedConsistentHash()
    idx.record_keys([7], ["a_b"], ring.hash_fn)
    points, found = idx.points_for(np.asarray([7], dtype=np.int64))
    assert found[0] and int(points[0]) == ring.hash_fn(b"a_b")


def test_owners_of_exclude_matches_get_exclude():
    """The vectorized drain lookup (owners_of(points, exclude)) must agree
    with the scalar route-around primitive (get(key, exclude)) — the drain
    hands rows exactly to the owners the surviving ring will resolve."""
    ring = ReplicatedConsistentHash()
    peers = [PeerInfo(grpc_address=f"10.0.0.{i}:80") for i in range(4)]
    for p in peers:
        ring.add(p)
    keys = [f"name_k{i}" for i in range(64)]
    points = np.asarray([ring.hash_fn(k.encode()) for k in keys], np.uint32)
    gone = frozenset({peers[1].grpc_address})
    vec = ring.owners_of(points, exclude=gone)
    for k, owner in zip(keys, vec):
        assert owner.grpc_address == ring.get(k, gone).grpc_address
        assert owner.grpc_address not in gone
    with pytest.raises(RuntimeError):
        ring.owners_of(points, exclude=frozenset(p.grpc_address for p in peers))


# --------------------------------------------------- set_peers satellites


def test_handoff_config_knobs():
    from gubernator_tpu.config import setup_daemon_config

    conf = setup_daemon_config(env={
        "GUBER_HANDOFF_DEADLINE": "2s",
        "GUBER_HANDOFF_CHUNK_ROWS": "128",
        "GUBER_HANDOFF_ENABLED": "false",
    })
    assert conf.behaviors.handoff_deadline_ms == 2000.0
    assert conf.behaviors.handoff_chunk_rows == 128
    assert conf.behaviors.handoff_enabled is False
    with pytest.raises(ConfigError):
        DaemonConfig(
            behaviors=BehaviorConfig(handoff_chunk_rows=0)
        ).validate()
    with pytest.raises(ConfigError):
        DaemonConfig(
            behaviors=BehaviorConfig(handoff_deadline_ms=0)
        ).validate()


def test_set_peers_no_loop_queues_dropped_clients_for_drain():
    """Satellite: with no running event loop, set_peers used to swallow the
    RuntimeError and LEAK dropped PeerClient channels. They now queue and
    close on the next loop entry."""
    conf = DaemonConfig(
        grpc_address="127.0.0.1:19251", cache_size=1024,
    )
    d = None
    try:
        from gubernator_tpu.service.daemon import Daemon

        d = Daemon(conf)
        peers = [
            PeerInfo(grpc_address="127.0.0.1:19251"),
            PeerInfo(grpc_address="127.0.0.1:19252"),
            PeerInfo(grpc_address="127.0.0.1:19253"),
        ]
        d.set_peers([PeerInfo(**vars(p)) for p in peers])
        clients = list(d._peer_clients.values())
        assert len(clients) == 2
        # shrink with NO loop running: clients must queue, not leak
        d.set_peers([PeerInfo(**vars(peers[0]))])
        assert len(d._orphaned_clients) == 2
        assert not any(c._closed for c in clients)

        async def enter_loop():
            # next loop entry: any set_peers flushes the orphan queue
            d.set_peers([PeerInfo(**vars(peers[0]))])
            await asyncio.sleep(0.05)

        asyncio.run(enter_loop())
        assert d._orphaned_clients == []
        assert all(c._closed for c in clients)
    finally:
        if d is not None:
            d.runner.close()


@async_test
async def test_set_peers_churn_reuses_clients_and_preserves_breakers():
    """Satellite: repeated add/remove cycles must reuse PeerClients by
    address while present, and a peer that flaps OUT and back IN must keep
    its breaker state — a flapping discovery backend must not reset open
    breakers to closed."""
    c = await Cluster.start(3, handoff_enabled=False)
    d0 = c.daemons[0]
    addr1 = c.daemons[1].conf.advertise_address
    try:
        all_peers = [d.peer_info() for d in c.daemons]
        client_before = d0._peer_clients[addr1]
        # same peer set again: client objects are reused by address
        d0.set_peers([PeerInfo(**vars(p)) for p in all_peers])
        assert d0._peer_clients[addr1] is client_before

        # trip the breaker, then flap the peer out and back in
        breaker = client_before.breaker
        for _ in range(10):
            breaker.record_failure()
        assert breaker.state is BreakerState.OPEN
        without = [p for p in all_peers if p.grpc_address != addr1]
        for cycle in range(3):
            d0.set_peers([PeerInfo(**vars(p)) for p in without])
            assert addr1 not in d0._peer_clients
            d0.set_peers([PeerInfo(**vars(p)) for p in all_peers])
            got = d0._peer_clients[addr1]
            assert got.breaker is breaker, f"cycle {cycle}"
            assert got.breaker.state is BreakerState.OPEN, f"cycle {cycle}"
        await asyncio.sleep(0.05)  # orphaned clients drain on the loop
    finally:
        await c.stop()


# ------------------------------------------------------ TransferState RPC


@async_test
async def test_transfer_state_idempotent_and_validated():
    from gubernator_tpu.proto import handoff_pb2 as handoff_pb
    from gubernator_tpu.service.wire import transfer_chunk_pb

    c = await Cluster.start(1)
    d = c.daemons[0]
    try:
        src = LocalEngine(capacity=1024, write_mode="xla")
        fp = np.arange(1, 33, dtype=np.int64) * 101
        now = d.now_ms()
        src.check_columns(cols(fp, now=now), now_ms=now)
        fps, slots = src.extract_live(now)
        pts = np.arange(fps.shape[0], dtype=np.uint32)
        req_pb = transfer_chunk_pb("t-1", 0, 1, "src:1", now, fps, pts, slots)

        r1 = await d.transfer_state(req_pb)
        assert r1.merged == 32 and not r1.duplicate
        # the receiver recorded the rows' ring points for onward routing
        points, found = d.ownership.points_for(fps)
        assert found.all() and (points == pts).all()
        # replayed chunk: answered from the ledger, no double merge
        r2 = await d.transfer_state(req_pb)
        assert r2.duplicate and r2.merged == 32
        assert await d.runner.live_count() == 32

        # malformed buffers fail loudly instead of merging garbage
        bad = handoff_pb.TransferStateReq()
        bad.CopyFrom(req_pb)
        bad.transfer_id = "t-2"
        bad.fps = bad.fps[:-8]
        with pytest.raises(ValueError):
            await d.transfer_state(bad)
    finally:
        await c.stop()


# ------------------------------------------------------- cluster-level flows


@async_test
async def test_scale_out_rebalance_moves_state():
    """set_peers diff path: adding a daemon launches a device-side extract
    at the old owners, and keys whose ring owner moved keep their counters
    at the new owner (conservative-merged, not answered fresh)."""
    from gubernator_tpu.service.daemon import Daemon
    from tests.cluster import daemon_config

    c = await Cluster.start(2)
    client = V1Client(c.daemons[0].conf.grpc_address)
    extra = None
    try:
        keys = [f"mv{i}" for i in range(24)]
        rs = (await client.get_rate_limits(
            [req(k, hits=4) for k in keys]
        )).responses
        assert all(r.error == "" and r.remaining == 6 for r in rs)

        extra = await Daemon.spawn(daemon_config())
        c.daemons.append(extra)
        peers = [d.peer_info() for d in c.daemons]
        for d in c.daemons:
            d.set_peers([PeerInfo(**vars(p)) for p in peers])
        await c.settle_handoffs()

        # keys now owned by the NEW daemon must still carry their counters
        moved = [
            k for k in keys if c.find_owning_daemon("ho", k) is extra
        ]
        assert moved, "expected some keys to move to the new daemon"
        rs = (await client.get_rate_limits(
            [req(k, hits=0) for k in moved]
        )).responses
        assert all(r.remaining == 6 for r in rs), [r.remaining for r in rs]
        s = await scrape(extra)
        assert metric_value(
            s, "gubernator_handoff_rows_total", phase="merged"
        ) >= len(moved)
    finally:
        await client.close()
        if extra is not None and extra not in c.daemons:
            await extra.close()
        await c.stop()


@async_test
async def test_drain_restart_preserves_state_no_fault():
    """Graceful drain + hand-back (the rolling-restart building block),
    no-fault case: counters survive a full stop/start of their owner, the
    drained daemon advertises "leaving" while it drains, and the cluster's
    transfer row-counts are in parity (no chunk lost)."""
    c = await Cluster.start(3)
    client = V1Client(c.daemons[1].conf.grpc_address)
    try:
        keys, i = [], 0
        while len(keys) < 6:
            k = f"dr{i}"
            i += 1
            if c.find_owning_daemon("ho", k) is c.daemons[0]:
                keys.append(k)
        rs = (await client.get_rate_limits(
            [req(k, hits=3) for k in keys]
        )).responses
        assert all(r.error == "" and r.remaining == 7 for r in rs)

        # health flips to "leaving" the moment the drain starts
        statuses = []

        async def probe_leaving():
            statuses.append((await c.daemons[0].health_check()).status)

        c.daemons[0]._leaving = True
        await probe_leaving()
        c.daemons[0]._leaving = False
        assert statuses == ["leaving"]

        await c.drain_restart(0)

        rs = (await client.get_rate_limits(
            [req(k, hits=0) for k in keys]
        )).responses
        assert all(r.remaining == 7 for r in rs), [r.remaining for r in rs]

        # no-fault parity: every extracted row was merged somewhere, every
        # transferred row was tombstoned at its source (the restarted
        # daemon's own counters died with it; survivors' must balance)
        phases = {p: 0.0 for p in (
            "extracted", "transferred", "merged", "tombstoned"
        )}
        for d in c.daemons:
            s = await scrape(d)
            for p in phases:
                phases[p] += metric_value(
                    s, "gubernator_handoff_rows_total", phase=p
                )
        assert phases["merged"] >= len(keys)  # drain + hand-back both merge
        assert phases["extracted"] == phases["transferred"] == phases[
            "tombstoned"
        ]
    finally:
        await client.close()
        await c.stop()


@async_test
async def test_drain_chunk_retry_against_blackhole_then_heal():
    """Breaker-driven retry of failed transfer chunks: a blackholed
    destination makes chunks fail (and retry) until the proxy heals inside
    the deadline — after which every row lands; nothing is lost."""
    c = await Cluster.start(
        2,
        chaos=True,
        behaviors=BehaviorConfig(
            batch_wait_ms=1.0,
            batch_timeout_ms=300.0,
            global_timeout_ms=300.0,
            peer_breaker_errors=2,
            peer_breaker_backoff_base_ms=100.0,
            peer_breaker_backoff_cap_ms=200.0,
            handoff_deadline_ms=8_000.0,
            handoff_chunk_rows=8,
        ),
    )
    d0, d1 = c.daemons
    client = V1Client(d0.conf.grpc_address)
    try:
        keys, i = [], 0
        while len(keys) < 10:
            k = f"bh{i}"
            i += 1
            if c.find_owning_daemon("ho", k) is d0:
                keys.append(k)
        await client.get_rate_limits([req(k, hits=3) for k in keys])
        live_before = await d0.runner.live_count()

        # blackhole the destination, heal it mid-drain
        c.proxy_for(d1).set_mode("blackhole")

        async def heal_later():
            await asyncio.sleep(1.0)
            c.proxy_for(d1).heal()

        heal = asyncio.create_task(heal_later())
        stats = await d0.handoff.drain()
        await heal
        assert stats["extracted"] == len(keys)
        assert stats["transferred"] == len(keys)  # retried through the fault
        assert stats["snapshotted"] == 0
        s = await scrape(d0)
        assert metric_value(s, "gubernator_handoff_chunk_retries_total") >= 1
        assert await d0.runner.live_count() == live_before - len(keys)
        assert await d1.runner.live_count() >= len(keys)
    finally:
        await client.close()
        await c.stop()


@async_test
async def test_drain_deadline_snapshots_unacked_remainder():
    """A destination that never heals: the drain gives up at the deadline,
    keeps the unacked rows in the table (they reach the shutdown checkpoint)
    and counts them `snapshotted`."""
    c = await Cluster.start(
        2,
        chaos=True,
        behaviors=BehaviorConfig(
            batch_wait_ms=1.0,
            batch_timeout_ms=200.0,
            peer_breaker_errors=2,
            peer_breaker_backoff_base_ms=100.0,
            peer_breaker_backoff_cap_ms=200.0,
            handoff_deadline_ms=900.0,
            handoff_chunk_rows=8,
        ),
    )
    d0, d1 = c.daemons
    client = V1Client(d0.conf.grpc_address)
    try:
        keys, i = [], 0
        while len(keys) < 6:
            k = f"dl{i}"
            i += 1
            if c.find_owning_daemon("ho", k) is d0:
                keys.append(k)
        await client.get_rate_limits([req(k, hits=3) for k in keys])
        live_before = await d0.runner.live_count()
        c.proxy_for(d1).set_mode("blackhole")
        t0 = time.perf_counter()
        stats = await d0.handoff.drain()
        assert time.perf_counter() - t0 < 5.0  # bounded by the deadline
        assert stats["extracted"] == len(keys)
        assert stats["transferred"] == 0
        assert stats["snapshotted"] == len(keys)
        # nothing tombstoned: the rows survive into the shutdown checkpoint
        assert await d0.runner.live_count() == live_before
    finally:
        await client.close()
        await c.stop()


# --------------------------------------- acceptance: rolling restart, chaos


@async_test
async def test_rolling_restart_under_traffic_bounded_over_admission():
    """The ISSUE's acceptance scenario: a 3-daemon cluster under continuous
    traffic, every daemon drained and restarted in turn, a chaos delay
    injected mid-handoff on one cycle. Every key's total admissions stay
    within one configured burst of the limit (the conservative-merge bound —
    without handoff each ownership move re-grants a full fresh bucket), and
    traffic never sees errors."""
    LIMIT_N, BURST = 25, 25
    c = await Cluster.start(
        3,
        chaos=True,
        behaviors=BehaviorConfig(
            batch_wait_ms=1.0,
            batch_timeout_ms=2_000.0,
            global_timeout_ms=2_000.0,
            handoff_deadline_ms=8_000.0,
        ),
    )
    keys = [f"rr{i}" for i in range(12)]
    admitted = {k: 0 for k in keys}
    errors: list = []
    lost = [0]  # batches whose response was lost mid-close (the server may
    # have admitted them — at-least-once from the client's view)
    draining = {"i": -1}
    stop = asyncio.Event()

    async def traffic():
        clients = {}
        try:
            while not stop.is_set():
                alive = [
                    d for j, d in enumerate(c.daemons) if j != draining["i"]
                ]
                d = alive[int(time.monotonic() * 1000) % len(alive)]
                cl = clients.get(d.conf.grpc_address)
                if cl is None:
                    cl = clients[d.conf.grpc_address] = V1Client(
                        d.conf.grpc_address
                    )
                try:
                    rs = (await cl.get_rate_limits(
                        [req(k, hits=1, limit=LIMIT_N, burst=BURST)
                         for k in keys]
                    )).responses
                except Exception:
                    lost[0] += 1  # transport race with a closing daemon
                else:
                    for k, r in zip(keys, rs):
                        if r.error:
                            errors.append(r.error)
                        elif r.status == 0:  # UNDER_LIMIT → admitted
                            admitted[k] += 1
                await asyncio.sleep(0.05)
        finally:
            for cl in clients.values():
                await cl.close()

    task = asyncio.create_task(traffic())
    try:
        await asyncio.sleep(0.2)  # some budget spent before the first drain
        for i in range(3):
            draining["i"] = i
            if i == 1:
                # chaos: slow one survivor's peer plane mid-handoff — chunk
                # sends ride the delay and still land inside the deadline
                c.proxy_for(c.daemons[2]).set_mode("delay", delay_s=0.05)
            await c.drain_restart(i)
            if i == 1:
                c.proxy_for(c.daemons[2]).heal()
            draining["i"] = -1
            await asyncio.sleep(0.3)
        # run until every key is exhausted (all daemons serving)
        async def all_over():
            cl = V1Client(c.daemons[0].conf.grpc_address)
            try:
                rs = (await cl.get_rate_limits(
                    [req(k, hits=0, limit=LIMIT_N, burst=BURST)
                     for k in keys]
                )).responses
                return all(r.remaining == 0 for r in rs)
            finally:
                await cl.close()

        await wait_for(all_over, timeout_s=30)
    finally:
        stop.set()
        await task
        await c.stop()

    # the occasional in-flight forward can race a de-registration; sustained
    # errors mean the routing/handoff plumbing is broken
    assert len(errors) <= 3, errors[:5]
    for k in keys:
        # conservative-merge bound: within one configured burst of the
        # limit. WITHOUT handoff each of the six ownership moves could
        # re-grant a fresh bucket (worst case ≈ limit × moves). Only the
        # UPPER bound is a sound invariant: at-least-once delivery (a
        # response lost mid-close, a forward retried after the owner
        # already applied it) spends server-side budget the client never
        # counts, so admitted can legitimately fall a few short of the
        # limit — and wait_for(all_over) already proved every bucket
        # exhausted server-side. Both failure modes only push admitted
        # DOWN; over-admission cannot hide behind them.
        assert admitted[k] <= LIMIT_N + BURST, (k, admitted[k], lost[0])
        assert admitted[k] >= LIMIT_N // 2, (k, admitted[k])  # sanity
