"""GLOBAL-behavior convergence tests — the analog of the reference's
TestGlobalBehavior suite (functional_test.go:1760-2167), which asserts exact
broadcast/update counts via metrics scraping and verifies every peer converges
to the same remaining."""

import numpy as np
import pytest

import jax

from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.parallel.global_sync import GlobalShardedEngine
from gubernator_tpu.parallel.mesh import shard_of
from gubernator_tpu.hashing import fingerprint
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, Status, MINUTE


def greq(key, hits=1, limit=100, behavior=Behavior.GLOBAL, created_at=None,
         algorithm=Algorithm.TOKEN_BUCKET):
    return RateLimitRequest(
        name="glob", unique_key=key, hits=hits, limit=limit, duration=MINUTE,
        algorithm=algorithm, behavior=behavior, created_at=created_at,
    )


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def owner_of(key: str, n: int = 8) -> int:
    return int(shard_of(np.array([fingerprint("glob", key)], dtype=np.int64), n)[0])


def non_owner_of(key: str, n: int = 8) -> int:
    return (owner_of(key, n) + 1) % n


def test_global_hits_flow_to_owner_and_broadcast_back(mesh, frozen_now):
    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    t = frozen_now
    key = "gk1"
    home = non_owner_of(key)

    # 5 hits arrive at a NON-owner: answered locally, queued for the owner
    for i in range(5):
        (r,) = eng.check([greq(key, created_at=t)], now_ms=t, home_shard=home)
        assert r.status == Status.UNDER_LIMIT
    assert eng.global_stats.hits_queued == 5
    assert eng.global_stats.send_queue_length == 1  # aggregated per key

    # sync tick: owner applies the aggregated 5 hits, broadcasts to replicas
    eng.sync(now_ms=t)
    assert eng.global_stats.sync_rounds == 1
    assert eng.global_stats.broadcasts_applied == 1
    assert eng.global_stats.updates_installed == 7  # every non-owner installs
    assert eng.global_stats.send_queue_length == 0

    # the authoritative state on the owner reflects all 5 hits: a zero-hit
    # probe routed through the normal (owner) path reports remaining 95
    (r,) = eng.check([greq(key, hits=0, behavior=0, created_at=t)], now_ms=t)
    assert r.remaining == 95

    # every replica converges: a GLOBAL read at ANY home shard sees 95
    for home2 in range(8):
        (r,) = eng.check([greq(key, hits=0, created_at=t)], now_ms=t, home_shard=home2)
        assert r.remaining == 95, f"replica at shard {home2} did not converge"


def test_global_over_limit_converges(mesh, frozen_now):
    # reference TestGlobalRateLimitsPeerOverLimit (functional_test.go:1094):
    # spend within the limit, sync, then over-ask — the owner applies the
    # accumulated hits with DRAIN_OVER_LIMIT forced (gubernator.go:526-532)
    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    t = frozen_now
    key = "gk-over"
    home = non_owner_of(key)
    (r,) = eng.check([greq(key, hits=3, limit=5, created_at=t)], now_ms=t,
                     home_shard=home)
    assert r.remaining == 2 and r.status == Status.UNDER_LIMIT
    eng.sync(now_ms=t)
    # replica over-ask: rejected locally without consuming, hits still queued
    (r,) = eng.check([greq(key, hits=3, limit=5, created_at=t)], now_ms=t,
                     home_shard=home)
    assert r.status == Status.OVER_LIMIT and r.remaining == 2
    eng.sync(now_ms=t)
    # owner applied 3 > 2 with DRAIN forced → drained to 0, everywhere
    for home2 in range(8):
        (r,) = eng.check([greq(key, hits=0, limit=5, created_at=t)], now_ms=t,
                         home_shard=home2)
        assert r.remaining == 0, f"shard {home2}"
    (r,) = eng.check([greq(key, hits=1, limit=5, created_at=t)], now_ms=t,
                     home_shard=home)
    assert r.status == Status.OVER_LIMIT


def test_global_hits_from_multiple_homes_aggregate(mesh, frozen_now):
    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    t = frozen_now
    key = "gk-multi"
    # hits land on several different non-owner homes before one sync
    homes = [h for h in range(8) if h != owner_of(key)][:4]
    for h in homes:
        eng.check([greq(key, hits=2, created_at=t)], now_ms=t, home_shard=h)
    eng.sync(now_ms=t)
    # owner must have applied 4 homes x 2 hits = 8
    (r,) = eng.check([greq(key, hits=0, behavior=0, created_at=t)], now_ms=t)
    assert r.remaining == 92


def test_global_leaky_bucket(mesh, frozen_now):
    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    t = frozen_now
    key = "gk-leaky"
    home = non_owner_of(key)
    (r,) = eng.check(
        [greq(key, hits=4, limit=10, algorithm=Algorithm.LEAKY_BUCKET, created_at=t)],
        now_ms=t, home_shard=home,
    )
    assert r.remaining == 6
    eng.sync(now_ms=t)
    for home2 in range(8):
        (r,) = eng.check(
            [greq(key, hits=0, limit=10, algorithm=Algorithm.LEAKY_BUCKET,
                  created_at=t)],
            now_ms=t, home_shard=home2,
        )
        assert r.remaining == 6


def test_zero_hit_global_not_queued(mesh, frozen_now):
    # reference global.go:85-89: Hits == 0 is never queued
    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    t = frozen_now
    eng.check([greq("gk-z", hits=0, created_at=t)], now_ms=t, home_shard=1)
    assert eng.global_stats.hits_queued == 0
    assert eng.global_stats.send_queue_length == 0


def test_mixed_global_and_plain(mesh, frozen_now):
    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    t = frozen_now
    out = eng.check(
        [greq("gm1", created_at=t),
         RateLimitRequest(name="glob", unique_key="plain1", hits=1, limit=7,
                          duration=MINUTE, created_at=t),
         greq("gm2", created_at=t)],
        now_ms=t, home_shard=2,
    )
    assert out[0].remaining == 99
    assert out[1].remaining == 6
    assert out[2].remaining == 99


def test_pipelined_hooks_match_serial_path(mesh, frozen_now):
    """The prepare/issue/finish hooks (the pipelined front-door path for
    GLOBAL batches — replaces round 4's can_pipeline veto) must produce the
    same responses, queue state, and counters as the serial check_columns
    on a twin engine, for a mixed GLOBAL + plain batch with duplicates."""
    from gubernator_tpu.ops.batch import columns_from_requests
    from gubernator_tpu.ops.engine import (
        finish_check_columns,
        issue_check_columns,
        prepare_check_columns,
    )

    t = frozen_now
    reqs = (
        [greq(f"pk{i}", behavior=0, created_at=t) for i in range(4)]
        + [greq(f"gk{i}", created_at=t) for i in range(6)]
        + [greq("gk0", hits=2, created_at=t)]  # duplicate GLOBAL key
        + [greq("pk0", behavior=0, created_at=t)]  # duplicate plain key
    )
    cols = columns_from_requests(reqs)

    serial = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    rc_serial = serial.check_columns(cols, now_ms=t)

    piped = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    pending = prepare_check_columns(piped, cols, now_ms=t)
    from gubernator_tpu.parallel.global_sync import GlobalPending

    assert isinstance(pending, GlobalPending)  # GLOBAL rows → custom pending
    pending = issue_check_columns(piped, pending)
    rc_piped, delta = finish_check_columns(piped, pending, lambda fn: fn())
    piped.stats.merge(delta)

    np.testing.assert_array_equal(rc_piped.status, rc_serial.status)
    np.testing.assert_array_equal(rc_piped.remaining, rc_serial.remaining)
    np.testing.assert_array_equal(rc_piped.reset_time, rc_serial.reset_time)
    np.testing.assert_array_equal(rc_piped.err, rc_serial.err)

    # queue state equal: same homes, same per-key accumulated hits
    for ps, pp in zip(serial.pending, piped.pending):
        assert len(ps) == len(pp)
        if len(ps):
            np.testing.assert_array_equal(
                np.sort(ps.hb.fp), np.sort(pp.hb.fp)
            )
            order_s, order_p = np.argsort(ps.hb.fp), np.argsort(pp.hb.fp)
            np.testing.assert_array_equal(
                ps.hits[order_s], pp.hits[order_p]
            )
    assert serial.global_stats.hits_queued == piped.global_stats.hits_queued
    assert serial.stats.cache_hits == piped.stats.cache_hits
    assert serial.stats.cache_misses == piped.stats.cache_misses
    assert serial.stats.checks == piped.stats.checks

    # both sides reconcile identically at the next sync tick
    serial.sync(now_ms=t)
    piped.sync(now_ms=t)
    assert (
        serial.global_stats.broadcasts_applied
        == piped.global_stats.broadcasts_applied
    )
    assert (
        serial.global_stats.updates_installed
        == piped.global_stats.updates_installed
    )


def test_pipelined_hooks_pure_local_falls_through(mesh, frozen_now):
    """Batches without GLOBAL rows return None from prepare_columns and ride
    the generic pipelined path."""
    from gubernator_tpu.ops.batch import columns_from_requests
    from gubernator_tpu.ops.engine import PendingCheck, prepare_check_columns

    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024)
    cols = columns_from_requests(
        [greq(f"k{i}", behavior=0, created_at=frozen_now) for i in range(4)]
    )
    pending = prepare_check_columns(eng, cols, now_ms=frozen_now)
    assert isinstance(pending, PendingCheck)


def test_fused_sync_drain_matches_serial_rounds(mesh, frozen_now):
    """A deep backlog drains through the fused multi-round step (ONE launch
    runs R rounds on-device); tables, replica state, and reconcile counters
    must match an identical engine drained round-by-round."""
    import jax.numpy as jnp

    from gubernator_tpu.ops.batch import columns_from_requests

    t = frozen_now

    def load(eng):
        # queue 3x sync_out entries per round-robin home → multi-round drain
        for batch in range(3):
            reqs = [
                greq(f"fk{batch}_{i}", hits=2, created_at=t) for i in range(64)
            ]
            eng.check_columns(columns_from_requests(reqs), now_ms=t)

    serial = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=16)
    fused = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=16)
    load(serial)
    load(fused)
    assert serial.global_stats.send_queue_length == \
        fused.global_stats.send_queue_length > 16

    # serial: force round-by-round; fused: the sync() fast path
    while serial.has_pending():
        serial._sync_round(now_ms=t)
    fused.sync(now_ms=t)

    assert not fused.has_pending()
    # padded no-op rounds are excluded from the counter: identical traffic
    # reports identical sync_rounds whichever drain path ran
    assert serial.global_stats.sync_rounds == fused.global_stats.sync_rounds
    assert (
        serial.global_stats.broadcasts_applied
        == fused.global_stats.broadcasts_applied
    )
    assert (
        serial.global_stats.updates_installed
        == fused.global_stats.updates_installed
    )
    assert bool(jnp.array_equal(serial.table.rows, fused.table.rows))
    assert bool(jnp.array_equal(serial.replica.rows, fused.replica.rows))

    # post-drain responses agree from any home (replica-served reads)
    probe = [greq("fk1_3", hits=0, created_at=t)]
    for home in range(8):
        (a,) = serial.check(probe, now_ms=t, home_shard=home)
        (b,) = fused.check(probe, now_ms=t, home_shard=home)
        assert (a.status, a.remaining) == (b.status, b.remaining)


def test_warm_sync_steps_pretraces_fused_variants(mesh, frozen_now):
    """warm_sync_steps compiles the single-round + every fused-R sync step
    with empty no-op outboxes, leaving state and counters untouched after
    the caller's reset — the first deep backlog must not compile on the
    serving path."""
    from gubernator_tpu.parallel.global_sync import GlobalStats

    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=16)
    eng.warm_sync_steps(now_ms=frozen_now)
    # fused steps key by (rounds, compact-wire?); compact engines warm BOTH
    # outbox formats per R, full-width ones just their own — either way
    # every R variant must be pre-traced
    assert sorted({r for r, _w in eng._sync_multi}) == [2, 4, 8, 16, 32, 64]
    eng.global_stats = GlobalStats()

    # a warm engine still reconciles correctly (state untouched by no-ops)
    key = "wk1"
    home = non_owner_of(key)
    for _ in range(3):
        eng.check([greq(key, created_at=frozen_now)], now_ms=frozen_now,
                  home_shard=home)
    eng.sync(now_ms=frozen_now)
    assert eng.global_stats.broadcasts_applied == 1
    (r,) = eng.check(
        [greq(key, hits=0, created_at=frozen_now)], now_ms=frozen_now,
        home_shard=owner_of(key),
    )
    assert r.remaining == 97


def test_store_engine_sync_stays_serial(mesh, frozen_now):
    """Store-configured engines must drain round-by-round: the fused step
    returns no per-round bc, and the Store write-through depends on it —
    every reconciled entry must reach on_change even on a deep backlog."""
    from gubernator_tpu.ops.batch import columns_from_requests
    from gubernator_tpu.store import RecordingStore

    t = frozen_now
    store = RecordingStore()
    eng = GlobalShardedEngine(
        mesh, capacity_per_shard=1024, sync_out=16, store=store
    )
    # queue a backlog deeper than one round per home
    for batch in range(3):
        reqs = [greq(f"sk{batch}_{i}", hits=1, created_at=t) for i in range(64)]
        eng.check_columns(columns_from_requests(reqs), now_ms=t)
    # the fused-vs-serial choice keys on PER-HOME depth, not the global sum
    assert max(len(p) for p in eng.pending) > eng.sync_out
    # check-time deliveries (owner-here rows write through immediately,
    # like the reference's owner-side getLocalRateLimit OnChange)
    n_check = sum(len(ch.fps) for ch in store.changes)
    eng.sync(now_ms=t)
    assert not eng.has_pending()
    assert not eng._sync_multi  # fused variants never built
    # the sync drain delivers every reconciled entry EXACTLY once via the
    # per-round bc — the raw count catches double deliveries the set alone
    # would hide (owner-here keys legitimately appear a second time: their
    # check-time apply was its own state change)
    synced_fps = [
        fp for ch in store.changes for fp in np.asarray(ch.fps).tolist()
    ][n_check:]
    assert len(synced_fps) == 192
    assert len(set(synced_fps)) == 192
    assert store.touched_fps >= set(synced_fps)


def test_sync_launch_failure_requeues_hits_and_poisons(mesh, frozen_now):
    """A collective sync launch that dies AFTER the accumulators were popped
    must not lose the hits: the popped boxes re-merge into
    pending, and the engine is marked poisoned so health surfaces unhealthy
    instead of serving from the donated (now-suspect) tables."""
    eng = GlobalShardedEngine(mesh, capacity_per_shard=1024, sync_out=64)
    t = frozen_now
    for i in range(6):
        eng.check([greq(f"rq{i}", hits=2, created_at=t)], now_ms=t,
                  home_shard=i % 8)
    queued_before = eng.global_stats.send_queue_length
    assert queued_before == 6
    # per-home breakdown must survive the failure round-trip exactly
    pending_before = [len(p) for p in eng.pending]
    per_key_hits = {
        int(fp): int(h)
        for p in eng.pending if len(p)
        for fp, h in zip(p.hb.fp, p.hits)
    }

    eng._ensure_global_plane()

    class Boom(RuntimeError):
        pass

    def dead_step(*_a, **_k):
        raise Boom("donated launch died")

    # stub BOTH outbox formats: which one the round takes depends on the
    # engine's wire mode (compact ships the int32 grid step)
    eng._sync_step = dead_step
    eng._sync_step_wire = dead_step
    with pytest.raises(Boom):
        eng._sync_round(now_ms=t)

    assert [len(p) for p in eng.pending] == pending_before
    assert eng.global_stats.send_queue_length == queued_before
    after = {
        int(fp): int(h)
        for p in eng.pending if len(p)
        for fp, h in zip(p.hb.fp, p.hits)
    }
    assert after == per_key_hits
    assert eng.poisoned is not None and "sync" in eng.poisoned

    # a healthy step afterwards drains the re-merged hits (fresh engine
    # state validates the re-merge kept well-formed columns)
    eng._sync_step = None
    eng._sync_step_wire = None
    eng._ensure_global_plane()
    eng.sync(now_ms=t)
    assert eng.global_stats.send_queue_length == 0
    assert eng.global_stats.broadcasts_applied == 6
