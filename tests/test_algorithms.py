"""ISSUE-10 algorithm-breadth suite: GCRA, sliding-window counters,
concurrency leases, and cascaded multi-limit checks.

Parity contract: every device implementation (LocalEngine full-width +
compact wire, 8-device ShardedEngine with device routing/dedup) must match
the pure-Python oracles in tests/oracle/algos.py decision-for-decision
across randomized schedules. Conservatism contract: checkpoint/handoff
replay through kernel2.merge2 can only UNDER-grant (stale GCRA TAT, stale
window counts). Cascade contract: deny-if-any, per-level responses,
(fp, level) dedup discrimination, single-dispatch evaluation.
"""

from __future__ import annotations

import asyncio
import functools

import numpy as np
import pytest

from gubernator_tpu.hashing import fingerprint
from gubernator_tpu.ops.batch import RequestColumns
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.types import Algorithm, RateLimitRequest
from tests.oracle.algos import (
    GcraOracle,
    LeaseOracle,
    SlidingWindowOracle,
    TokenOracle,
)

NOW = 1_700_000_000_000


def _cols(keys, algo, hits, limit, duration, now, burst=None, levels=None):
    n = len(keys)
    return RequestColumns(
        fp=np.array([fingerprint("alg", k) for k in keys], dtype=np.int64),
        algo=np.full(n, int(algo), dtype=np.int32),
        behavior=np.array(
            [(lvl << 8) for lvl in (levels or [0] * n)], dtype=np.int32
        ),
        hits=np.asarray(hits, dtype=np.int64),
        limit=np.asarray(limit, dtype=np.int64),
        burst=np.asarray(
            burst if burst is not None else np.zeros(n), dtype=np.int64
        ),
        duration=np.asarray(duration, dtype=np.int64),
        created_at=np.full(n, now, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


def _engines(request_mesh=None):
    """The device implementations under parity test."""
    engines = [
        ("local-full", LocalEngine(capacity=1 << 14, write_mode="xla", wire="full")),
        ("local-compact", LocalEngine(capacity=1 << 14, write_mode="xla", wire="compact")),
    ]
    if request_mesh is not None:
        from gubernator_tpu.parallel.sharded import ShardedEngine

        engines.append((
            "sharded-8dev",
            ShardedEngine(
                request_mesh, capacity_per_shard=1 << 12,
                route="device", dedup="device",
            ),
        ))
    return engines


@pytest.fixture
def mesh():
    import jax

    from gubernator_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) == 8, "tests require the 8-device CPU mesh"
    return make_mesh(8)


# ------------------------------------------------------------------ GCRA


def _gcra_schedule(rng, n_steps=40, n_keys=6):
    """Randomized (dt, key, hits) schedule with a mix of conforming and
    bursty arrivals."""
    t = NOW
    steps = []
    for _ in range(n_steps):
        t += int(rng.integers(0, 1500))
        keys = [f"g{int(k)}" for k in rng.choice(n_keys, size=rng.integers(1, 4), replace=False)]
        hits = [int(rng.integers(0, 5)) for _ in keys]
        steps.append((t, keys, hits))
    return steps


@pytest.mark.parametrize("wire", ["full", "compact"])
def test_gcra_oracle_parity_local(wire):
    rng = np.random.default_rng(7)
    eng = LocalEngine(capacity=1 << 14, write_mode="xla", wire=wire)
    oracle = GcraOracle()
    limit, dur = 10, 10_000
    for t, keys, hits in _gcra_schedule(rng):
        rc = eng.check_columns(
            _cols(keys, Algorithm.GCRA, hits, [limit] * len(keys),
                  [dur] * len(keys), t),
            now_ms=t,
        )
        for j, k in enumerate(keys):
            st, rem, reset = oracle.check(
                fingerprint("alg", k), t, hits[j], limit, dur
            )
            assert (int(rc.status[j]), int(rc.remaining[j]), int(rc.reset_time[j])) == (
                st, rem, reset
            ), (k, t, hits[j])


def test_gcra_oracle_parity_mesh(mesh):
    from gubernator_tpu.parallel.sharded import ShardedEngine

    rng = np.random.default_rng(11)
    eng = ShardedEngine(mesh, capacity_per_shard=1 << 12, route="device",
                        dedup="device")
    oracle = GcraOracle()
    limit, dur = 12, 6_000
    for t, keys, hits in _gcra_schedule(rng, n_steps=25, n_keys=24):
        rc = eng.check_columns(
            _cols(keys, Algorithm.GCRA, hits, [limit] * len(keys),
                  [dur] * len(keys), t),
            now_ms=t,
        )
        for j, k in enumerate(keys):
            st, rem, reset = oracle.check(
                fingerprint("alg", k), t, hits[j], limit, dur
            )
            assert (int(rc.status[j]), int(rc.remaining[j]), int(rc.reset_time[j])) == (
                st, rem, reset
            ), (k, t)


def test_gcra_token_equivalence_at_burst_limit():
    """With burst == limit, GCRA and the reference token bucket admit the
    same instant burst (exactly `limit` unit hits) and converge to the same
    long-run admission rate (limit per duration): across a randomized
    overloaded schedule the cumulative admitted counts never diverge by
    more than one burst."""
    rng = np.random.default_rng(13)
    eng = LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")
    tok = TokenOracle()
    limit, dur = 8, 8_000
    # instant burst: exactly `limit` admitted by both
    t = NOW
    g_admit = t_admit = 0
    for i in range(limit + 4):
        rc = eng.check_columns(
            _cols(["ge"], Algorithm.GCRA, [1], [limit], [dur], t), now_ms=t
        )
        g_admit += int(rc.status[0]) == 0
        st, _, _ = tok.check(1, t, 1, limit, dur)
        t_admit += st == 0
    assert g_admit == t_admit == limit
    # randomized OVERLOADED schedule (arrivals ~2× the sustainable rate):
    # both enforce the same long-run admission rate — limit per duration —
    # GCRA smoothly (1 per T), token in window steps, so the cumulative
    # admitted counts track within two windows' worth of quantization
    g_total = t_total = 0
    t0 = t
    for _ in range(400):
        t += int(rng.integers(0, dur // limit))
        rc = eng.check_columns(
            _cols(["gr"], Algorithm.GCRA, [1], [limit], [dur], t), now_ms=t
        )
        g_total += int(rc.status[0]) == 0
        st, _, _ = tok.check(2, t, 1, limit, dur)
        t_total += st == 0
    assert abs(g_total - t_total) <= 2 * limit, (g_total, t_total)
    # and both sit at the configured rate (±1 window) over the elapsed span
    expected = (t - t0) * limit // dur
    assert abs(g_total - expected) <= 2 * limit, (g_total, expected)


def test_gcra_drain_and_reset():
    eng = LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")
    limit, dur = 5, 5_000

    def one(key, hits, behavior, t):
        return eng.check(
            [RateLimitRequest(name="alg", unique_key=key, hits=hits,
                              limit=limit, duration=dur,
                              algorithm=Algorithm.GCRA, behavior=behavior,
                              created_at=t)],
            now_ms=t,
        )[0]

    # DRAIN_OVER_LIMIT: a denied request empties the tolerance
    assert one("d", 3, 0, NOW).status == 0
    r = one("d", 4, 32, NOW)  # 3+4 > 5 → deny, drain
    assert r.status == 1 and r.remaining == 0
    # RESET_REMAINING removes the item and reports a full bucket
    r = one("d", 0, 8, NOW)
    assert r.status == 0 and r.remaining == limit
    assert one("d", limit, 0, NOW).status == 0  # full again


# --------------------------------------------------------- sliding window


@pytest.mark.parametrize("wire", ["full", "compact"])
def test_sliding_window_boundary_parity(wire):
    """Window-boundary crossings: the interpolated carry-over from the
    previous window must match the oracle hit-for-hit, including the roll
    into an empty middle window and full staleness two windows later."""
    eng = LocalEngine(capacity=1 << 12, write_mode="xla", wire=wire)
    oracle = SlidingWindowOracle()
    limit, dur = 10, 10_000
    fp = fingerprint("alg", "w")
    # timestamps chosen to land before/on/after boundaries
    base = (NOW // dur) * dur
    times = [
        base + 100, base + 9_900, base + dur, base + dur + 2_500,
        base + dur + 9_999, base + 2 * dur + 1, base + 4 * dur + 7,
    ]
    hits = [4, 5, 3, 2, 6, 1, 2]
    for t, h in zip(times, hits):
        rc = eng.check_columns(
            _cols(["w"], Algorithm.SLIDING_WINDOW, [h], [limit], [dur], t),
            now_ms=t,
        )
        st, rem, reset = oracle.check(fp, t, h, limit, dur)
        assert (int(rc.status[0]), int(rc.remaining[0]), int(rc.reset_time[0])) == (
            st, rem, reset
        ), t


def test_sliding_window_randomized_parity_mesh(mesh):
    from gubernator_tpu.parallel.sharded import ShardedEngine

    rng = np.random.default_rng(17)
    eng = ShardedEngine(mesh, capacity_per_shard=1 << 12, route="device",
                        dedup="device")
    oracle = SlidingWindowOracle()
    limit, dur = 9, 4_000
    t = NOW
    for _ in range(60):
        t += int(rng.integers(0, 3_000))
        keys = [f"w{int(k)}" for k in rng.choice(16, size=3, replace=False)]
        hits = [int(rng.integers(0, 4)) for _ in keys]
        rc = eng.check_columns(
            _cols(keys, Algorithm.SLIDING_WINDOW, hits, [limit] * 3,
                  [dur] * 3, t),
            now_ms=t,
        )
        for j, k in enumerate(keys):
            st, rem, reset = oracle.check(fingerprint("alg", k), t, hits[j],
                                          limit, dur)
            assert (int(rc.status[j]), int(rc.remaining[j])) == (st, rem), (k, t)
            assert int(rc.reset_time[j]) == reset


def test_sliding_window_interpolation_denies_burst_across_boundary():
    """The point of interpolation: a full previous window keeps denying
    just past the boundary (a fixed window would admit a fresh burst)."""
    eng = LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")
    limit, dur = 10, 10_000
    base = (NOW // dur) * dur
    rc = eng.check_columns(
        _cols(["b"], Algorithm.SLIDING_WINDOW, [10], [limit], [dur],
              base + 9_000),
        now_ms=base + 9_000,
    )
    assert int(rc.status[0]) == 0
    # 1 ms into the next window: ~100% of the previous window still covered
    rc = eng.check_columns(
        _cols(["b"], Algorithm.SLIDING_WINDOW, [5], [limit], [dur],
              base + dur + 1),
        now_ms=base + dur + 1,
    )
    assert int(rc.status[0]) == 1
    # 90% through the next window the carry has decayed to ~1 → admits
    rc = eng.check_columns(
        _cols(["b"], Algorithm.SLIDING_WINDOW, [5], [limit], [dur],
              base + dur + 9_000),
        now_ms=base + dur + 9_000,
    )
    assert int(rc.status[0]) == 0


# ------------------------------------------------------- concurrency lease


@pytest.mark.parametrize("wire", ["full", "compact"])
def test_lease_acquire_release_expire(wire):
    eng = LocalEngine(capacity=1 << 12, write_mode="xla", wire=wire)
    oracle = LeaseOracle()
    limit, ttl = 10, 5_000
    fp = fingerprint("alg", "l")
    # note: releases (hits < 0) are not compact-encodable — the engine
    # falls those dispatches back to full-width transparently
    schedule = [
        (NOW, 8), (NOW + 10, 5), (NOW + 20, -6), (NOW + 30, 5),
        (NOW + 40, 0), (NOW + 100, -20), (NOW + 200, limit),
        # expiry reclamation: TTL passes → all leases reclaimed
        (NOW + 200 + ttl + 1, limit),
    ]
    for t, h in schedule:
        rc = eng.check_columns(
            _cols(["l"], Algorithm.CONCURRENCY_LEASE, [h], [limit], [ttl], t),
            now_ms=t,
        )
        st, rem, reset = oracle.check(fp, t, h, limit, ttl)
        assert (int(rc.status[0]), int(rc.remaining[0]), int(rc.reset_time[0])) == (
            st, rem, reset
        ), (t, h)


def test_lease_parity_mesh(mesh):
    from gubernator_tpu.parallel.sharded import ShardedEngine

    rng = np.random.default_rng(23)
    eng = ShardedEngine(mesh, capacity_per_shard=1 << 12, route="device",
                        dedup="device")
    oracle = LeaseOracle()
    limit, ttl = 6, 8_000
    t = NOW
    for _ in range(50):
        t += int(rng.integers(0, 2_000))
        keys = [f"l{int(k)}" for k in rng.choice(10, size=2, replace=False)]
        hits = [int(rng.integers(-3, 4)) for _ in keys]
        rc = eng.check_columns(
            _cols(keys, Algorithm.CONCURRENCY_LEASE, hits, [limit] * 2,
                  [ttl] * 2, t),
            now_ms=t,
        )
        for j, k in enumerate(keys):
            st, rem, reset = oracle.check(fingerprint("alg", k), t, hits[j],
                                          limit, ttl)
            assert (int(rc.status[j]), int(rc.remaining[j])) == (st, rem), (k, t, hits[j])


# ------------------------------------------------- merge/replay conservatism


def test_merge_replay_conservatism_gcra_and_window(frozen_now):
    """Checkpoint/handoff replay (kernel2.merge2) can only UNDER-grant for
    the new lanes: a stale GCRA TAT (smaller) must not roll admission back,
    a duplicated replay must be idempotent, and the same for sliding-window
    counts (REM_I remaining-style min + aux max)."""
    now = frozen_now
    src = LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")
    limit, dur = 10, 10_000
    # consume 4 → snapshot A (tat = now+4T); consume 4 more → snapshot B
    src.check_columns(_cols(["g"], Algorithm.GCRA, [4], [limit], [dur], now), now_ms=now)
    fps_a, slots_a = src.extract_live(now_ms=now)
    src.check_columns(_cols(["g"], Algorithm.GCRA, [4], [limit], [dur], now), now_ms=now)
    src.check_columns(
        _cols(["w"], Algorithm.SLIDING_WINDOW, [7], [limit], [dur], now), now_ms=now
    )
    fps_b, slots_b = src.extract_live(now_ms=now)

    dst = LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")
    # replay NEW then STALE then NEW again (duplicated + out-of-order)
    assert dst.merge_rows(fps_b, slots_b, now_ms=now) == len(fps_b)
    dst.merge_rows(fps_a, slots_a, now_ms=now)
    dst.merge_rows(fps_b, slots_b, now_ms=now)

    # the replayed engine must admit NO MORE than the source engine
    for key, algo in (("g", Algorithm.GCRA), ("w", Algorithm.SLIDING_WINDOW)):
        rc_src = src.check_columns(
            _cols([key], algo, [0], [limit], [dur], now), now_ms=now
        )
        rc_dst = dst.check_columns(
            _cols([key], algo, [0], [limit], [dur], now), now_ms=now
        )
        assert int(rc_dst.remaining[0]) <= int(rc_src.remaining[0]), key
        # and exactly equal here: the newest state won every merge
        assert int(rc_dst.remaining[0]) == int(rc_src.remaining[0]), key


# ---------------------------------------------------------------- cascades


def _cascade_cols(now, user_hits=1, user="u1", tenant="acme"):
    """3-level cascade: per-user token(5/min) + per-tenant window(8/min) +
    global GCRA(50/min) — the API-gateway shape from the ISSUE."""
    keys = [f"user:{user}", f"tenant:{tenant}", "global"]
    n = 3
    return RequestColumns(
        fp=np.array([fingerprint("casc", k) for k in keys], dtype=np.int64),
        algo=np.array([0, int(Algorithm.SLIDING_WINDOW), int(Algorithm.GCRA)],
                      dtype=np.int32),
        behavior=np.array([0, 1 << 8, 2 << 8], dtype=np.int32),
        hits=np.full(n, user_hits, dtype=np.int64),
        limit=np.array([5, 8, 50], dtype=np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, 60_000, dtype=np.int64),
        created_at=np.full(n, now, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


@pytest.mark.parametrize("wire", ["full", "compact"])
def test_cascade_deny_if_any_single_dispatch(wire, frozen_now):
    now = frozen_now
    eng = LocalEngine(capacity=1 << 12, write_mode="xla", wire=wire)
    d0 = eng.stats.dispatches
    rc = eng.check_columns(_cascade_cols(now, user_hits=4), now_ms=now)
    # ONE dispatch evaluated all three levels
    assert eng.stats.dispatches == d0 + 1
    assert int(rc.status[0]) == 0
    # carrier remaining = min across levels (user: 1 left)
    assert int(rc.remaining[0]) == 1
    # per-level rows keep their own responses
    assert int(rc.remaining[1]) == 4 and int(rc.remaining[2]) == 46
    rc = eng.check_columns(_cascade_cols(now, user_hits=4), now_ms=now)
    # user level denies → cascade verdict OVER; tenant level admitted (8)
    assert int(rc.status[0]) == 1
    assert int(rc.status[1]) == 0


def test_cascade_compact_wire_encodable(frozen_now):
    """An encodable 3-level cascade rides the compact wire — zero
    full-width fallbacks."""
    from gubernator_tpu.ops import wire as wire_mod
    from gubernator_tpu.ops.batch import pack_columns

    hb, err = pack_columns(_cascade_cols(NOW), NOW)
    assert not err.any()
    base = wire_mod.pick_base(hb)
    assert wire_mod.wire_encodable(hb, base)
    # roundtrip: host decode == original fields (incl. level bits)
    lanes = wire_mod.pack_wire_rows(hb, base)
    dec = wire_mod.decode_wire_host(lanes, base)
    np.testing.assert_array_equal(dec["fp"], hb.fp)
    np.testing.assert_array_equal(dec["algo"], hb.algo)
    np.testing.assert_array_equal(
        (dec["behavior"] >> 8) & 0xFF, [0, 1, 2]
    )
    np.testing.assert_array_equal(dec["limit"], hb.limit)
    # deeper than the 2-bit lane budget → full-width fallback
    deep = hb._replace(behavior=hb.behavior | np.int32(4 << 8))
    assert not wire_mod.wire_encodable(deep, base)


def test_cascade_fp_level_collision_regression(frozen_now, mesh):
    """The (fp, level) dedup discriminator: the SAME key at two levels of
    one cascade must evaluate BOTH limit configs (sequential semantics via
    the claim-conflict retry), not silently merge into one row whose
    newest config clobbers the other — on the host planner AND the
    in-trace device dedup."""
    from gubernator_tpu.parallel.sharded import ShardedEngine

    now = NOW
    key = fingerprint("casc", "clash")

    def batch():
        return RequestColumns(
            fp=np.array([key, key], dtype=np.int64),
            algo=np.zeros(2, dtype=np.int32),
            behavior=np.array([0, 1 << 8], dtype=np.int32),
            hits=np.array([1, 1], dtype=np.int64),
            limit=np.array([1000, 3], dtype=np.int64),
            burst=np.zeros(2, dtype=np.int64),
            duration=np.full(2, 60_000, dtype=np.int64),
            created_at=np.full(2, now, dtype=np.int64),
            err=np.zeros(2, dtype=np.int8),
        )

    for name, eng in (
        ("local", LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")),
        ("sharded", ShardedEngine(mesh, capacity_per_shard=1 << 11,
                                  route="device", dedup="device")),
    ):
        rc = eng.check_columns(batch(), now_ms=now)
        assert not rc.err.any(), (name, rc.err)
        # both configs were really applied: the level-1 row reports the
        # small limit's config, the carrier's own level the big one
        assert int(rc.limit[1]) == 3, name
        # two sequential applications of the same key happened (the second
        # sees the first's consumption under ITS config rules)
        assert int(rc.status[0]) == 1 or int(rc.remaining[1]) < 3, name


def test_same_level_cascade_rows_aggregate(frozen_now, mesh):
    """Opposite direction: the SAME (fp, level) across two DIFFERENT
    cascades still aggregates in-trace (50 users of one tenant cost one
    kernel row, hits summed) — the PR-3 machinery composes with levels."""
    from gubernator_tpu.parallel.sharded import ShardedEngine

    now = NOW
    eng = ShardedEngine(mesh, capacity_per_shard=1 << 11, route="device",
                        dedup="device")
    ten = fingerprint("casc", "tenant:shared")
    cols = RequestColumns(
        fp=np.array([fingerprint("casc", "user:a"), ten,
                     fingerprint("casc", "user:b"), ten], dtype=np.int64),
        algo=np.zeros(4, dtype=np.int32),
        behavior=np.array([0, 1 << 8, 0, 1 << 8], dtype=np.int32),
        hits=np.array([1, 1, 1, 1], dtype=np.int64),
        limit=np.array([10, 6, 10, 6], dtype=np.int64),
        burst=np.zeros(4, dtype=np.int64),
        duration=np.full(4, 60_000, dtype=np.int64),
        created_at=np.full(4, now, dtype=np.int64),
        err=np.zeros(4, dtype=np.int8),
    )
    rc = eng.check_columns(cols, now_ms=now)
    # both tenant rows see the aggregate (6 - 2 = 4 remaining)
    assert int(rc.remaining[1]) == 4 and int(rc.remaining[3]) == 4
    assert int(rc.remaining[0]) == 4  # carrier folded min(9, tenant 4)


def test_cascade_multi_pass_and_retry_refold(frozen_now):
    """Duplicate fps force a multi-pass plan (no in-trace fold); the host
    fold must still produce the combined verdict."""
    now = NOW
    eng = LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")
    u = fingerprint("casc", "mp-user")
    t = fingerprint("casc", "mp-tenant")
    # two cascades sharing the tenant at level 1 + a plain duplicate of the
    # user key → host planner splits passes
    cols = RequestColumns(
        fp=np.array([u, t, u, t], dtype=np.int64),
        algo=np.zeros(4, dtype=np.int32),
        behavior=np.array([0, 1 << 8, 0, 1 << 8], dtype=np.int32),
        hits=np.array([1, 1, 1, 1], dtype=np.int64),
        limit=np.array([10, 2, 10, 2], dtype=np.int64),
        burst=np.zeros(4, dtype=np.int64),
        duration=np.full(4, 60_000, dtype=np.int64),
        created_at=np.full(4, now, dtype=np.int64),
        err=np.zeros(4, dtype=np.int8),
    )
    rc = eng.check_columns(cols, now_ms=now)
    rc = eng.check_columns(cols, now_ms=now)
    # tenant (limit 2) exhausted after 2-3 hits → second round denies, and
    # the fold propagates OVER to both carriers
    assert int(rc.status[1]) == 1 or int(rc.status[3]) == 1
    assert int(rc.status[0]) == 1 and int(rc.status[2]) == 1


def test_cascade_pipelined_mesh_fold(frozen_now, mesh):
    """The PIPELINED mesh path (prepare/issue/finish split the daemon's
    runner drives) must fold cascade verdicts host-side: single_pass plans
    look 'single pass' but the routed per-shard programs cannot fold
    in-trace — regression for the capability gate."""
    from gubernator_tpu.ops.engine import (
        finish_check_columns,
        issue_check_columns,
        prepare_check_columns,
    )
    from gubernator_tpu.parallel.sharded import ShardedEngine

    now = NOW
    eng = ShardedEngine(mesh, capacity_per_shard=1 << 11, route="device",
                        dedup="device")
    cols = _cascade_cols(now, user_hits=4)
    for _ in range(2):  # second check drives the user level (5) over
        pending = prepare_check_columns(eng, cols, now_ms=now)
        pending = issue_check_columns(eng, pending)
        rc, _delta = finish_check_columns(
            eng, pending, lambda fn: fn()
        )
    assert int(rc.status[0]) == 1  # folded deny-if-any on the carrier
    assert int(rc.status[1]) == 0  # tenant level itself still under


# ----------------------------------------------------- forward compatibility


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper



@async_test
async def test_mixed_version_cluster_unknown_algorithm():
    """Mixed-version two-daemon cluster stub: a 'newer' client/peer sends
    an algorithm enum this build doesn't speak. The receiving daemon — and
    the OWNER it forwards to — answer that ITEM with the reference-worded
    error row; the rest of the batch succeeds, and V1Client surfaces it
    per item."""
    from tests.cluster import Cluster

    from gubernator_tpu.client import V1Client
    from gubernator_tpu.proto import gubernator_pb2 as pb

    cluster = await Cluster.start(2)
    try:
        d0 = cluster.daemons[0]
        # find a key OWNED BY THE OTHER daemon so the request is forwarded
        # (the unknown enum crosses the peer wire, like a newer peer would)
        fwd_key = None
        for i in range(100):
            k = f"fwd{i}"
            if not d0.is_self(d0.get_peer("mv_" + k)):
                fwd_key = k
                break
        assert fwd_key is not None
        c = V1Client(d0.conf.grpc_address)
        reqs = [
            pb.RateLimitReq(name="mv", unique_key=fwd_key, hits=1, limit=5,
                            duration=60_000, algorithm=7),
            pb.RateLimitReq(name="mv", unique_key="ok", hits=1, limit=5,
                            duration=60_000),
        ]
        resp = await c.get_rate_limits(reqs)
        assert resp.responses[0].error == "invalid rate limit algorithm"
        assert resp.responses[1].error == ""
        assert resp.responses[1].remaining == 4
        await c.close()
    finally:
        await cluster.stop()


@async_test
async def test_cascade_routes_to_level0_owner_and_returns_levels():
    """Two-daemon cluster: a cascade whose LEVEL-0 key is owned by the
    remote daemon forwards whole — the owner expands/evaluates all levels
    in its one dispatch and the per-level responses ride back over the
    peer wire."""
    from tests.cluster import Cluster

    from gubernator_tpu.client import V1Client
    from gubernator_tpu.proto import gubernator_pb2 as pb

    cluster = await Cluster.start(2)
    try:
        d0 = cluster.daemons[0]
        fwd_key = None
        for i in range(100):
            k = f"cu{i}"
            if not d0.is_self(d0.get_peer("cm_" + k)):
                fwd_key = k
                break
        assert fwd_key is not None
        c = V1Client(d0.conf.grpc_address)
        r = pb.RateLimitReq(name="cm", unique_key=fwd_key, hits=3, limit=5,
                            duration=60_000)
        r.cascade.add(name="cm_tenant", unique_key="acme", limit=4,
                      duration=60_000)
        resp = await c.get_rate_limits([r])
        top = resp.responses[0]
        assert len(top.cascade) == 1
        assert top.status == 0 and top.remaining == 1  # min(2, 1)
        resp = await c.get_rate_limits([r])
        top = resp.responses[0]
        assert top.status == 1  # tenant level (4) denies 3+3
        assert top.cascade[0].status == 1
        await c.close()
    finally:
        await cluster.stop()


@async_test
async def test_cascade_too_deep_is_per_item_error():
    from tests.cluster import Cluster

    from gubernator_tpu.client import V1Client
    from gubernator_tpu.proto import gubernator_pb2 as pb

    cluster = await Cluster.start(1, cascade_max_levels=3)
    try:
        d = cluster.daemons[0]
        c = V1Client(d.conf.grpc_address)
        r = pb.RateLimitReq(name="deep", unique_key="k", hits=1, limit=5,
                            duration=60_000)
        for i in range(3):  # 1 + 3 levels > 3
            r.cascade.add(name=f"lvl{i}", unique_key="x", limit=5,
                          duration=60_000)
        ok = pb.RateLimitReq(name="deep", unique_key="fine", hits=1, limit=5,
                             duration=60_000)
        resp = await c.get_rate_limits([r, ok])
        assert resp.responses[0].error == (
            "Cascade levels list too large; max size is '3'"
        )
        assert resp.responses[1].error == "" and resp.responses[1].remaining == 4
        await c.close()
    finally:
        await cluster.stop()
