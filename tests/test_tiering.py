"""Hot-set tiering oracle suite (gubernator_tpu/tier/, docs/tiering.md).

Pins the ISSUE 15 acceptance surface: the evictee sidecar (XLA and Pallas
kernels, both wire formats), demote/promote roundtrip BIT-exactness per
slot layout through the canonical-row conversion point, under-grant-only
under duplicated/stale promotes, Zipf churn against a bounded shadow with
zero over-grant, the shadow byte bound + LRU shed accounting, spill-file
fault-back, 8-device mesh demote/fault-back parity, and the checkpoint
interplay (demote → kill -9 → restart → fault-back from shadow, not
resurrection from a stale delta frame).
"""

from __future__ import annotations

import asyncio
import os

import numpy as np
import jax.numpy as jnp
import pytest

from gubernator_tpu.ops.batch import RequestColumns
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.ops.kernel2 import decide2_packed_cols, unpack_evictees
from gubernator_tpu.ops.table2 import Table2, extract_idle_rows, new_table2
from gubernator_tpu.tier import ROW_BYTES, ShadowTable

NOW = 1_700_000_000_000
HOUR = 3_600_000


def cols(fp, now, hits=1, limit=10, algo=0, duration=HOUR, burst=0):
    n = fp.shape[0]
    mk = lambda v, dt: np.full(n, v, dtype=dt)
    return RequestColumns(
        fp=np.asarray(fp, dtype=np.int64),
        algo=mk(algo, np.int32),
        behavior=np.zeros(n, dtype=np.int32),
        hits=mk(hits, np.int64),
        limit=mk(limit, np.int64),
        burst=mk(burst, np.int64),
        duration=mk(duration, np.int64),
        created_at=mk(now, np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


def arr12(fp, now, hits=1, limit=10):
    n = fp.shape[0]
    z = np.zeros(n, dtype=np.int64)
    mk = lambda v: np.full(n, v, dtype=np.int64)
    return jnp.asarray(np.stack([
        np.asarray(fp, dtype=np.int64), z, z, mk(hits), mk(limit), z,
        mk(HOUR), mk(now), mk(now + HOUR), z, mk(HOUR),
        (np.asarray(fp) != 0).astype(np.int64),
    ]))


def shadowed_engine(capacity=256, max_bytes=1 << 22, spill=None, **kw):
    eng = LocalEngine(capacity=capacity, write_mode="xla", **kw)
    eng.attach_shadow(ShadowTable(max_bytes=max_bytes, spill_path=spill))
    return eng


# ------------------------------------------------------------ sidecar


def test_evictee_sidecar_captures_victim_rows():
    """A full bucket's displaced live rows ride the dispatch outputs:
    fingerprints and full pre-dispatch state, count == the kernel's
    evicted_unexpired stat."""
    t = new_table2(8)  # ONE bucket of 8 slots
    seed = np.arange(1, 9, dtype=np.int64)
    t, _ = decide2_packed_cols(
        t, arr12(seed, NOW, hits=3), write="xla", math="token"
    )
    newk = np.arange(101, 105, dtype=np.int64)
    pad = np.zeros(16, dtype=np.int64)
    pad[:4] = newk
    hits = np.zeros(16, dtype=np.int64)
    hits[:4] = 1
    t, out = decide2_packed_cols(
        t, arr12(pad, NOW + 5, hits=1).at[3].set(jnp.asarray(hits)),
        write="xla", math="token", evictees=True,
    )
    host = np.asarray(out)
    from gubernator_tpu.ops.kernel2 import unpack_outputs

    _, st = unpack_outputs(host, 4)
    fps, rows = unpack_evictees(host)
    assert st[3] == fps.shape[0] == 4
    assert set(fps.tolist()) <= set(seed.tolist())
    # victim state is the PRE-dispatch row: limit 10, 3 consumed
    assert (rows[:, 2] == 10).all() and (rows[:, 4] == 7).all()


def test_evictee_sidecar_absent_without_flag():
    """evictees=False keeps the historic (B+2, 4) output shape — the
    zero-cost contract for tiering-off deployments."""
    t = new_table2(8)
    t, out = decide2_packed_cols(
        t, arr12(np.arange(1, 17, dtype=np.int64), NOW), write="xla",
        math="token",
    )
    assert np.asarray(out).shape == (18, 4)


def test_evictee_sidecar_parity_xla_vs_sweep():
    """Under the Pallas sweep write the sidecar is bit-identical to the
    XLA scatter's — outputs AND table bytes."""
    rng = np.random.default_rng(11)
    t0 = new_table2(64)
    seed = rng.integers(1, 1 << 60, size=64, dtype=np.int64)
    t0, _ = decide2_packed_cols(
        t0, arr12(seed, NOW), write="xla", math="token"
    )
    rows_np = np.asarray(t0.rows)
    batch = arr12(rng.integers(1, 1 << 60, size=32, dtype=np.int64), NOW + 5)
    tx = Table2(rows=jnp.asarray(rows_np.copy()))
    tp = Table2(rows=jnp.asarray(rows_np.copy()))
    tx, ox = decide2_packed_cols(
        tx, batch, write="xla", math="token", evictees=True
    )
    tp, op = decide2_packed_cols(
        tp, batch, write="sweep", math="token", evictees=True
    )
    assert np.array_equal(np.asarray(ox), np.asarray(op))
    assert np.array_equal(np.asarray(tx.rows), np.asarray(tp.rows))
    fx, rx = unpack_evictees(np.asarray(ox))
    assert fx.shape[0] > 0  # the scenario actually evicts


def test_evictee_sidecar_rides_compact_wire():
    """The engine's compact-wire dispatches carry the sidecar too: an
    evicting dispatch through a wire='compact' engine lands the victim
    rows in the shadow."""
    eng = shadowed_engine(capacity=8, wire="compact")
    seed = np.arange(1, 9, dtype=np.int64)
    eng.check_columns(cols(seed, NOW, hits=3), now_ms=NOW)
    eng.check_columns(
        cols(np.arange(101, 109, dtype=np.int64), NOW + 5), now_ms=NOW + 5
    )
    st = eng.shadow.stats()
    assert st["demoted_evict"] > 0
    assert eng.stats.evicted_unexpired >= st["demoted_evict"] > 0


# --------------------------------------------- roundtrip exactness


@pytest.mark.parametrize("layout,algo", [
    ("full", 0), ("gcra32", 2), ("token32", 0),
])
def test_demote_promote_roundtrip_bit_exact(layout, algo):
    """An unexpired row demoted (idle sweep) and faulted back re-packs to
    the SAME table bytes in every registered slot layout — the
    canonical-row conversion point is lossless for rows the layout can
    hold."""
    eng = LocalEngine(capacity=64, write_mode="xla", layout=layout)
    fp = np.array([12345], dtype=np.int64)
    eng.check_columns(cols(fp, NOW, hits=3, algo=algo), now_ms=NOW)
    found, before = eng.read_state(fp, raw=True)
    assert found[0]
    # demote: extract idle + tombstone (idle horizon 0 → everything idle)
    fps, slots = eng.extract_idle(NOW + 1000, 1)
    assert fp[0] in fps.tolist()
    eng.tombstone_fps(fps)
    found, _ = eng.read_state(fp)
    assert not found[0]
    full = np.asarray(eng.table.layout.unpack(slots))
    sh = ShadowTable(max_bytes=1 << 20)
    sh.offer(fps, full, NOW + 1000, reason="idle")
    # promote through the conservative merge
    pf, rows = sh.take(fp, NOW + 1000)
    assert pf.shape[0] == 1
    from gubernator_tpu.ops.layout import FULL

    eng.merge_rows(pf, rows, now_ms=NOW + 1000, layout=FULL)
    found, after = eng.read_state(fp, raw=True)
    assert found[0]
    i = list(fps).index(fp[0])
    np.testing.assert_array_equal(after[0], before[0])
    # and the shadow row itself equals the canonical unpack of the bytes
    np.testing.assert_array_equal(
        rows[0], np.asarray(eng.table.layout.unpack(before))[0]
    )


def test_stale_duplicate_promote_under_grants_only():
    """A stale or duplicated promote can only tighten: re-offering an OLD
    copy of a row and promoting it over newer state never raises
    remaining above the newer state's."""
    eng = shadowed_engine(capacity=64)
    fp = np.array([777], dtype=np.int64)
    eng.check_columns(cols(fp, NOW, hits=2), now_ms=NOW)  # rem 8
    _, old_row = eng.read_state(fp)  # canonical full row, rem 8
    eng.check_columns(cols(fp, NOW + 10, hits=5), now_ms=NOW + 10)  # rem 3
    # stale re-offer + forced promote
    eng.shadow.offer(fp, old_row, NOW + 20)
    rc = eng.check_columns(cols(fp, NOW + 30, hits=0), now_ms=NOW + 30)
    assert rc.remaining[0] <= 3  # min-merge: stale promote can't re-grant
    # duplicated promote of the same bytes is idempotent
    eng.shadow.offer(fp, old_row, NOW + 40)
    rc = eng.check_columns(cols(fp, NOW + 50, hits=0), now_ms=NOW + 50)
    assert rc.remaining[0] <= 3


# ------------------------------------------------- zero over-grant


def _drive(eng, keys, passes=4, wave=128, hits=3, limit=10):
    adm = {int(k): 0 for k in keys}
    t = NOW
    for _ in range(passes):
        for i in range(0, len(keys), wave):
            w = keys[i:i + wave]
            rc = eng.check_columns(
                cols(w, t, hits=hits, limit=limit), now_ms=t
            )
            ok = (rc.status == 0) & (rc.err == 0)
            for j in np.nonzero(ok)[0]:
                adm[int(w[j])] += hits
            t += 7
    return adm


def test_tiering_zero_over_grant_at_4x_tracked_keys():
    """The acceptance core: 4× tracked keys beyond table capacity, every
    key's total admissions ≤ its limit — eviction became a tiering event
    instead of a permissive re-grant. The identical drive WITHOUT tiering
    over-grants (the bug being fixed)."""
    rng = np.random.default_rng(3)
    CAP, TRACKED, LIMIT = 256, 1024, 10
    keys = np.unique(
        rng.integers(1, 1 << 62, size=TRACKED + 64, dtype=np.int64)
    )[:TRACKED]
    eng = shadowed_engine(capacity=CAP)
    adm = _drive(eng, keys, limit=LIMIT)
    over = [k for k, v in adm.items() if v > LIMIT]
    assert not over, f"{len(over)} keys over-granted with tiering on"
    assert eng.shadow.stats()["demoted_evict"] > 0  # tiering actually ran

    ctrl = LocalEngine(capacity=CAP, write_mode="xla")
    adm2 = _drive(ctrl, keys, limit=LIMIT)
    assert any(v > LIMIT for v in adm2.values()), (
        "control run did not over-grant; the scenario no longer "
        "exercises eviction"
    )


def test_zipf_churn_bounded_shadow_no_over_grant():
    """Zipf-shaped churn over 4× tracked keys against a shadow big enough
    to hold the cold set: hot keys stay exact, the byte bound holds."""
    rng = np.random.default_rng(17)
    CAP, TRACKED, LIMIT = 256, 1024, 50
    keys = np.unique(
        rng.integers(1, 1 << 62, size=TRACKED + 64, dtype=np.int64)
    )[:TRACKED]
    eng = shadowed_engine(capacity=CAP, max_bytes=TRACKED * ROW_BYTES)
    adm = {int(k): 0 for k in keys}
    # zipf ranks: heavy head, long tail
    zipf = np.minimum(rng.zipf(1.3, size=24 * 128) - 1, TRACKED - 1)
    t = NOW
    for i in range(24):
        w = keys[zipf[i * 128:(i + 1) * 128]]
        w = np.unique(w)  # unique-fp per batch (the serving contract)
        rc = eng.check_columns(cols(w, t, hits=1, limit=LIMIT), now_ms=t)
        ok = (rc.status == 0) & (rc.err == 0)
        for j in np.nonzero(ok)[0]:
            adm[int(w[j])] += 1
        t += 11
    assert all(v <= LIMIT for v in adm.values())
    st = eng.shadow.stats()
    assert st["nominal_bytes"] <= TRACKED * ROW_BYTES


# --------------------------------------------------- byte bound / spill


def test_shadow_byte_bound_and_lru_shed():
    sh = ShadowTable(max_bytes=4 * ROW_BYTES)
    fps = np.arange(1, 11, dtype=np.int64)
    rows = np.zeros((10, 16), dtype=np.int32)
    rows[:, 0] = fps.astype(np.int32)
    rows[:, 10] = 1  # exp_lo > 0 → live vs now=0
    sh.offer(fps, rows, 0)
    assert sh.nominal_bytes <= sh.max_bytes
    assert sh.ram_rows == 4
    assert sh.shed == 6  # oldest-first, counted
    # the 4 newest survive
    f, _ = sh.take(fps, 0)
    assert set(f.tolist()) == {7, 8, 9, 10}


def test_shadow_spill_overflow_and_faultback(tmp_path):
    """Over-budget rows shed to the spill file losslessly and fault back
    with one seek+read; a fresh ShadowTable re-indexes the file."""
    path = str(tmp_path / "spill")
    sh = ShadowTable(max_bytes=4 * ROW_BYTES, spill_path=path)
    fps = np.arange(1, 11, dtype=np.int64)
    rows = np.zeros((10, 16), dtype=np.int32)
    rows[:, 0] = fps.astype(np.int32)
    rows[:, 4] = fps.astype(np.int32)  # distinguishable payload
    rows[:, 10] = 1
    sh.offer(fps, rows, 0)
    assert sh.shed == 0
    f, r = sh.take(np.array([2], dtype=np.int64), 0)  # spilled row
    assert list(f) == [2] and r[0, 4] == 2
    sh.flush(0)
    sh2 = ShadowTable(max_bytes=1 << 20, spill_path=path)
    assert sh2.load() > 0
    f, r = sh2.take(np.array([9], dtype=np.int64), 0)
    assert list(f) == [9] and r[0, 4] == 9


def test_shadow_conflict_merges_conservatively():
    """Two demotes of one fingerprint keep the tighter remaining and the
    later expiry (merge2's rules, host-side)."""
    sh = ShadowTable(max_bytes=1 << 20)
    fp = np.array([5], dtype=np.int64)
    a = np.zeros((1, 16), dtype=np.int32)
    a[0, 0] = 5
    a[0, 4] = 8
    a[0, 10] = 100
    b = a.copy()
    b[0, 4] = 3
    b[0, 10] = 200
    sh.offer(fp, a, 0)
    sh.offer(fp, b, 0)
    assert sh.conflicts_merged == 1
    f, r = sh.take(fp, 0)
    assert r[0, 4] == 3 and r[0, 10] == 200


# ------------------------------------------------------ idle sweep


def test_extract_idle_respects_horizon_and_cap():
    eng = LocalEngine(capacity=256, write_mode="xla")
    old = np.arange(1, 33, dtype=np.int64)
    new = np.arange(101, 133, dtype=np.int64)
    eng.check_columns(cols(old, NOW), now_ms=NOW)
    eng.check_columns(cols(new, NOW + 50_000), now_ms=NOW + 50_000)
    fps, _ = eng.extract_idle(NOW + 60_000, 30_000)
    assert set(fps.tolist()) == set(old.tolist())
    capped, _ = eng.extract_idle(NOW + 60_000, 30_000, max_rows=5)
    assert capped.shape[0] == 5


def test_idle_demote_then_faultback_preserves_state():
    """The full demote-on-idle → fault-back loop at the engine level:
    state leaves HBM, the next check for the key resumes EXACTLY where it
    left off."""
    eng = shadowed_engine(capacity=256)
    fp = np.array([4242], dtype=np.int64)
    eng.check_columns(cols(fp, NOW, hits=6), now_ms=NOW)  # rem 4
    fps, slots = eng.extract_idle(NOW + 60_000, 30_000)
    eng.tombstone_fps(fps)
    full = np.asarray(eng.table.layout.unpack(slots))
    eng.shadow.offer(fps, full, NOW + 60_000, reason="idle")
    found, _ = eng.read_state(fp)
    assert not found[0]
    rc = eng.check_columns(
        cols(fp, NOW + 61_000, hits=1), now_ms=NOW + 61_000
    )
    assert rc.remaining[0] == 3  # 10 - 6 - 1: no re-grant
    assert eng.shadow.stats()["promoted"] == fps.shape[0]


# ------------------------------------------------------ pipelined path


def test_pipelined_check_promotes_and_demotes():
    """The prepare/issue/finish pipeline (EngineRunner.check) probes the
    shadow at prepare, merges at issue, and harvests the sidecar at
    finish — same zero-re-grant outcome as the serial path."""
    from gubernator_tpu.service.runner import EngineRunner

    rng = np.random.default_rng(23)
    keys = np.unique(rng.integers(1, 1 << 62, size=1100,
                                  dtype=np.int64))[:1024]
    eng = shadowed_engine(capacity=256)
    runner = EngineRunner(eng)

    async def drive():
        t = NOW
        # pass 1: every key consumes 6 of 10 (4x tracked keys → demotes)
        for i in range(0, 1024, 128):
            await runner.check(cols(keys[i:i + 128], t, hits=6), now_ms=t)
            t += 7
        # pass 2: +6 must deny for EVERY key (no fresh re-grant)
        denied = 0
        for i in range(0, 1024, 128):
            rc = await runner.check(
                cols(keys[i:i + 128], t, hits=6), now_ms=t
            )
            denied += int(((rc.status == 1) & (rc.err == 0)).sum())
            t += 7
        return denied

    try:
        denied = asyncio.run(drive())
        assert denied == 1024, f"only {denied}/1024 denied"
        assert eng.shadow.stats()["demoted_evict"] > 0
    finally:
        runner.close()


# ---------------------------------------------------------- 8-dev mesh


def test_sharded_idle_demote_faultback_8dev():
    """ShardedEngine tiering surface: per-shard extract-idle, tombstone,
    shadow fault-back through the routed merge — state preserved exactly
    across the demote/promote cycle on the 8-device mesh."""
    from gubernator_tpu.parallel import ShardedEngine, make_mesh

    mesh = make_mesh(8)
    eng = ShardedEngine(mesh, capacity_per_shard=64, write_mode="xla")
    eng.attach_shadow = lambda s: setattr(eng, "shadow", s)  # plain attr
    eng.shadow = ShadowTable(max_bytes=1 << 20)
    keys = np.unique(
        np.random.default_rng(5).integers(1, 1 << 60, size=64,
                                          dtype=np.int64)
    )
    eng.check_columns(cols(keys, NOW, hits=4), now_ms=NOW)
    fps, slots = eng.extract_idle(NOW + 60_000, 30_000)
    assert set(fps.tolist()) == set(keys.tolist())
    eng.tombstone_fps(fps)
    full = np.asarray(eng.table.layout.unpack(slots))
    eng.shadow.offer(fps, full, NOW + 60_000, reason="idle")
    found, _ = eng.read_state(keys)
    assert not found.any()
    rc = eng.check_columns(
        cols(keys, NOW + 61_000, hits=1), now_ms=NOW + 61_000
    )
    assert (np.asarray(rc.remaining) == 5).all()  # 10 - 4 - 1, preserved
    # collect=True surface: promote evictions come back typed
    n, mask, ev_f, ev_r = eng.merge_rows(
        fps[:4], full[:4], now_ms=NOW + 62_000, collect=True
    )
    assert mask.shape == (4,) and ev_r.shape[1] == 16


# ---------------------------------------------------- durability interplay


def test_tombstone_frame_roundtrip(tmp_path):
    from gubernator_tpu.store import (
        TOMBSTONE,
        DeltaLog,
        fps_from_slots,
    )

    log = DeltaLog(str(tmp_path / "d.delta"))
    rows = np.zeros((2, 16), dtype=np.int32)
    rows[:, 0] = [1, 2]
    log.append(4, 1000, rows)
    log.append_tombstones(5, 2000, np.array([2, (1 << 40) + 7],
                                            dtype=np.int64))
    scan = log.scan()
    assert scan.error is None
    assert scan.frames[1][3] is TOMBSTONE
    assert fps_from_slots(scan.frames[1][2]).tolist() == [2, (1 << 40) + 7]


@pytest.mark.slow
def test_demote_kill9_restart_faults_back_from_shadow(tmp_path):
    """The regression the ISSUE names: demote → kill -9 → restart — the
    key must NOT resurrect from a stale delta frame (the tombstone frame
    wins) and must fault back from the shadow spill with its consumption
    intact."""
    from gubernator_tpu.hashing import fingerprint
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from tests.cluster import Cluster

    async def run():
        c = await Cluster.start(
            1, cache_size=256,
            checkpoint_path=str(tmp_path / "ckpt.bin"),
            checkpoint_interval_ms=40.0,
            tier_enabled=True,
            tier_idle_ms=100.0,
            tier_shadow_bytes=1 << 22,
            tier_spill_path=str(tmp_path / "spill"),
            # long cadence → only the EXPLICIT sweep below runs, so the
            # tombstone frame is durably appended before the kill
            telemetry_interval_ms=60_000.0,
        )
        d = c.daemons[0]
        fp = fingerprint("t", "k")
        try:
            r = (await d.get_rate_limits([pb.RateLimitReq(
                name="t", unique_key="k", hits=7, limit=10,
                duration=600_000,
            )]))[0]
            assert r.status == pb.UNDER_LIMIT and r.remaining == 3
            # one checkpoint epoch captures the write (the stale frame a
            # resurrect would replay), then the row idles past 100 ms
            await asyncio.sleep(0.3)
            await d.tier.sweep_once()
            assert d.tier.shadow.stats()["demoted_idle"] >= 1
            found, _ = d.engine.read_state(np.array([fp], dtype=np.int64))
            assert not found[0]
            d2 = await c.crash_restart(0)
            found, _ = d2.engine.read_state(np.array([fp], dtype=np.int64))
            assert not found[0], "resurrected from a stale delta frame"
            r = (await d2.get_rate_limits([pb.RateLimitReq(
                name="t", unique_key="k", hits=1, limit=10,
                duration=600_000,
            )]))[0]
            assert r.remaining == 2, (
                f"fault-back lost state: remaining {r.remaining}, "
                "expected 2 (7 consumed pre-crash + 1)"
            )
        finally:
            await c.stop()

    asyncio.run(run())


# ---------------------------------------------------------- config/debug


def test_tier_config_validation():
    from gubernator_tpu.config import ConfigError, setup_daemon_config

    with pytest.raises(ConfigError):
        setup_daemon_config(env={"GUBER_TIER_IDLE_MS": "0"})
    with pytest.raises(ConfigError):
        setup_daemon_config(env={"GUBER_TIER_SHADOW_BYTES": "8"})
    with pytest.raises(ConfigError):
        setup_daemon_config(env={
            "GUBER_TIER_ENABLED": "true",
            "GUBER_TIER_SPILL_PATH": "/nonexistent-dir-xyz/spill",
        })
    conf = setup_daemon_config(env={
        "GUBER_TIER_ENABLED": "true",
        "GUBER_TIER_IDLE_MS": "30s",
        "GUBER_TIER_SHADOW_BYTES": str(1 << 20),
    })
    assert conf.tier_enabled and conf.tier_idle_ms == 30_000.0


def test_debug_tier_and_metrics(tmp_path):
    """Daemon wiring: /v1/debug/tier schema, the evicted_live_total and
    demoted_live_total fields on /v1/debug/table (a demotion is no state
    lost: ported in PR 42, when `evicted_live_total` became the state-loss
    count its docstring says it is), and the gubernator_tpu_demoted_live_total
    + gubernator_tier_* families on /metrics."""
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.service.metrics import parse_metrics
    from tests.cluster import Cluster

    async def run():
        c = await Cluster.start(
            1, cache_size=64,  # small: force evictions across waves
            tier_enabled=True,
            tier_idle_ms=60_000.0,
            tier_shadow_bytes=1 << 20,
            telemetry_interval_ms=60_000.0,
        )
        d = c.daemons[0]
        try:
            for w in range(8):
                reqs = [
                    pb.RateLimitReq(name="t", unique_key=f"k{w}.{i}",
                                    hits=2, limit=10, duration=600_000)
                    for i in range(32)
                ]
                for r in (await d.get_rate_limits(reqs)):
                    assert not r.error
            dbg = d.debug_tier()
            assert dbg["enabled"] and dbg["shadow"]["demoted_evict"] > 0
            tbl = await d.debug_table()
            assert tbl["evicted_live_total"] == 0
            assert tbl["demoted_live_total"] > 0
            assert "tiering" in tbl
            d.tier.observe()
            d.metrics.observe_engine(d.engine.stats)
            fams = parse_metrics(d.metrics.render().decode())
            assert fams["gubernator_tpu_evicted_live_total"][()] == 0
            assert fams["gubernator_tpu_demoted_live_total"][()] > 0
            demo = fams["gubernator_tier_demoted_rows_total"]
            assert demo[(("reason", "evict"),)] > 0
            assert "gubernator_tier_shadow_rows" in fams
        finally:
            await c.stop()

    asyncio.run(run())


# ------------------------------------------------ the shadow's index


@pytest.mark.parametrize("seed", [0, 1])
def test_fp_index_native_call_is_its_numpy_twin(seed, monkeypatch):
    """`_FpIndex` probes and fills through one GIL-free native call
    (`fp_index_find`, `fp_index_place`) where the module is loaded, and
    through array passes where it is not: the same table, the same answers,
    through fills, removals, refills over removed marks and a growth."""
    from gubernator_tpu import native
    from gubernator_tpu.tier import shadow as shadow_mod

    if native.load() is None:
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(seed)
    fps = np.unique(rng.integers(1, 1 << 62, size=60_000, dtype=np.int64))
    ids = np.arange(fps.shape[0], dtype=np.int32)
    tables = []
    for loaded in (True, False):
        if not loaded:
            monkeypatch.setattr(shadow_mod.native, "load", lambda: None)
        ix = shadow_mod._FpIndex(bits=4)
        ix.insert(fps[:20_000], ids[:20_000])
        slots = ix.find(fps[:30_000])
        assert (slots[:20_000] >= 0).all() and (slots[20_000:] < 0).all()
        assert np.array_equal(ix.vals[slots[:20_000]], ids[:20_000])
        ix.remove(slots[:5_000])
        assert (ix.find(fps[:5_000]) < 0).all()
        ix.insert(fps[20_000:], ids[20_000:])  # reuses marks, then grows
        assert np.array_equal(ix.vals[ix.find(fps[5_000:])], ids[5_000:])
        tables.append((ix.bits, ix.used, ix.gone, ix.keys.copy(), ix.vals.copy()))
    (b0, u0, g0, k0, v0), (b1, u1, g1, k1, v1) = tables
    assert (b0, u0, g0) == (b1, u1, g1) == (b0, 55_000 + fps.shape[0] - 60_000, g0)
    # the twin places a batch in another order, so slots may differ: what a
    # key maps to may not
    held = k0 > 0
    assert np.array_equal(np.sort(k0[held]), np.sort(k1[k1 > 0]))
