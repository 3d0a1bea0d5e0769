"""Durability at scale: incremental device-side checkpointing, crash-safe
warm restart, and the kill -9 recovery bound (docs/durability.md).

Layers under test:

* ops/checkpoint.py — the epoch tracker's dirty-block bookkeeping and the
  device-side dirty-block extract (local + 8-device mesh, parity vs the
  numpy live-slot oracle);
* store.py — CRC-framed delta frames: roundtrip, corrupt-frame and
  torn-tail skip (the clean prefix always replays);
* kernel2.merge2 replay — base + deltas reconstruct the pre-crash state
  byte-for-byte for clean frames, and a STALE frame can only tighten
  admission (never over-grant — the invariant the whole design leans on);
* service/checkpoint.py + daemon — background loop, debug/metrics surface,
  geometry-mismatch/corrupt-snapshot cold starts, shutdown that survives a
  failing Loader, and the chaos recovery bound: a kill -9'd daemon
  (Cluster.crash_restart → Daemon.abort) recovers within one checkpoint
  interval's writes of its pre-crash state.
"""

import asyncio
import functools
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from gubernator_tpu.config import ConfigError, setup_daemon_config
from gubernator_tpu.ops.batch import RequestColumns
from gubernator_tpu.ops.checkpoint import (
    EpochTracker,
    extract_begin,
    finish_extract,
)
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.ops.table2 import decode_live_slots
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.store import (
    DeltaLog,
    fps_from_slots,
    load_snapshot_meta,
    save_snapshot,
)
from tests.cluster import daemon_config

NOW = 1_700_000_000_000


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def cols(fps, hits=1, limit=1 << 20, behavior=None):
    n = fps.shape[0]
    return RequestColumns(
        fp=fps,
        algo=np.zeros(n, dtype=np.int32),
        behavior=(
            behavior if behavior is not None else np.zeros(n, dtype=np.int32)
        ),
        hits=np.full(n, hits, dtype=np.int64),
        limit=np.full(n, limit, dtype=np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, 3_600_000, dtype=np.int64),
        created_at=np.full(n, NOW, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


def install(eng, fps, remaining=37, limit=100):
    n = fps.shape[0]
    o = np.ones(n, dtype=np.int64)
    return eng.install_columns(
        fp=fps,
        algo=np.zeros(n, dtype=np.int32),
        status=np.zeros(n, dtype=np.int32),
        limit=o * limit,
        remaining=o * remaining,
        reset_time=o * (NOW + 3_600_000),
        duration=o * 3_600_000,
        now_ms=NOW,
    )


def live_map(rows, now=NOW):
    """fp → slot bytes for every live slot (the byte-parity oracle)."""
    slots, fp, _exp = decode_live_slots(np.asarray(rows), now)
    return {int(f): s.tobytes() for f, s in zip(fp, slots)}


def unique_fps(rng, n):
    return np.unique(
        rng.integers(1, (1 << 63) - 1, size=n * 2, dtype=np.int64)
    )[:n]


# ------------------------------------------------------------ epoch tracker


def test_epoch_tracker_marks_and_takes():
    tr = EpochTracker(1024, blk=8)
    assert tr.nblk == 128
    fps = np.asarray([1, 9, 1024 + 1, 8 * 50 + 3], dtype=np.int64)
    tr.mark(fps)
    # buckets 1, 9, 1, 403 → blocks 0, 1, 0, 50
    epoch, gids = tr.take()
    assert epoch == 1
    assert gids.tolist() == [0, 1, 50]
    # take cleared; fp == 0 (padding) is ignored
    tr.mark(np.zeros(4, dtype=np.int64))
    epoch, gids = tr.take()
    assert epoch == 2 and gids.size == 0
    # remark re-arms a failed epoch's dirt
    tr.remark(np.asarray([7, 9]))
    assert tr.dirty_blocks == 2
    _, gids = tr.take()
    assert gids.tolist() == [7, 9]
    tr.mark_all()
    assert tr.dirty_blocks == tr.nblk


def test_epoch_tracker_sharded_and_rebuild():
    tr = EpochTracker(1024, n_shards=4, blk=8)
    from gubernator_tpu.parallel.mesh import shard_of

    fps = np.asarray([(7 << 32) | 5, (2 << 32) | 900], dtype=np.int64)
    tr.mark(fps)
    _, gids = tr.take()
    shards = shard_of(fps, 4)
    want = sorted(
        int(s) * tr.nblk + int((f % 1024) // 8) for s, f in zip(shards, fps)
    )
    assert gids.tolist() == want
    # rebuild (resize): epoch lineage continues, everything dirty
    tr2 = tr.rebuild(2048)
    assert tr2.epoch == tr.epoch and tr2.dirty_blocks == tr2.nblk * 4


def test_tracker_blk_divides_small_tables():
    # 32-bucket table with the default blk=8 → 4 blocks; blk larger than
    # the table clamps
    tr = EpochTracker(32)
    assert tr.nblk * tr.blk == 32
    tr = EpochTracker(4, blk=64)
    assert tr.blk == 4 and tr.nblk == 1


# ---------------------------------------------------------------- delta log


def test_delta_frame_roundtrip(tmp_path):
    log = DeltaLog(str(tmp_path / "x.delta"))
    rng = np.random.default_rng(0)
    s1 = rng.integers(-(2**31), 2**31 - 1, size=(10, 16)).astype(np.int32)
    s2 = rng.integers(-(2**31), 2**31 - 1, size=(7, 16)).astype(np.int32)
    assert log.append(1, NOW, s1) > s1.nbytes
    log.append(2, NOW + 5, s2)
    scan = log.scan()
    assert scan.error is None and len(scan.frames) == 2
    (e1, t1, r1, l1), (e2, t2, r2, l2) = scan.frames
    assert (e1, t1) == (1, NOW) and (e2, t2) == (2, NOW + 5)
    np.testing.assert_array_equal(r1, s1)
    np.testing.assert_array_equal(r2, s2)
    assert scan.rows == 17
    # reset truncates atomically to an empty (header-only) log
    log.reset()
    assert log.frame_count() == 0


def test_delta_log_crc_corruption_keeps_clean_prefix(tmp_path):
    log = DeltaLog(str(tmp_path / "x.delta"))
    rng = np.random.default_rng(1)
    frames = [
        rng.integers(-(2**31), 2**31 - 1, size=(5, 16)).astype(np.int32)
        for _ in range(3)
    ]
    offsets = [0]
    for i, s in enumerate(frames):
        log.append(i + 1, NOW, s)
        offsets.append(log.size_bytes())
    # flip one payload byte inside frame 2
    with open(log.path, "r+b") as f:
        f.seek(offsets[2] - 3)
        b = f.read(1)
        f.seek(offsets[2] - 3)
        f.write(bytes([b[0] ^ 0xFF]))
    scan = log.scan()
    assert len(scan.frames) == 1 and "CRC" in scan.error
    np.testing.assert_array_equal(scan.frames[0][2], frames[0])
    assert scan.skipped_bytes > 0


def test_delta_log_truncated_tail(tmp_path):
    log = DeltaLog(str(tmp_path / "x.delta"))
    rng = np.random.default_rng(2)
    s = rng.integers(-(2**31), 2**31 - 1, size=(64, 16)).astype(np.int32)
    log.append(1, NOW, s)
    clean = log.size_bytes()
    log.append(2, NOW, s)
    # crash mid-append: cut the second frame's payload short
    with open(log.path, "r+b") as f:
        f.truncate(clean + 40)
    scan = log.scan()
    assert len(scan.frames) == 1 and "truncated" in scan.error
    # a header-only tail (payload never started) also skips cleanly
    with open(log.path, "r+b") as f:
        f.truncate(clean + 10)
    scan = log.scan()
    assert len(scan.frames) == 1 and "truncated" in scan.error
    # garbage header magic stops the scan too
    with open(log.path, "r+b") as f:
        f.truncate(clean)
        f.seek(clean)
        f.write(b"\x00" * 64)
    scan = log.scan()
    assert len(scan.frames) == 1 and "magic" in scan.error


def test_delta_log_repair_extends_after_torn_tail(tmp_path):
    """A damaged log must be truncated to its clean prefix before new
    appends — otherwise fresh frames land after the corrupt bytes, where
    the prefix scan can never reach them."""
    log = DeltaLog(str(tmp_path / "x.delta"))
    rng = np.random.default_rng(3)
    s1 = rng.integers(-(2**31), 2**31 - 1, size=(6, 16)).astype(np.int32)
    s2 = rng.integers(-(2**31), 2**31 - 1, size=(9, 16)).astype(np.int32)
    log.append(1, NOW, s1)
    clean = log.size_bytes()
    log.append(2, NOW, s2)
    with open(log.path, "r+b") as f:  # crash mid-append: torn tail
        f.truncate(log.size_bytes() - 8)
    # appending WITHOUT repair strands the new frame behind the tear
    log.append(3, NOW, s2)
    scan = log.scan()
    assert scan.error and len(scan.frames) == 1
    assert scan.clean_bytes == clean
    # repair truncates to the clean prefix; appends then extend a
    # scannable log
    log.repair(scan)
    assert log.size_bytes() == clean
    log.append(3, NOW, s2)
    scan = log.scan()
    assert scan.error is None and len(scan.frames) == 2
    np.testing.assert_array_equal(scan.frames[1][2], s2)
    # a log whose own header is damaged repairs to empty
    with open(log.path, "r+b") as f:
        f.seek(0)
        f.write(b"\x00" * 4)
    scan = log.scan()
    assert scan.error and scan.clean_bytes == 0
    log.repair(scan)
    scan = log.scan()
    assert scan.error is None and len(scan.frames) == 0
    log.append(4, NOW, s1)
    assert log.frame_count() == 1


# ------------------------------------------------------------- extract pass


def test_extract_dirty_local_parity():
    eng = LocalEngine(capacity=1 << 14, write_mode="xla")
    rng = np.random.default_rng(3)
    fps = unique_fps(rng, 4000)
    install(eng, fps)
    NB = eng.table.rows.shape[0]
    tr = EpochTracker(NB)
    tr.mark(fps)
    _, gids = tr.take()
    got_fps, got_slots = finish_extract(
        extract_begin(eng.table.rows, gids, tr.blk, NOW)
    )
    want = live_map(eng.table.rows)
    got = {int(f): s.tobytes() for f, s in zip(got_fps, got_slots)}
    assert got == want  # byte parity against the live-slot oracle


def test_extract_dirty_is_incremental():
    """Only the touched blocks' rows come back — the batch-proportional
    contract (cost ∝ write rate, not table size)."""
    eng = LocalEngine(capacity=1 << 16, write_mode="xla")
    rng = np.random.default_rng(4)
    fps = unique_fps(rng, 10_000)
    install(eng, fps)
    NB = eng.table.rows.shape[0]
    eng.ckpt = EpochTracker(NB)
    eng.ckpt.take()  # drop the install's dirt
    touched = fps[:64]
    eng.check_columns(cols(touched), now_ms=NOW)
    _, gids = eng.ckpt.take()
    got_fps, _ = finish_extract(
        extract_begin(eng.table.rows, gids, eng.ckpt.blk, NOW)
    )
    assert set(touched.tolist()) <= set(got_fps.tolist())
    # amplification bound: ≤ dirty blocks × blk × K slots, ≪ the table
    assert got_fps.shape[0] <= gids.shape[0] * eng.ckpt.blk * 8
    assert got_fps.shape[0] < fps.shape[0] // 2


def test_engine_paths_mark_dirty():
    """Every mutation surface feeds the tracker: sync check, pipelined
    issue, install, merge, tombstone; restore marks everything."""
    from gubernator_tpu.ops.engine import (
        finish_check_columns,
        issue_check_columns,
        prepare_check_columns,
    )

    eng = LocalEngine(capacity=1 << 12, write_mode="xla")
    NB = eng.table.rows.shape[0]
    eng.ckpt = EpochTracker(NB)
    rng = np.random.default_rng(5)
    fps = unique_fps(rng, 32)
    eng.check_columns(cols(fps[:8]), now_ms=NOW)
    assert eng.ckpt.dirty_blocks > 0
    eng.ckpt.take()
    # pipelined: marking happens at ISSUE (engine-thread job), not prepare
    pend = prepare_check_columns(eng, cols(fps[8:16]), now_ms=NOW)
    assert eng.ckpt.dirty_blocks == 0
    pend = issue_check_columns(eng, pend)
    assert eng.ckpt.dirty_blocks > 0
    finish_check_columns(eng, pend, lambda fn: fn())
    eng.ckpt.take()
    install(eng, fps[16:24])
    assert eng.ckpt.dirty_blocks > 0
    _, gids = eng.ckpt.take()
    got_fps, got_slots = finish_extract(
        extract_begin(eng.table.rows, gids, eng.ckpt.blk, NOW)
    )
    assert set(fps[16:24].tolist()) <= set(got_fps.tolist())
    # merge + tombstone mark too
    eng.merge_rows(got_fps, got_slots, now_ms=NOW)
    assert eng.ckpt.dirty_blocks > 0
    eng.ckpt.take()
    eng.tombstone_fps(fps[16:24])
    assert eng.ckpt.dirty_blocks > 0
    eng.ckpt.take()
    eng.restore(eng.snapshot())
    assert eng.ckpt.dirty_blocks == eng.ckpt.nblk


def test_extract_dirty_sharded_parity():
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.sharded import ShardedEngine

    eng = ShardedEngine(
        make_mesh(8), capacity_per_shard=1 << 12, write_mode="xla"
    )
    rng = np.random.default_rng(6)
    fps = unique_fps(rng, 6000)
    install(eng, fps)
    eng.ckpt = EpochTracker(
        int(eng.table.rows.shape[-2]), n_shards=eng.n_shards
    )
    eng.ckpt.mark(fps)
    _, gids = eng.ckpt.take()
    got_fps, got_slots = eng.checkpoint_finish(eng.checkpoint_begin(gids, NOW))
    want = live_map(eng.table.rows)
    got = {int(f): s.tobytes() for f, s in zip(got_fps, got_slots)}
    assert got == want
    # incremental: touch a subset, extract covers it and stays partial
    eng.check_columns(cols(fps[:128]), now_ms=NOW)
    _, gids = eng.ckpt.take()
    got_fps, _ = eng.checkpoint_finish(eng.checkpoint_begin(gids, NOW))
    assert set(fps[:128].tolist()) <= set(got_fps.tolist())
    assert got_fps.shape[0] < fps.shape[0]


# ------------------------------------------------------------------- replay


def test_replay_parity_local(tmp_path):
    """Base + delta frames replayed through merge2 reconstruct the source
    table's live rows byte-for-byte (clean frames, no RESET traffic)."""
    log = DeltaLog(str(tmp_path / "x.delta"))
    src = LocalEngine(capacity=1 << 14, write_mode="xla")
    src.ckpt = EpochTracker(src.table.rows.shape[0])
    rng = np.random.default_rng(7)
    fps = unique_fps(rng, 3000)
    # epoch 1: first wave of traffic → base snapshot
    src.check_columns(cols(fps[:2000], hits=3), now_ms=NOW)
    base_path = str(tmp_path / "base.npz")
    save_snapshot(base_path, src.snapshot(), epoch=src.ckpt.take()[0])
    # epochs 2..4: more traffic → delta frames
    for i in range(3):
        sl = fps[2000 + 300 * i: 2300 + 300 * i]
        src.check_columns(cols(sl, hits=2), now_ms=NOW + 1 + i)
        src.check_columns(cols(fps[:200], hits=1), now_ms=NOW + 1 + i)
        epoch, gids = src.ckpt.take()
        _fps, slots = finish_extract(
            extract_begin(src.table.rows, gids, src.ckpt.blk, NOW + 1 + i)
        )
        log.append(epoch, NOW + 1 + i, slots)
    # restore: base, then frames with epoch > base epoch
    dst = LocalEngine(capacity=1 << 14, write_mode="xla")
    rows, base_epoch, _layout = load_snapshot_meta(base_path)
    dst.restore(rows)
    for epoch, now_ms, slots, _lay in log.scan().frames:
        assert epoch > base_epoch
        dst.merge_rows(fps_from_slots(slots), slots, now_ms=now_ms)
    assert live_map(dst.table.rows, NOW + 4) == live_map(
        src.table.rows, NOW + 4
    )


def test_replay_never_over_grants():
    """A STALE frame (higher remaining) replayed over newer state cannot
    re-grant capacity, and OVER_LIMIT sticks — merge2 semantics asserted
    on the replay path."""
    eng = LocalEngine(capacity=1 << 10, write_mode="xla")
    eng.ckpt = EpochTracker(eng.table.rows.shape[0])
    fp = np.asarray([12345], dtype=np.int64)
    # stale frame: 3 hits consumed (remaining 7)
    eng.check_columns(cols(fp, hits=3, limit=10), now_ms=NOW)
    _, gids = eng.ckpt.take()
    _f, stale = finish_extract(
        extract_begin(eng.table.rows, gids, eng.ckpt.blk, NOW)
    )
    # newer state: 4 more consumed (remaining 3)
    eng.check_columns(cols(fp, hits=4, limit=10), now_ms=NOW + 10)
    eng.merge_rows(fps_from_slots(stale), stale, now_ms=NOW + 20)
    rc = eng.check_columns(cols(fp, hits=0, limit=10), now_ms=NOW + 30)
    assert int(rc.remaining[0]) == 3  # min wins: stale 7 did not resurrect
    # OVER sticks: an OVER frame replayed onto an UNDER table pins OVER
    # (exhaust, then overdraw — a rejected burst alone stores UNDER, like
    # the reference: the stored status only flips once the bucket is dry)
    eng2 = LocalEngine(capacity=1 << 10, write_mode="xla")
    eng2.ckpt = EpochTracker(eng2.table.rows.shape[0])
    eng2.check_columns(cols(fp, hits=10, limit=10), now_ms=NOW)
    eng2.check_columns(cols(fp, hits=1, limit=10), now_ms=NOW)  # → OVER
    _, gids = eng2.ckpt.take()
    _f, over = finish_extract(
        extract_begin(eng2.table.rows, gids, eng2.ckpt.blk, NOW)
    )
    eng3 = LocalEngine(capacity=1 << 10, write_mode="xla")
    eng3.check_columns(cols(fp, hits=1, limit=10), now_ms=NOW)  # UNDER
    eng3.merge_rows(fps_from_slots(over), over, now_ms=NOW + 1)
    rc = eng3.check_columns(cols(fp, hits=0, limit=10), now_ms=NOW + 2)
    assert int(rc.status[0]) == 1  # OVER stuck


def test_replay_expired_frames_drop():
    """Rows already expired at replay time must not resurrect."""
    eng = LocalEngine(capacity=1 << 10, write_mode="xla")
    eng.ckpt = EpochTracker(eng.table.rows.shape[0])
    fp = np.asarray([777], dtype=np.int64)
    c = cols(fp, hits=1, limit=10)._replace(
        duration=np.asarray([1000], dtype=np.int64)
    )
    eng.check_columns(c, now_ms=NOW)
    _, gids = eng.ckpt.take()
    _f, slots = finish_extract(
        extract_begin(eng.table.rows, gids, eng.ckpt.blk, NOW)
    )
    dst = LocalEngine(capacity=1 << 10, write_mode="xla")
    merged = dst.merge_rows(fps_from_slots(slots), slots, now_ms=NOW + 10_000)
    assert merged == 0 and dst.live_count(NOW + 10_000) == 0


# ----------------------------------------------------------- daemon plane


def ckpt_config(tmp_path, interval_ms=10_000.0, **over):
    conf = daemon_config(**over)
    conf.checkpoint_path = str(tmp_path / "base.npz")
    conf.checkpoint_interval_ms = interval_ms
    return conf


@async_test
async def test_daemon_checkpoint_loop_and_debug(tmp_path):
    """The background loop writes frames while serving; metrics families
    populate and /v1/debug/durability reports the plane's state."""
    import aiohttp

    from gubernator_tpu.service.daemon import Daemon
    from tests.cluster import metric_value, scrape, wait_for

    d = await Daemon.spawn(ckpt_config(tmp_path, interval_ms=25.0))
    try:
        for i in range(4):
            await d.get_rate_limits([
                pb.RateLimitReq(
                    name="dur", unique_key=f"k{i}", hits=1, limit=100,
                    duration=3_600_000,
                )
            ])
        # what the assertions below state: every one of the four rows is in
        # a frame on disk. An epoch is taken under the loop's lock, so the
        # second one to become durable from here was taken after the fourth
        # answer (the first may have been in flight when it came)
        cp = d.checkpointer
        answered_at = cp.last_epoch
        await wait_for(
            lambda: asyncio.sleep(0, cp.last_epoch >= answered_at + 2
                                  and cp.rows >= 4
                                  and cp._log.size_bytes() > 8)
        )
        scraped = await scrape(d)
        assert metric_value(
            scraped, "gubernator_tpu_checkpoint_rows_total", kind="delta"
        ) >= 4
        assert metric_value(
            scraped, "gubernator_tpu_checkpoint_bytes_total", kind="delta"
        ) > 0
        async with aiohttp.ClientSession() as s:
            url = f"http://{d.conf.http_address}/v1/debug/durability"
            async with s.get(url) as resp:
                assert resp.status == 200
                js = await resp.json()
        assert js["enabled"] is True
        assert js["last_epoch"] >= 1
        assert js["delta_log_bytes"] > 8
        assert js["last_error"] is None
        assert js["pending_dirty_blocks"] >= 0
    finally:
        await d.close()
    # graceful close compacted: base carries everything, log is empty
    _rows, epoch, _layout = load_snapshot_meta(str(tmp_path / "base.npz"))
    assert epoch >= 1
    assert DeltaLog(str(tmp_path / "base.npz") + ".delta").frame_count() == 0


@async_test
async def test_kill9_recovery_bound(tmp_path):
    """THE chaos acceptance: a daemon kill -9'd mid-traffic recovers from
    base + deltas, serves, and over-admits at most the writes admitted
    after the last checkpoint epoch — never under-counting in the safe
    direction (recovered remaining ≤ true remaining)."""
    from tests.cluster import Cluster

    cluster = await Cluster.start(
        1, checkpoint_path=str(tmp_path / "base.npz"),
        checkpoint_interval_ms=60_000.0,  # ticks driven manually below
    )
    d = cluster.daemons[0]
    LIMIT = 1000

    async def hit(n):
        r = await d.get_rate_limits([
            pb.RateLimitReq(
                name="chaos", unique_key="k", hits=n, limit=LIMIT,
                duration=3_600_000,
            )
        ])
        assert not r[0].error
        return r[0]

    try:
        for _ in range(12):
            await hit(50)  # 600 consumed
        await d.checkpointer.checkpoint_once()  # durable through 600
        window = 0
        for _ in range(2):
            await hit(50)  # 100 more — the at-risk window
            window += 50
        pre = await hit(0)
        assert pre.remaining == LIMIT - 700
        d = await cluster.crash_restart(0)  # kill -9 + respawn
        post = await hit(0)
        # recovered: the checkpointed 600 are remembered (not a cold start)
        # and the bound holds: re-granted capacity == the post-checkpoint
        # window, and the safe direction never over-counts remaining
        assert post.remaining == LIMIT - 600
        assert post.remaining - pre.remaining <= window
        # drive to OVER: total admitted across both lives ≤ limit + window
        admitted = 700
        while True:
            r = await hit(50)
            if r.status == pb.OVER_LIMIT:
                break
            admitted += 50
        assert admitted <= LIMIT + window
    finally:
        await cluster.stop()


@async_test
async def test_sharded_daemon_warm_restart(tmp_path):
    """Incremental checkpointing on the mesh engine: per-shard extract,
    abort, replay — counts survive on an 8-device sharded daemon."""
    from tests.cluster import Cluster

    cluster = await Cluster.start(
        1, engine="sharded", cache_size=4096,
        checkpoint_path=str(tmp_path / "base.npz"),
        checkpoint_interval_ms=60_000.0,
    )
    d = cluster.daemons[0]
    try:
        for i in range(16):
            r = await d.get_rate_limits([
                pb.RateLimitReq(
                    name="mesh", unique_key=f"k{i}", hits=4, limit=10,
                    duration=3_600_000,
                )
            ])
            assert not r[0].error
        await d.checkpointer.checkpoint_once()
        d = await cluster.crash_restart(0)
        assert d.checkpointer.restored in ("delta", "base+delta")
        for i in range(16):
            r = await d.get_rate_limits([
                pb.RateLimitReq(
                    name="mesh", unique_key=f"k{i}", hits=0, limit=10,
                    duration=3_600_000,
                )
            ])
            assert r[0].remaining == 6, (i, r[0])
    finally:
        await cluster.stop()


@async_test
async def test_compaction_folds_frames(tmp_path):
    """After GUBER_CHECKPOINT_COMPACT_FRAMES deltas the log folds into a
    fresh base and restarts replay nothing."""
    from gubernator_tpu.service.daemon import Daemon

    conf = ckpt_config(tmp_path)
    conf.checkpoint_compact_frames = 3
    d = await Daemon.spawn(conf)
    try:
        for i in range(3):
            await d.get_rate_limits([
                pb.RateLimitReq(
                    name="cp", unique_key=f"k{i}", hits=2, limit=10,
                    duration=3_600_000,
                )
            ])
            await d.checkpointer.checkpoint_once()
        assert d.checkpointer.frames_since_compaction == 0  # compacted
        assert d.checkpointer.base_epoch >= 3
        await d.abort()
        d2 = await Daemon.spawn(conf)
        assert d2.checkpointer.restored == "base"
        assert d2.checkpointer.replayed_frames == 0
        r = await d2.get_rate_limits([
            pb.RateLimitReq(
                name="cp", unique_key="k0", hits=0, limit=10,
                duration=3_600_000,
            )
        ])
        assert r[0].remaining == 8
        await d2.close()
    finally:
        if not d._shutting_down:
            await d.close()


@async_test
async def test_geometry_mismatch_cold_start(tmp_path):
    """A snapshot whose row geometry no longer matches the configured
    table (cache_size changed across restart) logs and cold-starts
    instead of crashing engine.restore at boot — on both restore paths."""
    from gubernator_tpu.service.daemon import Daemon

    path = str(tmp_path / "base.npz")
    save_snapshot(path, np.ones((64, 128), dtype=np.int32), epoch=1)
    for interval in (0.0, 10_000.0):  # classic Loader path + incremental
        conf = daemon_config(cache_size=8192)
        conf.checkpoint_path = path
        conf.checkpoint_interval_ms = interval
        d = await Daemon.spawn(conf)  # must not raise
        try:
            assert await d.runner.live_count() == 0  # cold
            r = await d.get_rate_limits([
                pb.RateLimitReq(
                    name="g", unique_key="k", hits=1, limit=5,
                    duration=60_000,
                )
            ])
            assert r[0].remaining == 4
            assert (
                d.metrics.checkpoint_errors.labels(stage="restore")
                ._value.get() >= 1
            )
        finally:
            # close() re-snapshots at the CONFIGURED geometry, so the next
            # loop iteration needs the mismatched file back
            await d.abort()
            save_snapshot(path, np.ones((64, 128), dtype=np.int32), epoch=1)


@async_test
async def test_corrupt_snapshot_cold_start(tmp_path):
    from gubernator_tpu.service.daemon import Daemon

    path = str(tmp_path / "base.npz")
    with open(path, "wb") as f:
        f.write(b"this is not a snapshot")
    conf = ckpt_config(tmp_path)
    d = await Daemon.spawn(conf)
    try:
        assert d.checkpointer.restored == "cold"
        r = await d.get_rate_limits([
            pb.RateLimitReq(
                name="c", unique_key="k", hits=1, limit=5, duration=60_000,
            )
        ])
        assert r[0].remaining == 4
    finally:
        await d.close()


@async_test
async def test_restore_repairs_torn_delta_log(tmp_path):
    """A kill -9 can tear the delta log mid-append; restore must truncate
    it to the clean prefix before serving, or every frame the restarted
    daemon appends lands behind the corrupt bytes where replay cannot
    reach it — a SECOND kill -9 before compaction would then lose up to
    compact_frames × interval of writes, not one interval."""
    from gubernator_tpu.service.daemon import Daemon

    conf = ckpt_config(tmp_path)
    d = await Daemon.spawn(conf)
    delta_path = d.checkpointer.delta_path
    try:
        await d.get_rate_limits([
            pb.RateLimitReq(
                name="t", unique_key="k0", hits=2, limit=10,
                duration=3_600_000,
            )
        ])
        await d.checkpointer.checkpoint_once()
        await d.get_rate_limits([
            pb.RateLimitReq(
                name="t", unique_key="k1", hits=3, limit=10,
                duration=3_600_000,
            )
        ])
        await d.checkpointer.checkpoint_once()
    finally:
        await d.abort()
    # crash mid-append: tear the second frame's tail
    with open(delta_path, "r+b") as f:
        f.truncate(os.path.getsize(delta_path) - 8)

    d2 = await Daemon.spawn(conf)
    try:
        assert d2.checkpointer.restored == "delta"
        assert d2.checkpointer.replayed_frames == 1
        assert (
            d2.metrics.checkpoint_errors.labels(stage="restore")
            ._value.get() >= 1
        )
        # life 2 admits more writes and checkpoints them...
        await d2.get_rate_limits([
            pb.RateLimitReq(
                name="t", unique_key="k2", hits=4, limit=10,
                duration=3_600_000,
            )
        ])
        await d2.checkpointer.checkpoint_once()
        # ...onto a repaired, scannable log
        assert DeltaLog(delta_path).scan().error is None
    finally:
        await d2.abort()

    d3 = await Daemon.spawn(conf)
    try:
        # a SECOND unclean death still recovers life 2's writes: the frame
        # appended after the repair replays
        assert d3.checkpointer.replayed_frames == 2
        for key, want in (("k0", 8), ("k2", 6)):
            r = await d3.get_rate_limits([
                pb.RateLimitReq(
                    name="t", unique_key=key, hits=0, limit=10,
                    duration=3_600_000,
                )
            ])
            assert r[0].remaining == want, (key, r[0])
    finally:
        await d3.close()


@async_test
async def test_shutdown_completes_with_failing_loader(tmp_path):
    """Satellite: a Loader whose save() raises (disk full, unwritable
    path) must not wedge close() — _door/runner shutdown always run, the
    failure is logged + counted."""
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.store import Loader

    class BoomLoader(Loader):
        def load(self):
            return None

        def save(self, rows):
            raise IOError("disk full")

    d = await Daemon.spawn(daemon_config(), loader=BoomLoader())
    await d.get_rate_limits([
        pb.RateLimitReq(
            name="b", unique_key="k", hits=1, limit=5, duration=60_000,
        )
    ])
    await d.close()  # must complete despite the failing save
    assert (
        d.metrics.checkpoint_errors.labels(stage="shutdown")._value.get()
        == 1
    )
    # the runner's executors really shut down (close reached them)
    with pytest.raises(RuntimeError):
        d.runner._exec.submit(lambda: None)


@async_test
async def test_unwritable_delta_path_defers_dirt(tmp_path):
    """A failed delta append re-arms the taken dirty set (remark): the
    next epoch still carries the writes once the disk recovers."""
    from gubernator_tpu.service.daemon import Daemon

    conf = ckpt_config(tmp_path)
    conf.checkpoint_delta_path = str(tmp_path / "no" / "such" / "dir.delta")
    d = await Daemon.spawn(conf)
    try:
        await d.get_rate_limits([
            pb.RateLimitReq(
                name="e", unique_key="k", hits=1, limit=5, duration=60_000,
            )
        ])
        # make the append fail: point the log at a directory path
        os.makedirs(conf.checkpoint_delta_path, exist_ok=True)
        out = await d.checkpointer.checkpoint_once()
        assert "error" in out
        assert d.checkpointer.last_error is not None
        assert d.engine.ckpt.dirty_blocks > 0  # re-armed, not lost
        assert (
            d.metrics.checkpoint_errors.labels(stage="delta")._value.get()
            >= 1
        )
        # recovery: free the path → the same dirt persists on the next tick
        os.rmdir(conf.checkpoint_delta_path)
        out = await d.checkpointer.checkpoint_once()
        assert out["rows"] >= 1 and out["bytes"] > 0
    finally:
        await d.close()


def test_config_validation():
    with pytest.raises(ConfigError, match="GUBER_CHECKPOINT_PATH"):
        setup_daemon_config(env={"GUBER_CHECKPOINT_INTERVAL_MS": "100"})
    with pytest.raises(ConfigError, match="COMPACT_FRAMES"):
        setup_daemon_config(env={
            "GUBER_CHECKPOINT_PATH": "/tmp/x.npz",
            "GUBER_CHECKPOINT_COMPACT_FRAMES": "0",
        })
    with pytest.raises(ConfigError, match="DELTA_PATH"):
        setup_daemon_config(env={"GUBER_CHECKPOINT_DELTA_PATH": "/tmp/x"})
    conf = setup_daemon_config(env={
        "GUBER_CHECKPOINT_PATH": "/tmp/x.npz",
        "GUBER_CHECKPOINT_INTERVAL_MS": "1s",
        "GUBER_CHECKPOINT_COMPACT_FRAMES": "16",
    })
    assert conf.checkpoint_interval_ms == 1000.0
    assert conf.checkpoint_compact_frames == 16


# ----------------------------------------------------- true kill -9 (slow)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
def test_true_kill9_subprocess(tmp_path):
    """The real thing: SIGKILL a server PROCESS mid-traffic, restart it on
    the same checkpoint dir, and verify the recovered daemon serves warm
    state (the in-process chaos tests above prove the bound; this proves
    no in-process shutdown hook was load-bearing)."""
    import urllib.request

    grpc_port, http_port = _free_port(), _free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        GUBER_GRPC_ADDRESS=f"127.0.0.1:{grpc_port}",
        GUBER_HTTP_ADDRESS=f"127.0.0.1:{http_port}",
        GUBER_CACHE_SIZE="8192",
        GUBER_CHECKPOINT_PATH=str(tmp_path / "base.npz"),
        GUBER_CHECKPOINT_INTERVAL_MS="100",
        GUBER_BATCH_WAIT="1ms",
    )

    def spawn():
        return subprocess.Popen(
            [sys.executable, "-m", "gubernator_tpu"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )

    def post(payload: bytes) -> dict:
        import json

        req = urllib.request.Request(
            f"http://127.0.0.1:{http_port}/v1/GetRateLimits",
            data=payload, headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=5) as resp:
            return json.loads(resp.read())

    def wait_ready(proc, timeout=120):
        deadline = time.time() + timeout
        while time.time() < deadline:
            assert proc.poll() is None, "server died during startup"
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/v1/HealthCheck", timeout=1
                )
                return
            except Exception:
                time.sleep(0.5)
        raise TimeoutError("server did not come up")

    body = (
        b'{"requests": [{"name": "kill9", "unique_key": "k", "hits": %d,'
        b' "limit": "100", "duration": "3600000"}]}'
    )
    proc = spawn()
    try:
        wait_ready(proc)
        for _ in range(5):
            r = post(body % 10)
            assert not r["responses"][0].get("error")
        time.sleep(1.0)  # ≥ several checkpoint intervals
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        proc = spawn()
        wait_ready(proc)
        r = post(body % 0)
        remaining = int(r["responses"][0]["remaining"])
        # 50 hits admitted pre-kill; every checkpointed epoch survives, so
        # the recovered count is warm (< 100) and conservative (≥ 50)
        assert remaining <= 50
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
