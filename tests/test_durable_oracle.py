"""The deployment `token10m-durable` at a size the CPU holds: a daemon with
the incremental checkpoint plane armed, driven through its gRPC door on
seeded keys and compared with the plain durable reference
(tests/oracle/durable.py), and the properties the benchmark cell leans on —
epochs on a fixed period, no program compiled by an epoch after warm-up, a
graceful stop whose base restore reads back, the plane in
/v1/debug/pipeline (docs/durability.md, PERF.md section 4).
"""

import asyncio
import copy
import functools
import time
import types
import zipfile

import numpy as np
import pytest

from gubernator_tpu.client import V1Client
from gubernator_tpu.ops.table2 import decode_live_slots
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.checkpoint import CheckpointManager
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.store import (
    SNAPSHOT_MAGIC,
    DeltaLog,
    encode_delta_frame,
    load_snapshot,
    load_snapshot_meta,
    read_delta_frames,
    save_snapshot,
)
from tests.cluster import daemon_config
from tests.oracle.durable import DurableOracle

LIMIT, DURATION = 10, 3_600_000
KEYS = 400


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def armed(tmp_path, interval_ms=3_600_000.0, **over):
    """A daemon's configuration with the plane armed; the default interval
    never ticks in a test, which then drives the epochs itself."""
    conf = daemon_config(**over)
    conf.checkpoint_path = str(tmp_path / "base.npz")
    conf.checkpoint_interval_ms = interval_ms
    return conf


def live_map(rows):
    """fp -> slot bytes of what is live now (warm_up's rows, which expire
    as they are written, are no one's state)."""
    slots, fp, _exp = decode_live_slots(np.asarray(rows), int(time.time() * 1e3))
    return {int(f): s.tobytes() for f, s in zip(fp, slots)}


class Traffic:
    """Seeded RPCs of checks through the door, each answer compared with
    the references as it arrives."""

    def __init__(self, seed, oracles):
        self.rng = np.random.default_rng(seed)
        self.oracles = oracles
        self.sent = {}  # key -> hits sent in this life and the one before

    async def rpc(self, client, n_items=25, compare=True):
        keys = self.rng.choice(KEYS, n_items, replace=False)
        hits = self.rng.integers(1, 4, n_items)
        now = int(time.time() * 1e3)
        resp = await client.get_rate_limits([
            pb.RateLimitReq(
                name="durable", unique_key=f"k{k}", hits=int(h), limit=LIMIT,
                duration=DURATION, created_at=now,
            )
            for k, h in zip(keys, hits)
        ])
        for k, h, r in zip(keys, hits, resp.responses):
            assert r.error == ""
            want = [o.check(int(k), now, int(h), LIMIT, DURATION)
                    for o in self.oracles]
            if compare:
                assert (r.status, r.remaining, r.reset_time) == want[0], (k, h)
            if r.status == pb.UNDER_LIMIT:
                self.sent[int(k)] = self.sent.get(int(k), 0) + int(h)

    async def peek_all(self, client):
        now = int(time.time() * 1e3)
        resp = await client.get_rate_limits([
            pb.RateLimitReq(
                name="durable", unique_key=f"k{k}", hits=0, limit=LIMIT,
                duration=DURATION, created_at=now,
            )
            for k in range(KEYS)
        ])
        return now, [r.remaining for r in resp.responses]


@async_test
async def test_served_answers_and_crash_bounds_against_the_durable_oracle(tmp_path):
    """Before the crash every answer is the uncrashed reference's. After
    Daemon.abort and a restart every key's remaining lies between the
    uncrashed reference's and the crashed one's, and what a key is granted
    over both lives is at most its limit plus the hits admitted since the
    last completed epoch."""
    never, crashed = DurableOracle(), DurableOracle()
    traffic = Traffic(20260929, (never, crashed))
    d = await Daemon.spawn(armed(tmp_path))
    c = V1Client(d.conf.grpc_address)
    try:
        for _ in range(12):
            await traffic.rpc(c)
        out = await d.checkpointer.checkpoint_once()
        assert out["rows"] > 0 and out["bytes"] > 0
        never.checkpoint(), crashed.checkpoint()
        for _ in range(8):
            await traffic.rpc(c)  # admitted, and not durable
        at_risk = dict(crashed.since)
        assert sum(at_risk.values()) > 0
        await c.close()
        conf = d.conf
        await d.abort()
        crashed.crash()
        d = await Daemon.spawn(conf)
        assert d.checkpointer.restored == "delta"
        c = V1Client(d.conf.grpc_address)
        now, got = await traffic.peek_all(c)
        regranted = 0
        for k, rem in enumerate(got):
            lo = never.live.check(k, now, 0, LIMIT, DURATION)[1]
            hi = crashed.live.check(k, now, 0, LIMIT, DURATION)[1]
            assert lo <= rem <= hi, (k, lo, rem, hi)
            assert rem - lo <= at_risk.get(k, 0)
            regranted += rem - lo
        assert regranted > 0  # the crash lost something, or nothing was shown
        # drive every key over its limit: both lives together grant a key at
        # most its limit and what was at risk when the first life ended
        before = dict(traffic.sent)
        for _ in range(2 * LIMIT):
            resp = await c.get_rate_limits([
                pb.RateLimitReq(name="durable", unique_key=f"k{k}", hits=1,
                                limit=LIMIT, duration=DURATION)
                for k in range(KEYS)
            ])
            for k, r in enumerate(resp.responses):
                if r.status == pb.UNDER_LIMIT:
                    traffic.sent[k] = traffic.sent.get(k, 0) + 1
        for k in range(KEYS):
            assert traffic.sent.get(k, 0) <= LIMIT + at_risk.get(k, 0), k
            assert traffic.sent.get(k, 0) >= max(LIMIT, before.get(k, 0))
    finally:
        await c.close()
        await d.close()


@async_test
async def test_base_and_deltas_replay_to_the_table_byte_for_byte(tmp_path):
    """Epochs, a compaction, more epochs, an unclean death: the restart's
    table holds the bytes the dead one held at its last epoch."""
    oracle = DurableOracle()
    traffic = Traffic(7, (oracle,))
    d = await Daemon.spawn(armed(tmp_path, checkpoint_compact_frames=3))
    c = V1Client(d.conf.grpc_address)
    try:
        for _ in range(5):  # the third frame compacts
            for _ in range(3):
                await traffic.rpc(c)
            await d.checkpointer.checkpoint_once()
        assert d.checkpointer.bases == 1
        assert d.checkpointer.frames_since_compaction == 2
        held = live_map(d.engine.table.rows)
        assert len(held) > KEYS // 2
        await c.close()
        conf = d.conf
        await d.abort()
        d = await Daemon.spawn(conf)
        assert d.checkpointer.restored == "base+delta"
        assert d.checkpointer.replayed_frames == 2
        got = live_map(d.engine.table.rows)
        assert {k: got[k] for k in held} == held
        c = V1Client(d.conf.grpc_address)
        now, rem = await traffic.peek_all(c)
        assert rem == [
            oracle.live.check(k, now, 0, LIMIT, DURATION)[1] for k in range(KEYS)
        ]
    finally:
        await c.close()
        await d.close()


def _fake_manager(interval_s, epoch_takes):
    """A CheckpointManager on a clock the test owns: `epoch_takes` is the
    seconds each epoch's work advances it by."""
    conf = types.SimpleNamespace(
        checkpoint_interval_ms=interval_s * 1e3, checkpoint_compact_frames=64,
        checkpoint_path="", checkpoint_delta_path="",
    )
    daemon = types.SimpleNamespace(conf=conf, _shutting_down=False)
    m = CheckpointManager(daemon)
    clock = types.SimpleNamespace(t=100.0)
    starts, takes = [], list(epoch_takes)

    async def sleep(dt):
        clock.t += dt

    async def once():
        starts.append(round(clock.t - 100.0, 6))
        clock.t += takes.pop(0)
        if not takes:
            daemon._shutting_down = True
        return {}

    m._clock, m._sleep, m.checkpoint_once = (lambda: clock.t), sleep, once
    return m, starts


def test_epochs_keep_their_period_when_an_epoch_is_slow():
    """An epoch is due one interval after the one before it was due: work
    that takes 0.4 or 0.9 of the interval does not stretch the period (the
    loop used to sleep the interval AFTER each epoch)."""
    m, starts = _fake_manager(1.0, [0.4, 0.9, 0.0, 0.4, 0.1])
    asyncio.run(m.loop())
    assert starts == [1.0, 2.0, 3.0, 4.0, 5.0]
    # the enabled flag is off (no path): the block reads None, the period is
    # still the loop's
    assert m._starts == 5
    assert (m._last_start - m._first_start) / 4 == pytest.approx(1.0)


def test_an_epoch_that_overruns_is_followed_at_once():
    m, starts = _fake_manager(1.0, [0.2, 1.3, 0.2, 0.2])
    asyncio.run(m.loop())
    # the second epoch ends 0.3 s after the third was due: the third starts
    # there, and the period counts from it
    assert starts == [1.0, 2.0, 3.3, 4.3]


@async_test
async def test_epochs_after_warm_up_compile_nothing(tmp_path):
    """Spawn compiled the extract's programs; epochs over dirty sets of any
    size then add no compiled program — not a gather, not a slice of its
    output (a pow2 pad per dirty-set size compiled one of each)."""
    import jax

    from gubernator_tpu.ops import checkpoint as ck

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(name)
        if name.endswith("backend_compile_duration") else None
    )
    d = await Daemon.spawn(armed(tmp_path))
    c = V1Client(d.conf.grpc_address)
    try:
        warmed = ck._extract_blocks_grid._cache_size()
        assert warmed >= 1
        for n_items in (1, 40, 300, 7):
            await Traffic(n_items, ()).rpc(c, n_items=n_items, compare=False)
            seen = len(compiles)
            out = await d.checkpointer.checkpoint_once()
            assert out["rows"] >= n_items
            assert len(compiles) == seen, compiles[seen:]
        assert ck._extract_blocks_grid._cache_size() == warmed
    finally:
        await c.close()
        await d.close()


def test_extract_grids_hold_any_dirty_set():
    """A dirty set wider than a grid runs the wide grid as often as it
    needs; the rows come back in table order whatever the cut."""
    from gubernator_tpu.ops import checkpoint as ck
    from gubernator_tpu.ops.engine import LocalEngine
    from tests.test_durability import NOW, cols, unique_fps

    eng = LocalEngine(capacity=1 << 17, write_mode="xla")  # 16,384 buckets
    eng.ckpt = ck.EpochTracker(eng.table.rows.shape[0])
    fps = unique_fps(np.random.default_rng(3), 30_000)
    eng.check_columns(cols(fps), now_ms=NOW)
    _, gids = eng.ckpt.take()
    assert gids.shape[0] > ck.EXTRACT_GRIDS[0]
    grids = (4096, 8192)
    old, ck.EXTRACT_GRIDS = ck.EXTRACT_GRIDS, grids
    try:
        pending = eng.checkpoint_begin(gids, NOW)
        assert len(pending) == -(-gids.shape[0] // 8192)
        got_fps, got_slots = eng.checkpoint_finish(pending)
    finally:
        ck.EXTRACT_GRIDS = old
    slots, fp, _ = decode_live_slots(np.asarray(eng.table.rows), NOW)
    assert got_fps.tolist() == fp.tolist()
    assert got_slots.tobytes() == slots.tobytes()


@async_test
async def test_a_graceful_stop_writes_what_restore_reads_back(tmp_path):
    """The stop's compaction leaves the base — the occupied slots, plain —
    and an empty log; the next start restores the table from it."""
    oracle = DurableOracle()
    traffic = Traffic(11, (oracle,))
    d = await Daemon.spawn(armed(tmp_path))
    c = V1Client(d.conf.grpc_address)
    for _ in range(10):
        await traffic.rpc(c)
    await d.checkpointer.checkpoint_once()
    for _ in range(4):
        await traffic.rpc(c)
    held = live_map(d.engine.table.rows)
    table_bytes = d.engine.table.rows.nbytes
    await c.close()
    conf = d.conf
    await d.close()
    base = str(tmp_path / "base.npz")
    assert read_delta_frames(base + ".delta").frames == []
    with zipfile.ZipFile(base) as z:
        assert {i.compress_type for i in z.infolist()} == {zipfile.ZIP_STORED}
        names = {i.filename for i in z.infolist()}
    assert {"slots.npy", "occupied.npy", "shape.npy"} <= names and "rows.npy" not in names
    import os

    assert os.path.getsize(base) < table_bytes // 4
    d = await Daemon.spawn(conf)
    c = V1Client(d.conf.grpc_address)
    try:
        assert d.checkpointer.restored == "base"
        got = live_map(d.engine.table.rows)
        assert {k: got[k] for k in held} == held
        now, rem = await traffic.peek_all(c)
        assert rem == [
            oracle.live.check(k, now, 0, LIMIT, DURATION)[1] for k in range(KEYS)
        ]
    finally:
        await c.close()
        await d.close()


@async_test
async def test_the_plane_in_debug_pipeline_and_its_stages(tmp_path):
    """`engine.ckpt_blk` and the `checkpoint` block are what the benchmark
    reads: armed, counted, the age read-and-reset; `ckpt_mark`, `ckpt_append`
    and `ckpt_base` are stages beside `ckpt_launch`/`ckpt_fetch`."""
    from tests.cluster import metric_value, scrape

    d = await Daemon.spawn(armed(tmp_path))
    c = V1Client(d.conf.grpc_address)
    try:
        pipe = d.debug_pipeline()
        assert pipe["engine"]["ckpt_blk"] == 1
        assert pipe["checkpoint"]["epochs"] == 0
        checks0 = pipe["engine"]["checks"]
        t = Traffic(5, ())
        for _ in range(3):
            await t.rpc(c, compare=False)
        await asyncio.sleep(0.05)
        out = await d.checkpointer.checkpoint_once()
        await d.checkpointer.compact()
        pipe = d.debug_pipeline()
        ck = pipe["checkpoint"]
        assert ck["epochs"] == 1 and ck["bases"] == 1 and ck["extracts"] == 1
        assert ck["dirty_blocks"] == out["dirty_blocks"] > 0
        assert ck["rows"] == out["rows"] >= 25
        assert ck["bytes"] == out["bytes"] >= 64 * out["rows"]
        assert ck["period_ms"] is None  # the loop has not ticked
        assert ck["epoch_age_ms_max"] >= 50.0  # the state sat 50 ms undurable
        assert d.debug_pipeline()["checkpoint"]["epoch_age_ms_max"] < ck["epoch_age_ms_max"]
        assert pipe["engine"]["checks"] - checks0 == 75
        scraped = await scrape(d)
        for stage, n in (("ckpt_mark", 3), ("ckpt_launch", 1), ("ckpt_fetch", 1),
                         ("ckpt_append", 1), ("ckpt_base", 1)):
            assert metric_value(
                scraped, "gubernator_tpu_stage_duration_count", stage=stage
            ) >= n, stage
    finally:
        await c.close()
        await d.close()
    off = await Daemon.spawn(daemon_config())
    try:
        pipe = off.debug_pipeline()
        assert pipe["engine"]["ckpt_blk"] is None and pipe["checkpoint"] is None
    finally:
        await off.close()


# ------------------------------------------------------------------ the files


def _table(rng, buckets=64):
    rows = np.zeros((buckets, 128), dtype=np.int32)
    slots = rows.reshape(-1, 16)
    used = rng.random(slots.shape[0]) < 0.6
    slots[used] = rng.integers(1, 1 << 30, (int(used.sum()), 16), dtype=np.int32)
    return rows


@pytest.mark.parametrize("shape", [(64, 128), (2, 32, 128)])
def test_snapshot_of_a_table_is_its_occupied_slots_plain(tmp_path, shape):
    rows = _table(np.random.default_rng(1), int(np.prod(shape[:-1]))).reshape(shape)
    path = str(tmp_path / "b.npz")
    n = save_snapshot(path, rows, epoch=9)
    assert n == int((rows.reshape(-1, 16)[:, 0] != 0).sum())
    got, epoch, layout = load_snapshot_meta(path)
    assert (epoch, layout) == (9, "full") and got.shape == shape
    assert got.tobytes() == rows.tobytes()
    # an empty slot's other fields are nothing: they are not written
    junk = rows.copy()
    empty = junk.reshape(-1, 16)[:, 0] == 0
    junk.reshape(-1, 16)[empty, 5] = 77
    junk.reshape(-1, 16)[empty, 1] = 0
    save_snapshot(path, junk, epoch=9)
    assert load_snapshot(path).tobytes() == rows.tobytes()


def test_a_compressed_whole_image_still_loads(tmp_path):
    """What every earlier version wrote: `rows`, deflated."""
    rows = _table(np.random.default_rng(2))
    path = str(tmp_path / "old.npz")
    with open(path, "wb") as f:
        np.savez_compressed(
            f, magic=np.frombuffer(SNAPSHOT_MAGIC.encode(), dtype=np.uint8),
            rows=rows, epoch=np.int64(4),
        )
    got, epoch, layout = load_snapshot_meta(path)
    assert got.tobytes() == rows.tobytes() and (epoch, layout) == (4, "full")
    assert load_snapshot(path).tobytes() == rows.tobytes()


def test_an_appended_frame_is_the_encoded_frame(tmp_path):
    """DeltaLog.append writes header and rows where they lie; the file holds
    the bytes `encode_delta_frame` joins, for rows that are a strided view
    as for rows that are contiguous."""
    rng = np.random.default_rng(3)
    wide = rng.integers(1, 1 << 30, (50, 32), dtype=np.int32)
    log = DeltaLog(str(tmp_path / "x.delta"))
    for epoch, slots in ((1, wide[:, :16]), (2, np.ascontiguousarray(wide[:, 16:])),
                         (3, np.zeros((0, 16), dtype=np.int32))):
        n = log.append(epoch, 1234, slots)
        assert n == len(encode_delta_frame(epoch, 1234, slots)) + (8 if epoch == 1 else 0)
    frames = log.scan().frames
    assert [f[0] for f in frames] == [1, 2, 3]
    assert frames[0][2].tobytes() == wide[:, :16].tobytes()
    assert frames[1][2].tobytes() == wide[:, 16:].tobytes()
    with open(log.path, "rb") as f:
        body = f.read()[8:]
    assert body.startswith(encode_delta_frame(1, 1234, wide[:, :16]))


def test_the_durable_oracle_itself():
    o = DurableOracle()
    assert o.check("a", 0, 3, LIMIT, DURATION) == (0, 7, DURATION)
    o.checkpoint()
    assert o.check("a", 1, 4, LIMIT, DURATION)[1] == 3
    assert o.check("a", 1, 5, LIMIT, DURATION)[0] == 1  # refused: not at risk
    assert o.since == {"a": 4}
    snap = copy.deepcopy(o.live.state)
    o.crash()
    assert o.check("a", 2, 0, LIMIT, DURATION)[1] == 7 and o.since == {}
    assert snap["a"][0] == 3
    leaky = DurableOracle("leaky")
    assert leaky.check("a", 0, 1, LIMIT, DURATION)[1] == 9


def test_a_long_frame_replays_in_chunks_of_one_shape(monkeypatch):
    """A frame longer than REPLAY_ROWS is merged REPLAY_ROWS rows a call,
    the last call reaching back over the one before it, and the table
    holds what one merge of the whole frame leaves."""
    from gubernator_tpu.ops.engine import LocalEngine
    from gubernator_tpu.service import checkpoint as svc
    from gubernator_tpu.store import fps_from_slots
    from tests.test_durability import cols, unique_fps

    src = LocalEngine(capacity=1 << 14, write_mode="xla")
    fps = unique_fps(np.random.default_rng(5), 2500)
    now = int(time.time() * 1e3)  # the merge drops what its own clock finds expired
    batch = cols(fps, hits=3)
    batch.created_at[:] = now
    src.check_columns(batch, now_ms=now)
    slots, fp, _ = decode_live_slots(np.asarray(src.table.rows), now)
    assert fp.shape[0] == 2500
    calls = []
    monkeypatch.setattr(svc, "REPLAY_ROWS", 1024)
    dst = LocalEngine(capacity=1 << 14, write_mode="xla")
    merge = dst.merge_rows
    monkeypatch.setattr(
        dst, "merge_rows", lambda f, s, **kw: calls.append(len(f)) or merge(f, s, **kw)
    )
    assert svc.CheckpointManager._replay(dst, fps_from_slots(slots), slots, None) == 0
    assert calls == [1024, 1024, 1024]  # 2,500 rows: the third reaches back
    whole = LocalEngine(capacity=1 << 14, write_mode="xla")
    whole.merge_rows(fp, slots)
    want = decode_live_slots(np.asarray(whole.table.rows), now)[0]
    got = decode_live_slots(np.asarray(dst.table.rows), now)[0]
    assert len(got) == 2500
    assert sorted(r.tobytes() for r in got) == sorted(r.tobytes() for r in want)
    calls.clear()
    svc.CheckpointManager._replay(dst, fp[:700], slots[:700], None)
    assert calls == [700]
