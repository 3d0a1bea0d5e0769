"""A plain RPC's answer leaves its dispatch encoded (service/batcher.py:
`Batcher.check(..., encoded=True)`, the dispatch's `encode` link): the fetch
thread that holds a chunk's answer writes the response bytes of every plain
RPC in it with one native call, and the crossing back hands each caller its
bytes. No second door-pool hop, the same bytes and the same counters.

Contract: a chunk that mixes plain RPCs with a general-path entry answers
each as the pb path does; `daemon.dispatch_encoded_rpcs` counts the plain
RPCs so answered; the OVER_LIMIT counter's total is the pb path's; a
cancelled caller is skipped; what the encode link raises reaches every
caller of the chunk; an RPC shed before its dispatch is encoded where it is
shed."""

import asyncio
import functools

import pytest

from gubernator_tpu import native
from gubernator_tpu.config import BehaviorConfig
from gubernator_tpu.ops.batch import ERR_OVERLOAD, ERROR_STRINGS
from gubernator_tpu.ops.engine import LocalEngine, ms_now
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service import batcher as batcher_mod
from gubernator_tpu.service.daemon import Daemon

from tests.cluster import daemon_config

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)

NOW = ms_now()
WINDOW_S = 0.2  # every RPC sent at once rides one chunk: see _spawn


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(asyncio.wait_for(fn(*a, **k), 120))  # nothing may hang

    return wrapper


def req(tag: str, i: int, **kw) -> "pb.RateLimitReq":
    # an hour: NOW is as old as this module's import, and a bucket must not
    # have expired by the wall clock when the tier's sweep looks at it
    d = dict(name="de", unique_key=f"{tag}{i}", hits=2, limit=3,
             duration=3_600_000, created_at=NOW)
    d.update(kw)
    return pb.RateLimitReq(**d)


def body(items) -> bytes:
    return pb.GetRateLimitsReq(requests=items).SerializeToString()


def _engine(kind: str):
    if kind.startswith("sharded"):
        from gubernator_tpu.parallel import make_mesh
        from gubernator_tpu.parallel.global_sync import GlobalShardedEngine

        if kind == "sharded1":  # the mesh engine over one device
            return GlobalShardedEngine(make_mesh(1), capacity_per_shard=8192)
        # four devices as a TPU resolves them (cell 3): it takes the lanes
        return GlobalShardedEngine(
            make_mesh(4), capacity_per_shard=8192, route="device",
            dedup="device", wire="compact",
        )
    if kind == "store":  # a Store: the runner's serial path, one link
        from gubernator_tpu.store import RecordingStore

        return LocalEngine(capacity=8192, wire="compact", store=RecordingStore())
    return LocalEngine(capacity=8192, wire="compact")


def _conf(kind: str = "local", tmp_path=None, **behaviors):
    """The configuration of a daemon whose batch window stays open for
    WINDOW_S whatever the engine does, so that RPCs sent together are one
    chunk. `tiered` arms the tiering plane (a shadow behind the table: cell
    7) and `durable` the checkpoint plane (every dispatch marks its blocks:
    cell 5)."""
    conf = daemon_config(http_address="")
    conf.behaviors = BehaviorConfig(
        batch_wait_ms=WINDOW_S * 1e3, adaptive_batch=False,
        batch_timeout_ms=5000.0, **behaviors,
    )
    if kind == "tiered":
        conf.tier_enabled, conf.tier_idle_ms = True, 1.0
        conf.tier_shadow_bytes = 1 << 20
    if kind == "durable":
        conf.checkpoint_path = str(tmp_path / "base.npz")
        conf.checkpoint_interval_ms = 25.0
    return conf


async def _spawn(kind: str = "local", tmp_path=None, **behaviors) -> Daemon:
    return await Daemon.spawn(
        _conf(kind, tmp_path, **behaviors), engine=_engine(kind)
    )


async def _over_limit_count(d) -> float:
    await d.runner.live_count()  # behind the dispatches' stats on the engine thread
    return d.metrics.over_limit_counter._value.get()


def _fields(r):
    return (r.status, r.limit, r.remaining, r.reset_time, r.error)


# three RPCs a round: plain, general (an error row sends it there), plain.
# Two rounds over the same keys: hits 2 of limit 3, so round 2 is OVER_LIMIT.
ERROR_ROW = pb.RateLimitReq(name="de", hits=1, limit=1)  # no unique_key


def _round():
    return [
        [req("a", i) for i in range(5)],
        [req("b", 0), ERROR_ROW, req("b", 1)],
        [req("c", i, algorithm=i % 2) for i in range(3)],
    ]


@pytest.mark.parametrize(
    "kind", ["local", "sharded1", "store", "sharded4", "tiered", "durable"]
)
@async_test
async def test_mixed_chunk_answers_as_the_pb_path(kind, monkeypatch, tmp_path):
    """Whatever engine a cell runs behind the one dispatch protocol. The
    reference daemon has neither plane armed: both are exact."""
    monkeypatch.setattr(batcher_mod, "ms_now", lambda: NOW + 9)
    d = await _spawn(kind, tmp_path)
    d_pb = await Daemon.spawn(daemon_config(http_address=""), engine=_engine(kind))
    try:
        assert d.engine.supports_wire_ingress == (kind != "sharded1")
        for rnd in range(2):
            if rnd and kind == "tiered":
                # round 1's rows, idle for a millisecond, go to the shadow
                # (by this sweep, or by the daemon's own if it came first):
                # round 2 brings every key back ahead of its launch
                await asyncio.sleep(0.005)
                await d.tier.sweep_once()
                assert d.tier.pipeline()["demoted_idle"] == 10
            n0 = d.batcher.dispatches
            got = await asyncio.gather(
                *(d.get_rate_limits_raw(body(items)) for items in _round())
            )
            assert d.batcher.dispatches == n0 + 1  # one chunk, three entries
            for items, raw in zip(_round(), got):
                want = await d_pb.get_rate_limits(list(items))
                answers = pb.GetRateLimitsResp.FromString(raw).responses
                assert [_fields(r) for r in answers] == [_fields(r) for r in want]
                if rnd and len(items) != 3:  # a plain RPC, refused
                    assert {r.status for r in answers} == {pb.OVER_LIMIT}
                    assert answers[0].metadata["retry_after_ms"] == str(3_600_000 - 9)
        assert (d.raw_rpcs, d.plain_rpcs) == (6, 4)
        pipe = d.debug_pipeline()
        assert pipe["daemon"]["dispatch_encoded_rpcs"] == 4
        if kind == "tiered":
            assert pipe["tier"]["promoted"] == 10 and pipe["tier"]["lost"] == 0
        if kind == "durable":
            assert pipe["engine"]["ckpt_blk"] and pipe["checkpoint"] is not None
        over = await _over_limit_count(d)
        assert over == await _over_limit_count(d_pb) and over > 0
    finally:
        await d.close()
        await d_pb.close()


class CountingDoor:
    """The door pool, counting the jobs it is given."""

    def __init__(self, pool):
        self.pool, self.jobs = pool, 0

    def submit(self, fn, *a, **k):
        self.jobs += 1
        return self.pool.submit(fn, *a, **k)

    def shutdown(self, *a, **k):
        return self.pool.shutdown(*a, **k)


@async_test
async def test_a_big_plain_rpc_crosses_the_door_pool_once():
    """600 rows are 4,800 B of answer and more of request: the parse is
    the RPC's one job on the door pool; a general RPC of that size has two."""
    d = await _spawn()
    try:
        d._door = door = CountingDoor(d._door)
        big = [req("big", i) for i in range(600)]
        assert len(body(big)) >= d.DOOR_OFFLOAD_BYTES <= 600 * 8
        out = await d.get_rate_limits_raw(body(big))
        assert len(pb.GetRateLimitsResp.FromString(out).responses) == 600
        assert (door.jobs, d.plain_rpcs) == (1, 1)
        await d.get_rate_limits_raw(body(big + [ERROR_ROW]))
        assert (door.jobs, d.plain_rpcs) == (3, 1)
    finally:
        await d.close()


@async_test
async def test_a_cancelled_caller_is_skipped_and_the_others_answered():
    d = await _spawn()
    try:
        first, gone, last = (
            asyncio.ensure_future(d.get_rate_limits_raw(body([req(t, 0)])))
            for t in "xyz"
        )
        await asyncio.sleep(WINDOW_S / 5)  # all three enqueued, none dispatched
        assert d.batcher.debug()["pending_requests"] == 3
        gone.cancel()
        for task in (first, last):
            (answer,) = pb.GetRateLimitsResp.FromString(await task).responses
            assert (answer.status, answer.remaining) == (pb.UNDER_LIMIT, 1)
        assert gone.cancelled()
        assert d.batcher.dispatches == 1 and d.batcher.encoded_requests == 2
    finally:
        await d.close()


@async_test
async def test_what_the_encode_link_raises_reaches_every_caller(monkeypatch):
    """Plain callers and the general-path entry of the same chunk alike,
    as an exception of their RPC; the next chunk is served."""
    d = await _spawn()
    try:
        def broken(*a):
            raise RuntimeError("encode link down")

        with monkeypatch.context() as m:
            m.setattr(batcher_mod, "encode_responses_many", broken)
            got = await asyncio.gather(
                *(d.get_rate_limits_raw(body(items)) for items in _round()),
                return_exceptions=True,
            )
        assert [type(g) for g in got] == [RuntimeError] * 3
        assert all("encode link down" in str(g) for g in got)
        assert d.batcher.dispatches == 1 and d.batcher.debug()["inflight"] == 0
        out = await d.get_rate_limits_raw(body([req("after", 0)]))
        assert len(pb.GetRateLimitsResp.FromString(out).responses) == 1
    finally:
        await d.close()


@async_test
async def test_an_rpc_shed_in_the_queue_is_answered_in_bytes():
    """The overload plane's answer never reaches a dispatch: a plain RPC
    whose deadline passes while the window is open gets its overload rows
    encoded where it is shed, and is not counted as dispatch-encoded."""
    d = await _spawn(overload_deadline_ms=WINDOW_S * 1e3 / 10)
    try:
        out = await d.get_rate_limits_raw(body([req("s", 0), req("s", 1)]))
        answers = pb.GetRateLimitsResp.FromString(out).responses
        assert [(r.status, r.error) for r in answers] == [
            (pb.OVER_LIMIT, ERROR_STRINGS[ERR_OVERLOAD])
        ] * 2
        assert "retry_after_ms" in answers[0].metadata
        assert d.plain_rpcs == 1 and d.batcher.shed_rows["deadline"] == 2
        assert d.debug_pipeline()["daemon"]["dispatch_encoded_rpcs"] == 0
        assert await _over_limit_count(d) == 2
    finally:
        await d.close()


@async_test
async def test_many_dispatches_in_flight_hand_each_caller_its_own_bytes():
    """Four dispatches in flight on the fetch pool, the interpreter switching
    threads every 10 µs: every one of 2 × 150 RPCs gets the answer to its own
    rows (its limit is its mark), none is lost, and every one is counted."""
    import sys

    conf = daemon_config(http_address="")  # the adaptive window, as served
    conf.behaviors.coalesce_limit = 256  # a wave is a dozen chunks
    # 3,075 keys: a table in which no bucket of eight overflows
    engine = LocalEngine(capacity=1 << 17, wire="compact")
    d = await Daemon.spawn(conf, engine=engine)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for wave, status in enumerate((pb.UNDER_LIMIT, pb.OVER_LIMIT)):
            rpcs = [
                [req(f"m{k}-", i, limit=100 + k, hits=100 + k) for i in range(1 + k % 40)]
                for k in range(150)
            ]
            got = await asyncio.gather(
                *(d.get_rate_limits_raw(body(items)) for items in rpcs)
            )
            for k, (items, raw) in enumerate(zip(rpcs, got)):
                answers = pb.GetRateLimitsResp.FromString(raw).responses
                assert [(r.limit, r.status) for r in answers] == (
                    [(100 + k, status)] * len(items)
                ), (wave, k)
        assert d.batcher.encoded_requests == d.plain_rpcs == 300
        assert d.batcher.dispatches >= 24 and d.batcher.debug()["inflight"] == 0
    finally:
        sys.setswitchinterval(interval)
        await d.close()
