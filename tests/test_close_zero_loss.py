"""`Daemon.close()` loses nothing it has admitted.

Called with RPCs queued in the batcher (their window still open) and others
in flight in the runner's chain (their fetch held up), it answers every one
of them, none with an error; the answers to the one key they all hit count
down without a gap or a repeat, so no admitted hit is lost or granted twice;
and what the engine keeps beside the table is settled: a tiered engine holds
no undrained sidecar and has lost no row, a durable daemon's close wrote
every one of those hits, and a daemon started from its files holds them.

One dispatch protocol (`EngineRunner._run_chain`) means this holds on every
engine a cell runs: the local engine, the four-device mesh as a TPU resolves
it, a table with a shadow behind it, and the checkpoint plane armed.
"""

import asyncio
import time

import pytest

from gubernator_tpu import native
from gubernator_tpu.ops import engine as engine_mod
from gubernator_tpu.ops.engine import ms_now
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.daemon import Daemon

from tests.cluster import wait_for
from tests.test_dispatch_encode import _conf, _engine, async_test, body

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)

NOW = ms_now()
LIMIT = 1000
HELD_S = 0.15  # how long a dispatch's fetch is held up
WAVE = 9  # RPCs a wave: more copies of the hot key than exact passes


def item(key: str, hits: int = 1) -> "pb.RateLimitReq":
    return pb.RateLimitReq(
        name="close", unique_key=key, hits=hits, limit=LIMIT,
        duration=3_600_000, created_at=NOW,
    )


def counts_down(remaining: list, start: int) -> bool:
    """The answers to one key, each of one hit, in any order: every distinct
    value is `start` less the number of answers at or above it. Copies of a
    key decided together (the planner's aggregate past its exact passes, the
    mesh's in-trace fold) share their aggregate's answer, and the rule is
    the same: a hit lost leaves a value short of its count, a hit granted
    twice a value past it."""
    seen = 0
    for value in sorted(set(remaining), reverse=True):
        seen += remaining.count(value)
        if value != start - seen:
            return False
    return seen == len(remaining)


def test_the_rule_tells_a_lost_hit_and_a_hit_granted_twice():
    assert counts_down([9, 8, 7, 6], 10) and counts_down([6, 8, 8, 6, 5], 10)
    assert not counts_down([9, 8, 6], 10)  # a gap: a hit answered nobody
    assert not counts_down([9, 8, 8], 10)  # a repeat: two callers, one hit
    assert not counts_down([8, 7], 10)


@pytest.mark.parametrize("kind", ["local", "sharded4", "tiered", "durable"])
@async_test
async def test_close_answers_what_is_queued_and_what_is_in_flight(
    kind, monkeypatch, tmp_path
):
    def conf():  # a 0.2 s batch window: what is enqueued in it stays queued
        c = _conf(kind, tmp_path)
        if kind == "durable":
            c.checkpoint_interval_ms = 60_000.0  # no epoch but the close's
        return c

    d = await Daemon.spawn(conf(), engine=_engine(kind))
    closed = False
    try:
        assert d.engine.supports_wire_ingress
        start = LIMIT
        if kind == "tiered":
            # the hot key goes to the shadow first: the wave in flight at the
            # close brings it back ahead of its launch, which leaves a sidecar
            (first,) = pb.GetRateLimitsResp.FromString(
                await d.get_rate_limits_raw(body([item("hot")]))
            ).responses
            assert first.remaining == LIMIT - 1
            start -= 1
            await asyncio.sleep(0.005)
            await d.tier.sweep_once()  # or the daemon's own, if it came first
            assert d.tier.pipeline()["demoted_idle"] == 1

        held = []
        finish = engine_mod.finish_check_columns

        def held_up(*a, **k):
            held.append(time.perf_counter())
            time.sleep(HELD_S)
            return finish(*a, **k)

        monkeypatch.setattr(engine_mod, "finish_check_columns", held_up)

        def wave(tag):
            return [
                asyncio.ensure_future(d.get_rate_limits_raw(
                    body([item("hot"), item(f"{tag}{i}")])
                ))
                for i in range(WAVE)
            ]

        flying = wave("a")
        await wait_for(lambda: asyncio.sleep(0, bool(held)), interval_s=0.002)
        queued = wave("b")
        await asyncio.sleep(0.01)  # enqueued; their window (0.2 s) has most of its time to go
        seen = d.batcher.debug()
        assert seen["inflight"] == 1 and seen["pending_requests"] == WAVE
        assert not any(t.done() for t in flying + queued)
        await d.close()
        closed = True
        assert all(t.done() for t in flying + queued)  # close waited for them
        got = [pb.GetRateLimitsResp.FromString(t.result()).responses
               for t in flying + queued]
        assert all(len(rs) == 2 and not rs[0].error and not rs[1].error for rs in got)
        assert all(rs[1].remaining == LIMIT - 1 for rs in got)  # its own key
        hot = [rs[0].remaining for rs in got]
        assert counts_down(hot, start), sorted(hot, reverse=True)
        assert min(hot) == start - 2 * WAVE
        assert d.batcher.debug()["inflight"] == 0
        assert d.engine.stats.dropped == 0
        if kind == "tiered":
            assert d.engine._sidecars == []
            tier = d.tier.pipeline()
            assert tier["promoted"] >= 1 and tier["lost"] == 0
        if kind == "durable":
            monkeypatch.undo()
            again = await Daemon.spawn(conf())
            try:
                assert again.checkpointer.restored != "none"
                back = pb.GetRateLimitsResp.FromString(
                    await again.get_rate_limits_raw(body(
                        [item("hot", 0)] + [item(f"{t}{i}", 0) for t in "ab"
                                            for i in range(WAVE)]
                    ))
                ).responses
                assert back[0].remaining == LIMIT - 2 * WAVE
                assert [r.remaining for r in back[1:]] == [LIMIT - 1] * (2 * WAVE)
            finally:
                await again.close()
    finally:
        if not closed:
            await d.close()
