"""The generator: latency is timed from the due time, so a stalled server's
backlog counts; every seed offers the same work in another order."""

import asyncio

import numpy as np

import harness
import loadgen
import wirefmt

KEYSPEC = {"keys": 1000, "limit": 100, "duration_ms": 3_600_000, "hits": 1}
OPEN = {"loop": "open", "rate_rpc_per_s": 200,
        "items_per_rpc": {"mix": [[0.6, 1, 1], [0.3, 2, 10], [0.1, 11, 100]]},
        "keys": {"dist": "zipf", "theta": 0.99}, "warm_seconds": 0}


class StalledDoor:
    """Answers at once, except that nothing is answered before `until`."""

    timeout_s = 5.0

    def __init__(self, until: float):
        self.until = until

    def start(self, body: bytes):
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        n = body.count(b"\x0a\x04bulk")
        data = wirefmt.response_bytes([(0, 100, 99, 1)] * n)
        loop.call_at(max(loop.time(), self.until), fut.set_result, data)
        return fut


def _run(door_factory, seconds=1.0, seed=5):
    async def go():
        tr = loadgen.Traffic(OPEN, KEYSPEC, seed, seconds)
        tr.prepare()
        return await tr.run(door_factory(asyncio.get_running_loop().time()))

    return asyncio.run(go())


def test_due_time_latency_counts_a_stalled_servers_backlog():
    led = _run(lambda now: StalledDoor(now + 0.6))
    gen = harness.generator_report(led)
    vals = {"p50_ms": harness.end_to_end_values(led, gen)["p50_ms"], "p99_ms": gen["rpc_p99_ms"]}
    # RPCs due at t in [0, 0.6) wait 0.6 - t: 60% of the window's RPCs, so
    # the median waits about 0.1 s and the tail nearly the whole stall.
    # Timed from the send, none of this would show: sends stay on schedule.
    assert 50 < vals["p50_ms"] < 200
    assert vals["p99_ms"] > 500
    assert harness.generator_report(led)["late_p99_ms"] < 50


def test_a_prompt_server_reads_near_zero():
    led = _run(lambda now: StalledDoor(now))
    assert harness.generator_report(led)["rpc_p99_ms"] < 50


def test_a_refused_rpc_is_given_the_windows_length():
    spec = dict(OPEN, max_outstanding=20)

    async def go():
        tr = loadgen.Traffic(spec, KEYSPEC, 5, 1.0)
        tr.prepare()
        return await tr.run(StalledDoor(asyncio.get_running_loop().time() + 0.9))

    led = asyncio.run(go())
    gen = harness.generator_report(led)
    assert gen["rpcs_refused_by_generator"] > 50
    assert gen["rpc_p99_ms"] == 1000.0


def test_every_seed_offers_the_same_work_in_another_order():
    plans = []
    for seed in (1, 2_999_999_999):
        tr = loadgen.Traffic(OPEN, KEYSPEC, seed, 2.0)
        tr.prepare()
        due, offsets, ranks, _blob, _w = tr._plan
        plans.append((np.diff(offsets), ranks, due))
    (s1, r1, d1), (s2, r2, d2) = plans
    assert not np.array_equal(s1, s2)
    assert np.array_equal(np.sort(s1), np.sort(s2))
    assert np.array_equal(np.sort(r1), np.sort(r2))
    assert len(d1) == len(d2) == 400 and abs(d1[-1] - 2.0) < 1e-9
