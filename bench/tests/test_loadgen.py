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
        due, offsets, ranks = tr._plan[:3]
        plans.append((np.diff(offsets), ranks, due))
    (s1, r1, d1), (s2, r2, d2) = plans
    assert not np.array_equal(s1, s2)
    assert np.array_equal(np.sort(s1), np.sort(s2))
    assert np.array_equal(np.sort(r1), np.sort(r2))
    assert len(d1) == len(d2) == 400 and abs(d1[-1] - 2.0) < 1e-9


# --------------------------------------------------------- a behavior mix

MIX = {"mix": [[0.9, []], [0.1, ["GLOBAL"]]]}


def test_an_open_loop_without_a_mix_sends_the_bytes_it_always_sent():
    """What the plan held at e445c33, before a traffic file could carry a
    `behavior`: the same arrivals, sizes and ranks, and one body after
    another out of one buffer."""
    import hashlib

    tr = loadgen.Traffic(OPEN, KEYSPEC, 2654435761, 2.0)
    tr.prepare()
    due, offsets = tr._plan[:2]
    assert hashlib.sha256(due.tobytes() + offsets.tobytes()).hexdigest() == (
        "046e847f939abc1e5766ae698faf1e0b951083524217060ea74fea0eee4c36e8")
    bodies = b"".join(tr._planned_body(i) for i in range(len(due)))
    assert hashlib.sha256(bodies).hexdigest() == (
        "7217045fff3dd41e695f6231a2e04e71fcaba2e6e1c166c14e8121611ce4f52c")
    assert set(tr._plan[4]) == {0}


def test_every_seed_offers_the_same_mix_of_behaviors():
    plans = []
    for seed in (1, 2_999_999_999, 40_503):
        tr = loadgen.Traffic(dict(OPEN, behavior=MIX), KEYSPEC, seed, 20.0)
        tr.prepare()
        due, offsets, ranks, _blobs, behaviors, _first = tr._plan
        plans.append((np.asarray(behaviors), np.diff(offsets), ranks))
        # the mix leaves arrivals, sizes and ranks what they are without it
        plain = loadgen.Traffic(OPEN, KEYSPEC, seed, 20.0)
        plain.prepare()
        assert all(np.array_equal(a, b) for a, b in zip(plain._plan[:3], tr._plan[:3]))
        # and every RPC's bytes carry its own behavior in every row
        for i in (0, 7, len(due) - 1):
            want = tr._body(ranks[offsets[i] : offsets[i + 1]], behaviors[i])
            assert tr._planned_body(i) == want
    b0 = plans[0][0]
    assert set(b0.tolist()) == {0, wirefmt.GLOBAL}
    for b, sizes, ranks in plans[1:]:
        assert not np.array_equal(b, b0)
        assert np.array_equal(np.sort(b), np.sort(b0))
        assert np.array_equal(np.sort(sizes), np.sort(plans[0][1]))
        assert np.array_equal(np.sort(ranks), np.sort(plans[0][2]))
    # 4,000 RPCs at a share of 0.1: three standard deviations are 57
    assert abs(int((b0 == wirefmt.GLOBAL).sum()) - 400) < 57


def test_a_closed_loop_takes_its_behaviors_from_one_cycle_and_its_ledger_keeps_them():
    class PromptDoor:
        timeout_s = 5.0

        def __init__(self):
            self.bodies = []

        async def start(self, body: bytes):
            self.bodies.append(body)
            return wirefmt.response_bytes([(0, 100, 99, 1)] * 5)

    spec = {"loop": "closed", "inflight": 3, "items_per_rpc": {"fixed": 5},
            "keys": {"dist": "uniform"}, "behavior": MIX}
    cycles = []
    for seed in (1, 2_999_999_999, 40_503):
        tr = loadgen.Traffic(spec, KEYSPEC, seed, 0.2)
        tr.prepare()
        cycles.append(tr._cycle)
        door = PromptDoor()
        led = asyncio.run(tr.run(door))
        n = len(led.idx)
        assert n > loadgen.BEHAVIOR_CYCLE / 10
        assert led.behavior == [tr._cycle[i % loadgen.BEHAVIOR_CYCLE] for i in range(n)]
        for body, b in zip(door.bodies, led.behavior):
            assert body.count(b"\x38\x02") == (5 if b else 0)
        # the keys the seed draws are the ones it draws without a mix
        plain = loadgen.Traffic({k: v for k, v in spec.items() if k != "behavior"},
                                KEYSPEC, seed, 0.2)
        plain.prepare()
        led2 = asyncio.run(plain.run(PromptDoor()))
        m = min(n, len(led2.idx))
        assert all(np.array_equal(a, b) for a, b in zip(led.idx[:m], led2.idx[:m]))
    assert sorted(cycles[0]) == sorted(cycles[1]) == sorted(cycles[2])
    assert cycles[0] != cycles[1]
    assert abs(cycles[0].count(wirefmt.GLOBAL) - 100) < 29


def test_without_a_mix_every_rpc_carries_the_keyspaces_behavior():
    tr = loadgen.Traffic(OPEN, dict(KEYSPEC, behavior=["GLOBAL"]), 5, 1.0)
    tr.prepare()
    assert set(tr._plan[4]) == {wirefmt.GLOBAL}
    assert tr._planned_body(0).count(b"\x38\x02") == int(np.diff(tr._plan[1])[0])
