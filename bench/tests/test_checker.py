"""The counters read back after the window: exact, and off by one either way."""

import numpy as np
import pytest

import checker
import loadgen
import wirefmt

KEYSPEC = {"keys": 4000, "limit": 100, "duration_ms": 3_600_000, "hits": 1}
T0 = 1_790_000_000_000


def _world(rng):
    """A table that behaved: 4,000 keys filled by 4 RPCs, then hit a
    seeded number of times; every 37th key evicted and installed anew."""
    counts = 1 + rng.integers(0, 140, size=4000)
    created = T0 + np.arange(4) * 7
    idx = np.arange(0, 4000, 3)
    n = counts[idx]
    born = created[idx // checker.FILL_RPC_ITEMS] + KEYSPEC["duration_ms"]
    remaining = np.maximum(100 - n, 0)
    status = (n > 100).astype(np.int64)
    reset = born.copy()
    anew = idx % 37 == 0
    remaining[anew] = np.minimum(remaining[anew] + 3, 100)
    status[anew] = 0
    reset[anew] += 5_000
    ans = wirefmt.Answers(np.asarray([len(idx)]), np.asarray([0]), status,
                          np.full(len(idx), 100), remaining, reset)
    return idx, ans, counts, created


def _judge(idx, ans, counts, created):
    return checker.judge_counters(idx, ans, counts, created, KEYSPEC, T0 + 60_000)


def test_a_sound_table_is_exact():
    idx, ans, counts, created = _world(np.random.default_rng(1))
    out = _judge(idx, ans, counts, created)
    assert (out["below_expected"], out["above_expected_not_evicted"],
            out["status_wrong"], out["fields_wrong"]) == (0, 0, 0, 0)
    assert out["evicted"] == int((idx % 37 == 0).sum())


def test_one_counter_off_by_one_is_seen_either_way():
    idx, ans, counts, created = _world(np.random.default_rng(2))
    j = int(np.flatnonzero((idx % 37 != 0) & (counts[idx] < 90))[0])
    ans.remaining[j] -= 1  # a hit counted twice
    assert _judge(idx, ans, counts, created)["below_expected"] == 1
    ans.remaining[j] += 2  # a hit lost
    out = _judge(idx, ans, counts, created)
    assert (out["below_expected"], out["above_expected_not_evicted"]) == (0, 1)


def test_a_key_past_its_limit_that_holds_some_is_above_and_named():
    idx, ans, counts, created = _world(np.random.default_rng(5))
    j = int(np.flatnonzero((idx % 37 != 0) & (counts[idx] > 100))[0])
    ans.remaining[j], ans.status[j] = 3, 0  # an aggregate of 5 refused at 3 left
    out = _judge(idx, ans, counts, created)
    assert (out["above_expected_not_evicted"], out["above_on_keys_past_limit"]) == (1, 1)
    assert "an aggregate refused whole" in out["examples"][0]


def test_an_evicted_key_may_hold_more_but_never_less():
    idx, ans, counts, created = _world(np.random.default_rng(3))
    j = int(np.flatnonzero((idx % 37 == 0) & (counts[idx] < 90))[0])
    ans.remaining[j] = 100 - counts[idx[j]] - 1
    assert _judge(idx, ans, counts, created)["below_expected"] == 1


def test_over_limit_must_stick_once_the_limit_is_passed():
    idx, ans, counts, created = _world(np.random.default_rng(4))
    j = int(np.flatnonzero((idx % 37 != 0) & (counts[idx] > 100))[0])
    ans.status[j] = 0
    assert _judge(idx, ans, counts, created)["status_wrong"] == 1


def test_eviction_allowance_is_three_times_the_servers_own_count():
    # PR 23's chip runs: about 6,200 of 202,000 sampled keys evicted against
    # evicted_live_total of 2.96% of the keys (1.03 to 1.06 times n*p)
    assert 6281 < checker.eviction_allowance(202_000, 0.0296) < 3.1 * 202_000 * 0.0296
    assert checker.eviction_allowance(1000, 0.0) == 10


def test_window_invariants_catch_a_replayed_hit():
    idx = np.arange(10)
    counts = np.full(10, 2)  # the generator sent each key 2 checks in all
    rows = [(0, 100, 98, T0 + 3_600_000)] * 9 + [(0, 100, 97, T0 + 3_600_000)]
    led = loadgen.Ledger(0, 1)
    led.idx, led.resp = [idx], [wirefmt.response_bytes(rows)]
    checker.settle(led)
    out = checker.window_invariants([led], counts, KEYSPEC, T0, T0 + 10)
    assert out["violations"] == 1 and out["answers"] == 10


def test_an_item_answered_with_an_error_fails_its_rpc():
    led = loadgen.Ledger(0, 1)
    good = wirefmt.response_bytes([(0, 100, 99, T0)])
    shed = b"\x0a\x0c\x08\x01\x10\x64\x2a\x06" + b"shed!!"
    led.idx = [np.arange(1), np.arange(1, 2)]
    led.resp = [good, shed]
    checker.settle(led)
    assert led.resp == [good, None] and led.errors == ["item error: shed!!"]
    assert led.answers.n_items.tolist() == [1]


# ------------------------------------------------- a leaky keyspace's rules

LEAKY = {"keys": 4000, "algorithm": "leaky", "limit": 100, "duration_ms": 134_000_000,
         "hits": 1}
PER_TOKEN = 1_340_000  # int(134,000,000 / 100)
T_PEEK = T0 + 60_000


def _leaky_world(rng):
    """A leaky table that behaved: no key evicted, no leak landed, every
    peek UNDER_LIMIT with reset_time = the peek's stamp + what is taken."""
    counts = 1 + rng.integers(0, 140, size=4000)
    idx = np.arange(0, 4000, 3)
    remaining = np.maximum(100 - counts[idx], 0)
    ans = wirefmt.Answers(np.asarray([len(idx)]), np.asarray([0]),
                          np.zeros(len(idx), dtype=np.int64), np.full(len(idx), 100),
                          remaining, T_PEEK + (100 - remaining) * PER_TOKEN)
    return idx, ans, counts


def _set_remaining(ans, j, remaining):
    ans.remaining[j] = remaining
    ans.reset_time[j] = T_PEEK + (100 - remaining) * PER_TOKEN


def _judge_leaky(idx, ans, counts):
    return checker.judge_counters(idx, ans, counts, None, LEAKY, T_PEEK)


def _evicted_ok(out, evicted_live_total):
    bound = checker.eviction_bound(LEAKY, out["sample"], evicted_live_total, LEAKY["keys"])
    return checker.Compared("counters_evicted_in_sample", out["evicted"], bound).ok


def test_a_sound_leaky_table_is_exact():
    idx, ans, counts = _leaky_world(np.random.default_rng(11))
    out = _judge_leaky(idx, ans, counts)
    assert (out["below_expected"], out["evicted"], out["status_wrong"],
            out["fields_wrong"], out["above_expected_not_evicted"]) == (0, 0, 0, 0, 0)
    assert _evicted_ok(out, 0)


def test_leaky_one_hit_lost_fails_when_the_server_counted_no_eviction():
    idx, ans, counts = _leaky_world(np.random.default_rng(12))
    j = int(np.flatnonzero(counts[idx] < 90)[0])
    _set_remaining(ans, j, ans.remaining[j] + 1)
    out = _judge_leaky(idx, ans, counts)
    assert (out["below_expected"], out["evicted"], out["fields_wrong"]) == (0, 1, 0)
    assert not _evicted_ok(out, 0)
    assert "a hit lost, or the key evicted live" in out["examples"][0]


def test_leaky_one_hit_counted_twice_is_below():
    idx, ans, counts = _leaky_world(np.random.default_rng(13))
    j = int(np.flatnonzero(counts[idx] < 90)[0])
    _set_remaining(ans, j, ans.remaining[j] - 1)
    out = _judge_leaky(idx, ans, counts)
    assert (out["below_expected"], out["evicted"], out["fields_wrong"]) == (1, 0, 0)


@pytest.mark.parametrize("evicted_live_total,ok", [(0, False), (1, True)])
def test_leaky_a_wiped_key_passes_only_if_the_server_counted_it(evicted_live_total, ok):
    idx, ans, counts = _leaky_world(np.random.default_rng(14))
    j = int(np.flatnonzero(counts[idx] > 20)[0])
    _set_remaining(ans, j, 99)  # installed anew by the last check it got
    out = _judge_leaky(idx, ans, counts)
    assert (out["below_expected"], out["evicted"]) == (0, 1)
    assert _evicted_ok(out, evicted_live_total) is ok


def test_leaky_eviction_bound_is_the_servers_count_capped_by_the_allowance():
    assert checker.eviction_bound(LEAKY, 1000, 0, 4000) == 0
    assert checker.eviction_bound(LEAKY, 1000, 7, 4000) == 7
    assert checker.eviction_bound(LEAKY, 1000, 400, 4000) == checker.eviction_allowance(1000, 0.1)
    assert checker.eviction_bound(KEYSPEC, 1000, 0, 4000) == 10  # token: as it was


def test_leaky_reset_time_and_peek_status_are_held_exactly():
    idx, ans, counts = _leaky_world(np.random.default_rng(15))
    ans.reset_time[3] += 1
    ans.status[5] = 1  # a peek is never over the limit
    out = _judge_leaky(idx, ans, counts)
    assert (out["fields_wrong"], out["status_wrong"]) == (1, 1)


@pytest.mark.parametrize("limit,duration_ms,refused", [
    (100, 134_000_000, False),   # 1,340 s a token
    (100, 114_000_000, True),    # 1,140 s: a leak could land inside a run
    (10, 60_000, True),          # the scripted leaky keys' own: 6 s a token
])
def test_a_leaky_keyspace_that_could_leak_inside_a_run_is_refused(limit, duration_ms, refused):
    import harness
    from doors import BenchFailure

    keyspec = dict(LEAKY, limit=limit, duration_ms=duration_ms)
    spec = {"config": {"keyspace": keyspec}}
    if refused:
        with pytest.raises(BenchFailure, match="leaks a token every"):
            harness.Session(spec, 1)  # before any server is started
    else:
        assert harness.Session(spec, 1).server is None
    harness.Session({"config": {"keyspace": dict(keyspec, algorithm="token")}}, 1)


def test_leaky_window_invariants_hold_reset_time_to_the_answers_own_stamp():
    idx = np.arange(4)
    counts = np.full(4, 3)
    stamp = T0 + 5
    rows = [(0, 100, 98, stamp + 2 * PER_TOKEN), (0, 100, 97, stamp + 3 * PER_TOKEN),
            (0, 100, 97, stamp + 2 * PER_TOKEN),       # a token short
            (0, 100, 98, stamp + 134_000_000)]         # a token bucket's reset_time
    led = loadgen.Ledger(0, 1)
    led.idx, led.resp = [idx], [wirefmt.response_bytes(rows)]
    checker.settle(led)
    out = checker.window_invariants([led], counts, LEAKY, T0, T0 + 10)
    assert out["violations"] == 2 and out["answers"] == 4


class _OracleDoor:
    """Answers a fill RPC as the plain oracle of `algorithm` would."""

    def __init__(self, algorithm):
        self.algorithm, self.oracle = algorithm, checker.ORACLES[algorithm]

    async def check_raw(self, body: bytes) -> bytes:
        from gubernator_tpu.proto import gubernator_pb2 as pb

        rows = []
        for r in pb.GetRateLimitsReq.FromString(body).requests:
            assert r.algorithm == self.algorithm
            status, remaining, reset = self.oracle().check(
                0, r.created_at, r.hits, r.limit, r.duration)
            rows.append((status, r.limit, remaining, reset))
        return wirefmt.response_bytes(rows)


@pytest.mark.parametrize("keyspec", [KEYSPEC, LEAKY], ids=["token", "leaky"])
def test_fill_expects_the_first_answer_of_the_keyspaces_own_oracle(keyspec):
    import asyncio

    algorithm = wirefmt.keyspec_algorithm(keyspec)
    out = asyncio.run(checker.fill(_OracleDoor(algorithm), 7, keyspec))
    assert (out["mismatches"], out["byte_identical_rpcs"], out["rpcs"]) == (0, 4, 4)


def test_fill_of_a_leaky_keyspace_refuses_a_token_buckets_answer():
    import asyncio

    class TokenAnswers(_OracleDoor):
        async def check_raw(self, body):
            return await _OracleDoor.check_raw(self, body.replace(b"\x30\x01", b"\x30\x00"))

    out = asyncio.run(checker.fill(TokenAnswers(wirefmt.TOKEN), 7, LEAKY))
    assert out["mismatches"] == 4000 and "expected" in out["examples"][0]


@pytest.mark.parametrize("keyspec,items", [(KEYSPEC, 5600), (LEAKY, 6800)],
                         ids=["token", "leaky"])
def test_scenarios_add_the_leaky_script_at_the_keyspaces_own_limit(keyspec, items):
    groups = checker.fresh_scenarios(7, 200, T0, False, keyspec)
    assert sum(len(step) for steps in groups for step in steps) == items
    labels = [step[0][3].split("/")[0] for steps in groups for step in steps]
    assert ("leakspec" in labels) == (keyspec is LEAKY)
    if keyspec is LEAKY:
        steps = next(g for g in groups if g[0][0][3].startswith("leakspec"))
        # taken to the limit without a leak landing, then OVER_LIMIT, then a peek
        assert [step[0][2][:2] for step in steps] == [
            (0, 99), (0, 49), (0, 49), (0, 0), (1, 0), (0, 0)]
        per_step = [step[0][2][2] - T0 for step in steps]
        assert per_step[0] == PER_TOKEN and per_step[4] == 200_000 + 100 * PER_TOKEN


# --------------------------- what a keyspace without a behavior is sent (PR 46)

# sha256 over every item's bytes, limit, expectation and label of
# `fresh_scenarios(3_000_000_019, 3, T0, dup_aggregates, keyspec)` at e445c33
_SCENARIOS_BEFORE = {
    ("token", False): "9f45499ed98286fc6e8f0faeb19c76930219af8b04e2c8d9b9acc82b389cb79f",
    ("token", True): "ae2364a6660ec7252e71f62afa25aaed2e8a72a79aac00150cb738cff39725cb",
    ("leaky", False): "930912e7d9531d7d79b16588bbb4a84ec07906eda9c49030b8c6d403a90ac8c7",
    ("leaky", True): "67748ed4c8c60b649b27fe3649f1f94ffeb4254737383347e6986acc390ce5d0",
}


def _scenario_digest(groups) -> str:
    import hashlib

    return hashlib.sha256(repr(
        [[[(b.hex(), lim, e, lab) for b, lim, e, lab in step] for step in steps]
         for steps in groups]).encode()).hexdigest()


@pytest.mark.parametrize("algorithm,dup", list(_SCENARIOS_BEFORE))
def test_the_scripts_of_a_keyspace_without_a_behavior_are_what_they_were(algorithm, dup):
    keyspec = {"keys": 10, "hits": 1, "limit": 100, "duration_ms": 3_600_000}
    if algorithm == "leaky":
        keyspec.update(algorithm="leaky", duration_ms=134_000_000)
    for extra in ({}, {"script_algorithms": ["token", "leaky"]}, {"behavior": []}):
        groups = checker.fresh_scenarios(3_000_000_019, 3, T0, dup, {**keyspec, **extra})
        assert _scenario_digest(groups) == _SCENARIOS_BEFORE[algorithm, dup]
        assert checker.scripts_left_out({**keyspec, **extra}) == {}


def test_a_keyspace_may_keep_the_leaky_script_from_its_table():
    """`keyspace.script_algorithms` ["token"]: what lets a packed token
    layout be timed as itself; one leaky row would migrate it to `full`."""
    both = checker.fresh_scenarios(7, 200, T0, False, KEYSPEC)
    token = checker.fresh_scenarios(7, 200, T0, False, dict(KEYSPEC, script_algorithms=["token"]))
    labels = [steps[0][0][3].split("/")[0] for steps in token]
    assert labels == ["drain", "reset", "drainover", "peek", "wide", "dup"]
    assert [s for s in both if not s[0][0][3].startswith("leak/")] == token
    assert sum(len(step) for steps in token for step in steps) == 5600 - 6 * 200
    assert not any(b"\x30\x01" in item[0] for steps in token for step in steps for item in step)
    assert any(b"\x30\x01" in item[0] for steps in both for step in steps for item in step)
    assert list(checker.scripts_left_out(dict(KEYSPEC, script_algorithms=["token"]))) == ["leak"]


class _RecordingDoor(_OracleDoor):
    """Every RPC's bytes, in the order they were started, and how many were
    in flight at once."""

    def __init__(self, algorithm, remaining=None):
        super().__init__(algorithm)
        self.bodies, self.inflight, self.most = [], 0, 0

    async def check_raw(self, body: bytes) -> bytes:
        import asyncio

        self.bodies.append(body)
        self.inflight += 1
        self.most = max(self.most, self.inflight)
        await asyncio.sleep(0)
        self.inflight -= 1
        return await super().check_raw(body)


@pytest.mark.parametrize("keyspec", [KEYSPEC, LEAKY], ids=["token", "leaky"])
def test_fill_and_read_back_send_a_keyspace_without_a_behavior_what_they_sent(keyspec):
    """The bytes are `request_bytes` without a behavior (pinned to the
    parent's in test_wirefmt.py), one RPC a thousand keys, 64 in flight."""
    import asyncio

    algorithm = wirefmt.keyspec_algorithm(keyspec)
    limit, dur = keyspec["limit"], keyspec["duration_ms"]
    door = _RecordingDoor(algorithm)
    out = asyncio.run(checker.fill(door, 7, keyspec))
    assert out["mismatches"] == 0 and len(door.bodies) == 4 and door.most == 4
    for r, body in enumerate(sorted(door.bodies)):
        assert b"\x38" + wirefmt.varint(wirefmt.GLOBAL) not in body[:40]
    want = {wirefmt.request_bytes(wirefmt.key_ids(7, np.arange(r * 1000, r * 1000 + 1000)), 1,
                                  limit, dur, created_at=int(out["created"][r]),
                                  algorithm=algorithm) for r in range(4)}
    assert set(door.bodies) == want

    door = _RecordingDoor(algorithm)
    idx = np.arange(0, 4000, 3)
    ans = asyncio.run(checker.read_back(door, 7, idx, keyspec, T_PEEK))
    assert isinstance(ans, wirefmt.Answers) and len(ans.status) == len(idx)
    assert door.bodies == [
        wirefmt.request_bytes(wirefmt.key_ids(7, idx[lo : lo + 1000]), 0, limit, dur,
                              created_at=T_PEEK, algorithm=algorithm)
        for lo in range(0, len(idx), 1000)]
    assert door.most == 2


def test_a_global_keyspace_is_read_once_a_peer_one_rpc_at_a_time():
    import asyncio

    keyspec = dict(KEYSPEC, behavior=["GLOBAL"])
    door = _RecordingDoor(wirefmt.TOKEN)
    idx = np.arange(0, 4000, 3)
    readings = asyncio.run(checker.read_back(door, 7, idx, keyspec, T_PEEK, readings=4))
    assert len(readings) == 4 and all(len(a.status) == len(idx) for a in readings)
    parts = [wirefmt.request_bytes(wirefmt.key_ids(7, idx[lo : lo + 1000]), 0, 100, 3_600_000,
                                   created_at=T_PEEK, behavior=wirefmt.GLOBAL)
             for lo in range(0, len(idx), 1000)]
    # a part's four readings straight after one another, nothing beside them
    assert door.bodies == [p for p in parts for _ in range(4)] and door.most == 1
    door = _RecordingDoor(wirefmt.TOKEN)
    asyncio.run(checker.fill(door, 7, keyspec))
    assert all(body.count(b"\x38\x02") == 1000 for body in door.bodies)
