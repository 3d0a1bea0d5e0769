"""A GLOBAL keyspace's rules (bench/checker.py's docstring): the oracle that
carries both homes, the drain, the read-back of every peer, what GLOBAL may
over-admit, and what is refused before a server starts. No server here; the
scratch cell end to end is in test_end_to_end.py."""

import asyncio
import itertools

import numpy as np
import pytest

import checker
import loadgen
import wirefmt
from doors import BenchFailure
from oracles import GlobalOracle, TokenOracle

D = 7_200_000
T0 = 1_790_000_000_000
O = wirefmt.DRAIN_OVER_LIMIT
# the scripts a GLOBAL keyspace keeps, as `checker.fresh_scenarios` has them:
# (hits, limit, the row's own behavior) a step
KEPT = {
    "drain": [(2, 5, 0), (2, 5, 0), (2, 5, 0), (1, 5, 0), (1, 5, 0), (0, 5, 0)],
    "drainover": [(3, 5, 0), (4, 5, O), (0, 5, 0), (1, 5, 0)],
    "peek": [(0, 7, 0), (1, 7, 0), (0, 7, 0)],
    "wide": [(1 << 18, 1 << 20, 0), (1 << 18, 1 << 20, 0), (0, 1 << 20, 0)],
}


class TwoPeers:
    """Upstream's GLOBAL between an owner and one other peer, each a plain
    `TokenOracle`, written out step by step (gubernator.go:401-429, 526-532,
    global.go): the reference `GlobalOracle` is held to here. `double`
    plants the fault the check is there for: the owner applies queued hits
    twice."""

    def __init__(self, double=False):
        self.owner, self.other, self.double = TokenOracle(), TokenOracle(), double

    def check(self, home, at, hits, limit, drain):
        peer = self.owner if home == "owner" else self.other
        ans = peer.check(0, at, hits, limit, D, drain=drain)
        self.queued = (home, hits, limit)
        return ans

    def sync(self, now):
        home, hits, limit = self.queued
        if hits == 0:
            return  # never queued
        # the owner's own check is queued as a broadcast of its state
        apply = 0 if home == "owner" else hits
        for _ in range(2 if self.double and apply else 1):
            status, rem, reset = self.owner.check(0, now, apply, limit, D, drain=True)
        self.other.state[0] = (rem, reset, status)  # installs the ANSWER


def _play(script, homes, double=False):
    """The script against TwoPeers with the given home per step; returns
    the steps whose answer the oracle did not admit."""
    oracle, cluster, refused = GlobalOracle(), TwoPeers(double), []
    for s, ((hits, limit, beh), home) in enumerate(zip(script, homes)):
        at = T0 + s
        status, rem, reset = cluster.check(home, at, hits, limit, bool(beh & O))
        admitted = oracle.answers("k", at, hits, limit, D, drain=bool(beh & O))
        if not checker._admits(admitted, [status, limit, rem, reset, ""]):
            refused.append((s, (status, rem, reset), admitted))
        tick = at + 40 + 13 * s  # the owner's clock at its next tick
        cluster.sync(tick)
        oracle.settle("k", at, hits, limit, D, bool(beh & O), (at, tick + 60))
    return refused


@pytest.mark.parametrize("name", list(KEPT))
def test_the_oracle_admits_every_kept_script_whichever_peer_answers(name):
    script = KEPT[name]
    for homes in itertools.product(("owner", "other"), repeat=len(script)):
        assert _play(script, homes) == [], homes


@pytest.mark.parametrize("name", ["drain", "peek"])  # `drainover` empties the bucket either way
def test_the_oracle_refuses_hits_an_owner_applied_twice(name):
    script = KEPT[name]
    caught = [homes for homes in itertools.product(("owner", "other"), repeat=len(script))
              if _play(script, homes, double=True)]
    # every order in which another peer took a hit that a later step can see
    assert len(caught) >= 2 ** len(script) // 2


def test_where_no_over_ask_happened_the_answer_is_the_plain_oracles():
    oracle, plain = GlobalOracle(), TokenOracle()
    for s, (hits, limit, _) in enumerate(KEPT["wide"]):
        want = plain.check("k", T0 + s, hits, limit, D)
        got = oracle.answers("k", T0 + s, hits, limit, D)
        assert {(a[0], a[1]) for a in got} == {want[:2]}
        # the reset_time is the pinned stamp's, or an owner's tick's
        assert got[0][2] == got[0][3] == want[2]
        oracle.settle("k", T0 + s, hits, limit, D, False, (T0 + s + 5, T0 + s + 90))
    assert all(a[3] <= T0 + 90 + D for a in got)


def test_after_an_over_ask_both_homes_states_are_carried():
    oracle = GlobalOracle()
    for s, (hits, at_most) in enumerate([(3, 1), (4, 1)]):
        assert len({a[:2] for a in oracle.answers("k", T0 + s, hits, 5, D)}) == at_most
        oracle.settle("k", T0 + s, hits, 5, D, False, (T0 + s, T0 + s + 100))
    # the owner left 2 where it answered, took them all where another peer did
    assert {a[:2] for a in oracle.answers("k", T0 + 2, 0, 5, D)} == {(0, 2), (0, 0), (1, 0)}


# ------------------------------------------------------------------ drain


class GlobalDoor:
    """`/v1/debug/global` from a script of (pending, sync_rounds); counts
    what the drain sends."""

    def __init__(self, readings, sync_wait_ms=1.0):
        self.readings, self.sync_wait_ms = iter(readings), sync_wait_ms
        self.gets, self.sent, self.last = 0, [], None

    async def get(self, path):
        assert path == "/v1/debug/global"
        self.gets += 1
        self.last = next(self.readings, self.last)
        pending, rounds = self.last
        return {"mesh": {"pending": pending, "sync_rounds": rounds},
                "manager": {"pending_hits": 0, "pending_updates": 0,
                            "sync_wait_ms": self.sync_wait_ms}}

    async def check_raw(self, body):
        self.sent.append((self.gets, body))
        return b""


def test_a_drain_waits_for_a_whole_round_after_the_first_reading_of_zero():
    # 5 pending; popped (0 pending, their round still on the device); the
    # sentinel queued; popped; and only then has a round ended since
    door = GlobalDoor([(5, 3), (5, 3), (0, 3), (1, 3), (0, 3), (0, 3), (0, 4)])
    out = asyncio.run(checker.drain(door, 77))
    assert out["undrained"] == 0 and out["sync_rounds"] == 4 and door.gets == 7
    (after_get, body), = door.sent  # one sentinel, at the first reading of 0
    assert after_get == 3
    assert body == wirefmt.encode_item("drain", "s77", 1, 1 << 30, checker.COMPACT_MAX_DURATION_MS,
                                       behavior=wirefmt.GLOBAL)


def test_an_idle_server_is_drained_by_the_sentinels_own_round():
    door = GlobalDoor([(0, 9), (1, 9), (0, 10)])
    assert asyncio.run(checker.drain(door, 1))["undrained"] == 0 and len(door.sent) == 1


@pytest.mark.parametrize("stuck,undrained", [((7, 3), 7), ((0, 3), 1)])
def test_what_is_left_at_the_time_limit_is_reported(stuck, undrained):
    door = GlobalDoor([stuck])
    out = asyncio.run(checker.drain(door, 1))
    assert out["undrained"] == undrained
    # fifty of the server's own sync waits
    assert 50.0 <= out["ms"] < 500.0


def test_a_backlog_that_empties_round_by_round_is_not_stuck():
    """The fifty waits run from the last round that ended: a fill's backlog
    took 5.4 s to drain at full size, a round every 10 ms."""
    slow = [(2000 - 10 * r, r) for r in range(200) for _ in range(4)]  # a round every 4 polls
    door = GlobalDoor(slow + [(0, 200), (1, 200), (0, 201)], sync_wait_ms=0.1)
    out = asyncio.run(checker.drain(door, 1))
    assert out["undrained"] == 0 and out["sync_rounds"] == 201
    assert out["ms"] > 50 * 0.1  # longer than a stuck queue is given


# -------------------------------------------------- the read-back of every peer

KEYSPEC = {"keys": 4000, "limit": 100, "duration_ms": 3_600_000, "hits": 1,
           "behavior": ["GLOBAL"]}
DUR = KEYSPEC["duration_ms"]
T_FILLED, T_PEEK = T0 + 900, T0 + 60_000


def _world(rng, peers=4):
    """A cluster that behaved: 4,000 keys filled by 4 RPCs, a key in four
    installed by its fill RPC at its owner (reset_time = created_at +
    duration) and the others by a sync tick some ms later; every peer holds
    the owner's state; every 37th key was evicted at one peer, which
    answers a fresh bucket, and every 41st at its owner and installed anew."""
    counts = 1 + rng.integers(0, 140, size=4000)
    created = T0 + np.arange(4) * 7
    idx = np.arange(0, 4000, 3)
    n = counts[idx]
    born = created[idx // checker.FILL_RPC_ITEMS] + DUR
    born = np.where(idx % 4 == 0, born, born + rng.integers(1, 800, size=len(idx)))
    readings = []
    for r in range(peers):
        rem, status, reset = np.maximum(100 - n, 0), np.zeros(len(idx), np.int64), born.copy()
        status[n > 100] = (r + idx[n > 100]) % 2  # past the limit a copy may differ in status
        one = (idx % 37 == 0) & (idx % peers == r)
        rem[one], status[one], reset[one] = 100, 0, T_PEEK + DUR
        anew = idx % 41 == 0
        rem[anew] = np.minimum(rem[anew] + 2, 100)
        status[anew], reset[anew] = 0, born[anew] + 20_000
        readings.append(wirefmt.Answers(np.asarray([len(idx)]), np.asarray([0]), status,
                                        np.full(len(idx), 100), rem, reset))
    return idx, readings, counts, created


def _judge(idx, readings, counts, created):
    return checker.judge_global_counters(idx, readings, counts, created, KEYSPEC, T_PEEK, T_FILLED)


def _zeros(out):
    return [out[k] for k in ("below_expected", "above_expected_not_evicted", "status_wrong",
                             "fields_wrong", "replica_disagreements")]


def test_a_sound_cluster_is_exact_at_every_peer():
    idx, readings, counts, created = _world(np.random.default_rng(1))
    out = _judge(idx, readings, counts, created)
    assert _zeros(out) == [0, 0, 0, 0, 0], out["examples"]
    assert out["evicted"] == int(((idx % 37 == 0) | (idx % 41 == 0)).sum())
    assert out["evicted_at_every_peer"] == int((idx % 41 == 0).sum())
    assert out["readings"] == 4


def _kept(idx, counts, at_most=90):
    return int(np.flatnonzero((idx % 37 != 0) & (idx % 41 != 0) & (counts[idx] < at_most))[0])


def test_one_peer_one_hit_ahead_is_below_and_disagrees():
    """What ROADMAP C2 predicts: a replica row retried after a sync tick."""
    idx, readings, counts, created = _world(np.random.default_rng(2))
    readings[2].remaining[_kept(idx, counts)] -= 1
    out = _judge(idx, readings, counts, created)
    assert _zeros(out) == [1, 0, 0, 0, 1]


def test_a_hit_the_owner_never_got_is_above_at_every_peer():
    idx, readings, counts, created = _world(np.random.default_rng(3))
    j = _kept(idx, counts)
    for ans in readings:
        ans.remaining[j] += 1
    assert _zeros(_judge(idx, readings, counts, created)) == [0, 1, 0, 0, 0]


def test_peers_that_answer_two_reset_times_disagree():
    idx, readings, counts, created = _world(np.random.default_rng(4))
    readings[1].reset_time[_kept(idx, counts)] += 1
    assert _zeros(_judge(idx, readings, counts, created)) == [0, 0, 0, 0, 1]


def test_over_limit_on_a_key_under_its_limit_is_wrong_and_past_it_is_either():
    idx, readings, counts, created = _world(np.random.default_rng(5))
    readings[0].status[_kept(idx, counts)] = 1
    assert _zeros(_judge(idx, readings, counts, created)) == [0, 0, 1, 0, 0]


def test_a_reset_time_from_before_the_fill_is_no_answer():
    idx, readings, counts, created = _world(np.random.default_rng(6))
    readings[3].reset_time[_kept(idx, counts)] = T0 - 1 + DUR
    assert _zeros(_judge(idx, readings, counts, created))[3] == 1


def test_an_evicted_copy_may_hold_more_but_never_less():
    idx, readings, counts, created = _world(np.random.default_rng(7))
    j = int(np.flatnonzero((idx % 41 == 0) & (counts[idx] < 90))[0])
    readings[1].remaining[j] = 100 - counts[idx[j]] - 1
    assert _judge(idx, readings, counts, created)["below_expected"] == 1


# ------------------------------------------- what GLOBAL may over-admit


def _ledger(idx, rows):
    led = loadgen.Ledger(0, 1)
    led.idx, led.resp = [np.asarray(idx)], [wirefmt.response_bytes(rows)]
    checker.settle(led)
    return led


def test_over_admission_is_bounded_by_one_bucket_a_peer():
    keyspec = dict(KEYSPEC, keys=3, limit=2)
    under, over = (0, 2, 1, T0 + DUR), (1, 2, 0, T0 + DUR)
    # key 0: the fill's grant and 3 more (4 > limit, <= limit x 2 peers);
    # key 1: the fill's and 4 more (5 > 4); key 2: refused throughout
    led = _ledger([0, 0, 0, 1, 1, 1, 1, 2, 2], [under] * 7 + [over] * 2)
    assert checker.over_admission([led], 3, keyspec, peers=2) == {"keys": 1, "excess_hits": 5}
    assert checker.over_admission([led], 3, keyspec, peers=4)["keys"] == 0


# ----------------------------------------- refused before the server starts


@pytest.mark.parametrize("keyspec,traffic,why", [
    ({"behavior": ["RESET_REMAINING"]}, None, "a rule for GLOBAL alone"),
    ({"behavior": ["GLOBAL", "DRAIN_OVER_LIMIT"]}, None, "DRAIN_OVER_LIMIT"),
    ({"behavior": ["GLOBL"]}, None, "behavior 'GLOBL'"),
    ({}, {"behavior": {"mix": [[0.9, []], [0.1, ["MULTI_REGION"]]]}}, "MULTI_REGION"),
    ({"behavior": ["GLOBAL"], "algorithm": "leaky", "duration_ms": 134_000_000}, None,
     "leaky keyspace under GLOBAL"),
    ({"algorithm": "leaky", "duration_ms": 134_000_000},
     {"behavior": {"mix": [[1, ["GLOBAL"]]]}}, "leaky keyspace under GLOBAL"),
    ({"script_algorithms": ["leaky"]}, None, "script_algorithms"),
    ({"script_algorithms": ["token", "gcra"]}, None, "script_algorithms"),
    ({"algorithm": "leaky", "duration_ms": 134_000_000, "script_algorithms": ["token"]}, None,
     "script_algorithms"),
])
def test_a_keyspace_the_check_has_no_rule_for_is_refused(keyspec, traffic, why):
    base = {"keys": 10, "hits": 1, "limit": 100, "duration_ms": 3_600_000}
    with pytest.raises(BenchFailure, match=why):
        checker.refuse_keyspec({**base, **keyspec}, 1150.0, traffic)


def test_global_and_a_mix_of_it_are_taken():
    base = {"keys": 10, "hits": 1, "limit": 100, "duration_ms": 3_600_000}
    mix = {"behavior": {"mix": [[0.9, []], [0.1, ["GLOBAL"]]]}}
    checker.refuse_keyspec(dict(base, behavior=["GLOBAL"]), 1150.0, {})
    checker.refuse_keyspec(dict(base, script_algorithms=["token"]), 1150.0, mix)
    assert checker.carries_global(base, mix) and not checker.is_global(base)
    assert not checker.carries_global(base, {}) and not checker.carries_global(base)
    assert checker.carries_global(dict(base, behavior=["GLOBAL"]))


# ------------------------------------------------- the scripts that are sent


def test_a_global_keyspace_sends_the_scripts_its_contract_fixes():
    keyspec = {"keys": 10, "hits": 1, "limit": 100, "duration_ms": 3_600_000,
               "behavior": ["GLOBAL"]}
    groups = checker.fresh_scenarios(9, 3, T0, True, keyspec)
    names = [steps[0][0][3].split("/")[0] for steps in groups]
    assert names == list(KEPT)
    assert sorted(checker.scripts_left_out(keyspec)) == ["dup", "leak", "reset"]
    for steps, script in zip(groups, KEPT.values()):
        assert [(st[0][2].hits, st[0][1], st[0][2].drain) for st in steps] == [
            (h, lim, bool(b & O)) for h, lim, b in script]
        for s, step in enumerate(steps):
            for k, (body, limit, op, _label) in enumerate(step):
                # every row carries GLOBAL besides its script's own flags
                hits, _lim, beh = script[s]
                assert body == wirefmt.encode_item(
                    op.key[0], f"s9-{k}", hits, limit, D, wirefmt.TOKEN,
                    beh | wirefmt.GLOBAL, T0 + s)


class ScriptedCluster:
    """A door over TwoPeers-like state for `run_scenarios`: every step's RPC
    reaches the owner or the other peer in turn; `drain` runs the tick."""

    def __init__(self, double=False):
        self.double, self.peers, self.n, self.queued = double, {}, 0, []

    async def check_raw(self, body):
        from gubernator_tpu.proto import gubernator_pb2 as pb

        home = "owner" if self.n % 2 else "other"
        self.n += 1
        rows = []
        for r in pb.GetRateLimitsReq.FromString(body).requests:
            assert r.behavior & wirefmt.GLOBAL
            two = self.peers.setdefault((r.name, r.unique_key), TwoPeers(self.double))
            status, rem, reset = two.check(home, r.created_at, r.hits, r.limit,
                                           bool(r.behavior & O))
            self.queued.append(two)
            rows.append((status, r.limit, rem, reset))
        return wirefmt.response_bytes(rows)

    async def drain(self):
        for two in self.queued:
            two.sync(checker.now_ms())
        self.queued = []


@pytest.mark.parametrize("double,mismatches", [(False, 0), (True, None)])
def test_run_scenarios_drains_after_every_step_and_asks_the_global_oracle(double, mismatches):
    keyspec = {"keys": 10, "hits": 1, "limit": 100, "duration_ms": 3_600_000,
               "behavior": ["GLOBAL"]}
    door = ScriptedCluster(double)
    groups = checker.fresh_scenarios(9, 4, checker.now_ms(), True, keyspec)
    out = asyncio.run(checker.run_scenarios(door, groups, drain=door.drain))
    assert out["compared"] == 4 * sum(len(s) for s in KEPT.values()) == 64
    assert door.n == 16
    if double:
        assert out["mismatches"] > 0 and "expected [" in out["examples"][0]
    else:
        assert out["mismatches"] == mismatches, out["examples"]
