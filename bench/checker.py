"""What decides `correct`: the fill, the scripted scenarios, the invariants of
every answer of the window, and the counters read back after it.

Nothing here looks at a clock to decide an outcome, and nothing differs
between a traced and an untraced run. `fill` and `fresh_scenarios` are
copied from `chip_smoke.py` (commit 846d0923: `load_bulk` without its GLOBAL
rows, `fresh_scenarios`, `run_fresh`) and build their bytes with
bench/wirefmt.py instead of the program's proto module.

The key fact the counters rest on: a token bucket's reset_time is fixed when
the key is installed, and the fill pins `created_at` per RPC. So a key that
was never evicted still answers reset_time == its fill RPC's created_at +
duration, and must then hold exactly limit - (hits the generator sent it).
A later reset_time means the table evicted the key while live and a later
check installed it anew: such a key may hold more than expected, never less,
and the server's own count of live evictions bounds how many there may be.

A leaky bucket (`keyspace.algorithm` "leaky") has no such fact: its reset_time
is the answer's own `created_at` + (limit - remaining) * int(duration/limit)
and moves with every answer. Its counters rest on another. Upstream's leaky
bucket (algorithms.go, oracles.LeakyOracle) applies a leak only once a whole
token has leaked, int((now - updated) / rate) > 0 with rate = duration/limit
ms, and only then moves `updated`. So while everything a run does to a key,
from its fill to its read-back, takes less than `rate` ms, no leak ever
lands, `remaining` stays a whole number, and a key that was never evicted
must hold exactly max(limit - sent, 0) whatever order the server took its
rows in: the token rule's strength, with the leak arithmetic still computed
on every row. `refuse_keyspec` therefore refuses a leaky keyspace whose
duration_ms/limit is under the run's time budget. Leaks that do land are
judged where order is known, in the scripted scenarios. What a leaky key's
read-back cannot say is whether the key was evicted live: below expected is
a hit counted twice (limit 0); above expected is a hit lost or a live
eviction, one as likely as the other, and every such key counts as evicted.
Their number may not pass min(evicted_live_total, the token rule's
allowance): each eviction the server counted lifts at most one key, and the
sample is part of the table. So the rule is exact, one key above fails the
run, exactly when the server counted no live eviction, and a leaky
configuration has to be sized so that it counts none (upstream's LRU evicts
no live key while the cache holds them all). A leaky peek (hits = 0) never
answers OVER_LIMIT, and its reset_time must be the peek's pinned
`created_at` + (limit - remaining) * int(duration/limit), exactly.

A GLOBAL keyspace (`keyspace.behavior` ["GLOBAL"], token buckets; PR 46) is
judged by GLOBAL's contract (upstream docs/architecture.md "Global
behavior", global.go, gubernator.go:401-429 and :526-532), which is
eventually consistent: a check is answered by whichever peer it reaches from
the state that peer holds, its hits reach the key's owner at the owner's
next sync tick (GlobalSyncWait, 100 ms), and the owner's answer is then
installed by every peer. Between a send and that install a peer's state is
stale; after it (`drain`) every peer holds the owner's. So:
  fill          as any keyspace's: a key's first check is exact wherever it
                lands (remaining == limit - hits, reset_time == the RPC's
                pinned created_at + duration). Then a drain.
  scenarios     a drain after every step, and `oracles.GlobalOracle` in
                place of the plain oracle: the answer is the plain oracle's
                on the state all peers share; the state afterwards is the
                owner's own application of the step (it was the peer
                reached) or its application of the queued hits with
                DRAIN_OVER_LIMIT forced, on its own clock (another peer
                was); a client cannot know which, so after an over-ask the
                oracle carries both and an answer is right if either admits
                it, and a reset_time fixed by an owner's tick is held inside
                [send, end of the drain] + duration. The scripts whose
                outcome the contract leaves open are left out
                (`GLOBAL_LEFT_OUT` says which and why).
  window        `window_invariants` as it is: every rule in it is order-free
                and holds for a stale peer (a stale `remaining` is higher,
                never lower than limit - all hits sent; OVER_LIMIT only on a
                key sent more than its limit). Plus what GLOBAL admits and
                upstream documents, bounded: `global_over_admitted` counts
                the keys whose UNDER_LIMIT answers carried more than limit *
                peers hits (each peer can grant at most one bucket before it
                hears from the owner; limit 0). The plain excess over
                `limit` is reported unjudged.
  read-back     a drain, then every sampled key read with hits = 0 `peers`
                times, a part's readings one straight after another so that
                successive dispatches walk the peers. The token rule's key
                fact does not hold: a key whose fill reached another peer
                than its owner was installed at the owner by a sync tick, on
                the owner's clock, and every peer then answers reset_time ==
                that tick + duration (found on the four-device CPU mesh, PR
                46; a key whose fill reached its owner keeps created_at +
                duration everywhere). What replaces it: a key never evicted
                answers a reset_time inside [its fill RPC's created_at, the
                end of the drain that followed the fill] + duration, the
                same from every peer; every such reading must hold exactly
                max(limit - sent, 0) (below: a hit counted twice; above: a
                hit lost; both 0, under the token rule's names), UNDER_LIMIT
                unless the key was sent more than its limit (past it the
                owner's item and a peer's installed copy may differ in
                status, as upstream's do), and the readings of one key must
                agree in remaining and reset_time (`replica_disagreements`,
                limit 0). A later reset_time is an eviction, at the owner
                (every peer agrees on it) or at one peer (which answers a
                fresh bucket, as upstream's peer does): a key with such a
                reading counts once in `counters_evicted_in_sample`, held to
                the token rule's allowance from the server's own count.
`drain` says when a drain is over, and `global_undrained` (limit 0) what was
left when it gave up. A traffic file may also mix GLOBAL RPCs into a
keyspace that is not GLOBAL (`behavior` in bench/loadgen.py): the window's
rules and the drains are these, and the read-back, which then carries no
flag, asks the owners alone and judges by the token rule.
"""

from __future__ import annotations

import asyncio
import math
import time
from typing import NamedTuple

import numpy as np

import loadgen
import wirefmt
from doors import BenchFailure
from oracles import GlobalOracle, LeakyOracle, TokenOracle

FILL_RPC_ITEMS = 1_000  # upstream's batch cap
FILL_INFLIGHT = 64
FRESH_KEYS_PER_SCRIPT = 200
ORACLES = {wirefmt.TOKEN: TokenOracle, wirefmt.LEAKY: LeakyOracle}
ALGORITHM_NAMES = tuple(wirefmt.ALGORITHMS)
# the longest duration the compact wire carries (ops/wire.py: 27 bits)
COMPACT_MAX_DURATION_MS = (1 << 27) - 1
# the scripted leaky steps pin `created_at` up to this far ahead: inside the
# server's tolerance of +-5 min (config.created_at_tolerance_ms), beyond which
# a pinned stamp is clamped to the server's clock
LEAKY_SCRIPT_SPAN_MS = 200_000
# a drain gives up after this many of the server's GlobalSyncWait without a
# round that ended
DRAIN_LIMIT_SYNC_WAITS = 50
# the scripts a GLOBAL keyspace leaves out, and why GLOBAL's contract does
# not fix their outcome
GLOBAL_LEFT_OUT = {
    "reset": "RESET_REMAINING is OR-ed into the queued hits and the owner answers a reset "
             "with reset_time 0, which a peer installs as an item already expired: the next "
             "answer depends on which peer gives it (the order-dependent flags are BASELINE "
             "config 4's rule)",
    "leak": "its created_at steps 15 s at a time, but an owner applies queued hits at its "
            "own clock, so how much has leaked is not the script's to say",
    "dup": "how copies of one key in one dispatch are answered is the engine's duplicate "
           "rule, not GLOBAL's; their summed hits reach the owner either way, which the "
           "read-back sees",
}


class Op(NamedTuple):
    """One scripted check of a GLOBAL keyspace, as `oracles.GlobalOracle` is
    asked about it: the key, the pinned `created_at`, and whether the row
    carries DRAIN_OVER_LIMIT itself."""

    key: tuple
    at: int
    hits: int
    limit: int
    duration: int
    drain: bool


class Compared:
    """One number the check compares, beside its limit."""

    def __init__(self, name: str, value, limit, of=None):
        self.name, self.value, self.limit, self.of = name, value, limit, of

    @property
    def ok(self) -> bool:
        return self.value <= self.limit

    def to_dict(self) -> dict:
        d = {"name": self.name, "value": self.value, "limit": self.limit, "ok": self.ok}
        if self.of is not None:
            d["of"] = self.of
        return d


def now_ms() -> int:
    return time.time_ns() // 1_000_000


def is_leaky(keyspec: dict) -> bool:
    return wirefmt.keyspec_algorithm(keyspec) == wirefmt.LEAKY


def is_global(keyspec: dict) -> bool:
    return bool(wirefmt.keyspec_behavior(keyspec) & wirefmt.GLOBAL)


def traffic_behaviors(traffic: dict | None) -> list:
    """The behavior of every entry of a traffic file's `behavior` mix, as
    wire numbers ([] when the file has no such key)."""
    mix = loadgen.behavior_mix(traffic or {})
    return mix[1] if mix else []


def carries_global(keyspec: dict, traffic: dict | None = None) -> bool:
    """Whether any row of a run can carry GLOBAL: only then is anything
    drained, and only then are GLOBAL's numbers compared."""
    return is_global(keyspec) or any(b & wirefmt.GLOBAL for b in traffic_behaviors(traffic))


def script_algorithms(keyspec: dict) -> list:
    """The algorithm families the scripted scenarios send (`keyspace.
    script_algorithms`; absent = both). A table in a packed single-algorithm
    layout migrates to the full layout, one way, at its first row of another
    family: a configuration that times such a table names its own family."""
    return list(keyspec.get("script_algorithms", ALGORITHM_NAMES))


def scripts_left_out(keyspec: dict) -> dict:
    """{script: reason} of the scripts `fresh_scenarios` does not send."""
    out = dict(GLOBAL_LEFT_OUT) if is_global(keyspec) else {}
    if "leaky" not in script_algorithms(keyspec):
        out["leak"] = "keyspace.script_algorithms names no leaky script"
    return out


def leaky_ms_per_token(keyspec: dict) -> int:
    """int64(rate) of upstream's leaky bucket: what one token adds to an
    answer's reset_time."""
    return int(int(keyspec["duration_ms"]) / int(keyspec["limit"]))


def refuse_keyspec(keyspec: dict, run_budget_s: float, traffic: dict | None = None) -> None:
    """Before the server starts. A behavior the check has no rule for is
    refused, in the keyspace and in the traffic's mix: this check judges
    GLOBAL alone. A leaky keyspace has to leak slower than one token in the
    run's whole time budget, or a leak could land between a key's fill and
    its read-back and the order-free counter rule (module docstring) would
    not hold."""
    try:
        behaviors = [wirefmt.keyspec_behavior(keyspec), *traffic_behaviors(traffic)]
    except ValueError as exc:
        raise BenchFailure(str(exc)) from None
    for bits in behaviors:
        if bits & ~wirefmt.GLOBAL:
            names = [n for n, b in wirefmt.BEHAVIORS.items() if bits & b & ~wirefmt.GLOBAL]
            raise BenchFailure(
                f"behavior {names}: the check has a rule for GLOBAL alone. RESET_REMAINING "
                "and DRAIN_OVER_LIMIT make what a key holds depend on the order the server "
                "took its rows in, which the read-back does not know (BASELINE.json config "
                "4's issue builds that rule); the others have none yet either"
            )
    scripts = script_algorithms(keyspec)
    if "token" not in scripts or set(scripts) - set(ALGORITHM_NAMES) or (
            is_leaky(keyspec) and "leaky" not in scripts):
        raise BenchFailure(
            f"keyspace.script_algorithms {scripts}: a list of {list(ALGORITHM_NAMES)} that "
            "keeps \"token\" and the keyspace's own algorithm")
    if is_leaky(keyspec) and carries_global(keyspec, traffic):
        raise BenchFailure(
            "a leaky keyspace under GLOBAL has no rule yet: an owner applies queued hits at "
            "its own clock, and the scripts that would judge its leak are the ones GLOBAL "
            "leaves out")
    if not is_leaky(keyspec):
        return
    floor_ms = run_budget_s * 1000.0
    per_token = int(keyspec["duration_ms"]) / int(keyspec["limit"])
    if per_token < floor_ms:
        raise BenchFailure(
            f"keyspace.algorithm leaky with limit {keyspec['limit']} per "
            f"{keyspec['duration_ms']} ms leaks a token every {per_token:.0f} ms, under "
            f"the floor of {floor_ms:.0f} ms (a run's time budget): a leak could land "
            "inside a run, and the read-back judges leaky counters as exact only while "
            "none does; leaks that land are judged by the scripted scenarios"
        )


async def gather_all(coros) -> None:
    """Await every task; the first failure cancels the rest and raises."""
    tasks = [asyncio.ensure_future(c) for c in coros]
    try:
        await asyncio.gather(*tasks)
    finally:
        for t in tasks:
            t.cancel()


# ------------------------------------------------------------------ drain


async def drain(door, seed: int) -> dict:
    """Wait until every GLOBAL hit sent so far has been applied by its owner
    and the owner's answer installed by every peer. Returns {"ms": the wall,
    "undrained": what was left at the time limit (0: drained), "sync_rounds":
    the server's count at the end}.

    What `GET /v1/debug/global` can say (`daemon.debug_global`): `mesh.
    pending`, the entries in the peers' outboxes, and `mesh.sync_rounds`,
    the collective rounds that have ENDED; `manager`, the cross-daemon
    queues. One round is the whole exchange: `GlobalShardedEngine.sync`
    pops up to `sync_out` entries a peer (`_build_box`), and one jitted step
    (`_sync_core`) gathers them, has each owner apply its keys' summed hits,
    gathers the owners' answers and installs them in every other peer's
    replica table; rounds run one after another on the engine thread, and a
    round is counted once its counters are back on the host. So an entry is
    applied and installed everywhere by the end of the round that popped it,
    and no second round is needed for the broadcast. But a reading of
    pending = 0 only proves that the last entries have been POPPED: their
    round may still be on the device, and on an idle server no later round
    would ever come to show that it ended (the tick skips an empty queue).
    So, at the first reading of 0 in every queue, with `sync_rounds` = r
    read after it, the drain sends one GLOBAL hit on a key of its own
    (`drain/s<seed>`, outside every keyspace) and waits for pending = 0 with
    `sync_rounds` > r: the first round to end after that reading is the one
    that was in flight at it, if one was, or else the sentinel's own, which
    began after it. Either way one whole round has ended after the first
    reading of 0, and every entry popped before it has landed.

    The drain gives up once no round has ended for 50 of the server's own
    `GUBER_GLOBAL_SYNC_WAIT` (`manager.sync_wait_ms`); `undrained` is then
    what the queues still hold, or 1 where they are empty and the awaited
    round has not ended. The 50 waits run from the last round that ended,
    not from the drain's start: at full size the backlog a fill of 10M
    GLOBAL keys leaves took sound runs 2.2 s (warm) and 5.4 s (cold) to
    drain, round after round, against 5.0 s (PERF.md section 6, PR 46), and
    a queue that empties round by round is not stuck."""
    t0 = t_progress = time.monotonic()
    wait_rounds, last_rounds = None, None
    while True:
        g = await door.get("/v1/debug/global")
        mesh, mgr = g["mesh"], g["manager"]
        pending = int(mesh["pending"]) + int(mgr["pending_hits"]) + int(mgr["pending_updates"])
        rounds = int(mesh["sync_rounds"])
        if rounds != last_rounds:
            last_rounds, t_progress = rounds, time.monotonic()
        if pending == 0 and wait_rounds is None:
            wait_rounds = rounds
            await door.check_raw(wirefmt.encode_item(
                "drain", f"s{seed}", 1, 1 << 30, COMPACT_MAX_DURATION_MS,
                behavior=wirefmt.GLOBAL))
            continue
        done = pending == 0 and wait_rounds is not None and rounds > wait_rounds
        sync_wait_s = float(mgr["sync_wait_ms"]) / 1e3
        if done or time.monotonic() - t_progress > DRAIN_LIMIT_SYNC_WAITS * sync_wait_s:
            return {"ms": (time.monotonic() - t0) * 1e3, "sync_rounds": rounds,
                    "undrained": 0 if done else max(pending, 1)}
        await asyncio.sleep(sync_wait_s / 10)


# ------------------------------------------------------------------- fill


async def fill(door, seed: int, keyspec: dict) -> dict:
    """One check with the cell's `hits` per live key, `created_at` pinned
    per RPC, 64 RPCs in flight. Every answer must be what the plain oracle of
    the keyspace's algorithm gives a fresh key at that `created_at` (token:
    UNDER_LIMIT, remaining == limit - hits, reset_time == created_at +
    duration; leaky: reset_time == created_at + hits * int(duration/limit));
    the usual answer is compared as bytes and only another is decoded."""
    n_keys, limit = int(keyspec["keys"]), int(keyspec["limit"])
    hits, duration = int(keyspec["hits"]), int(keyspec["duration_ms"])
    algorithm = wirefmt.keyspec_algorithm(keyspec)
    behavior = wirefmt.keyspec_behavior(keyspec)
    n_rpcs = -(-n_keys // FILL_RPC_ITEMS)
    created = np.zeros(n_rpcs, dtype=np.int64)
    out = {"mismatches": 0, "examples": [], "byte_identical_rpcs": 0}
    sem = asyncio.Semaphore(FILL_INFLIGHT)

    async def one(r: int) -> None:
        async with sem:
            lo = r * FILL_RPC_ITEMS
            n = min(FILL_RPC_ITEMS, n_keys - lo)
            created[r] = t = now_ms()
            body = wirefmt.request_bytes(
                wirefmt.key_ids(seed, np.arange(lo, lo + n)), hits, limit,
                duration, created_at=t, algorithm=algorithm, behavior=behavior,
            )
            data = await door.check_raw(body)
            status, remaining, reset = ORACLES[algorithm]().check(0, t, hits, limit, duration)
            want = (status, limit, remaining, reset)
            if data == wirefmt.response_bytes([want]) * n:
                out["byte_identical_rpcs"] += 1
                return
            rows = wirefmt.decode_response_slow(data)
            if len(rows) != n:
                out["mismatches"] += n
                out["examples"].append(f"fill rpc {r}: {len(rows)} answers for {n} items")
                return
            for j, row in enumerate(rows):
                if tuple(row[:4]) != want or row[4]:
                    out["mismatches"] += 1
                    if len(out["examples"]) < 5:
                        out["examples"].append(f"fill key {lo + j}: {row} expected {want}")

    t0 = time.monotonic()
    await gather_all(one(r) for r in range(n_rpcs))
    out.update(
        keys=n_keys, rpcs=n_rpcs, created=created,
        wall_s=time.monotonic() - t0,
    )
    return out


# ---------------------------------------------------- scripted scenarios


def fresh_scenarios(seed: int, n_per: int, t0: int, dup_aggregates: bool, keyspec: dict):
    """Scripts over fresh keys: a list of steps, each a list of (item bytes,
    limit, expected (status, remaining, reset_time), label) that goes out
    as ONE RPC, steps strictly one after another. Expectations come from the
    plain oracles. `dup_aggregates`: what the same key several times in one
    batch means on the engine under test — the local engine decides them one
    after another; the mesh's in-trace dedup aggregates them into one
    decision whose answer every copy gets (upstream's own GLOBAL hot-key
    rule, docs/architecture.md). The fresh keys' duration is twice the bulk
    keys', so a fresh key can evict a bulk key and never another fresh key
    (but no longer than the compact wire carries). A leaky keyspace adds one
    script at its own limit and duration, `leakspec`. Every row carries the
    keyspace's behavior besides its script's own; `scripts_left_out` names
    the scripts that are not sent, and in a GLOBAL keyspace the expectation
    of a row is not an answer but the `Op` that `oracles.GlobalOracle` is
    asked about once the step has drained (`run_scenarios`)."""
    tok, leak = TokenOracle(), LeakyOracle()
    D = min(2 * int(keyspec["duration_ms"]), COMPACT_MAX_DURATION_MS)
    R, O = wirefmt.RESET_REMAINING, wirefmt.DRAIN_OVER_LIMIT
    always, left_out = wirefmt.keyspec_behavior(keyspec), scripts_left_out(keyspec)
    as_ops = is_global(keyspec)

    def item(name, k, hits, limit, algo=wirefmt.TOKEN, behavior=0, at=t0, duration=D):
        return wirefmt.encode_item(
            name, f"s{seed}-{k}", hits, limit, duration, algo, behavior | always, at
        )

    def tstep(name, script):
        steps = []
        for s, (hits, limit, beh) in enumerate(script):
            step = []
            for k in range(n_per):
                exp = Op((name, k), t0 + s, hits, limit, D, bool(beh & O)) if as_ops else tok.check(
                    (name, k), t0 + s, hits, limit, D,
                    reset=bool(beh & R), drain=bool(beh & O),
                )
                step.append((
                    item(name, k, hits, limit, behavior=beh, at=t0 + s), limit,
                    exp, f"{name}/{k} step {s} hits={hits} behavior={beh}",
                ))
            steps.append(step)
        return steps

    token_scripts = {
        # down to OVER_LIMIT and past it; the at-limit status is sticky
        "drain": [(2, 5, 0), (2, 5, 0), (2, 5, 0), (1, 5, 0), (1, 5, 0), (0, 5, 0)],
        "reset": [(3, 5, 0), (1, 5, R), (1, 5, 0)],
        "drainover": [(3, 5, 0), (4, 5, O), (0, 5, 0), (1, 5, 0)],
        "peek": [(0, 7, 0), (1, 7, 0), (0, 7, 0)],
        # hits >= 2^18 cannot ride the compact wire: the full-width fallback
        "wide": [(1 << 18, 1 << 20, 0), (1 << 18, 1 << 20, 0), (0, 1 << 20, 0)],
    }
    groups = [tstep(name, script) for name, script in token_scripts.items()
              if name not in left_out]
    # leaky bucket across a pinned created_at step: rate = 60000/10 = 6000
    # ms per token, so +15000 ms leaks 2.5 tokens (never a borderline value)
    lk = []
    for s, (hits, dt, beh) in enumerate(
        [] if "leak" in left_out else
        [(4, 0, 0), (7, 0, 0), (3, 15_000, 0), (0, 15_000, 0),
         (9, 15_000, O), (1, 27_000, 0)]
    ):
        step = []
        for k in range(n_per):
            exp = leak.check(("leak", k), t0 + dt, hits, 10, 60_000, drain=bool(beh & O))
            step.append((
                item("leak", k, hits, 10, algo=wirefmt.LEAKY, behavior=beh,
                     at=t0 + dt, duration=60_000),
                10, exp, f"leak/{k} step {s} hits={hits} behavior={beh}",
            ))
        lk.append(step)
    if lk:
        groups.append(lk)
    if is_leaky(keyspec):
        # the keyspace's own limit and duration: `created_at` steps forward
        # by fractions of a token, so the leak is computed on every step and
        # must never land; hits up to the limit, one more is OVER_LIMIT, and
        # peeks between
        L, dur = int(keyspec["limit"]), int(keyspec["duration_ms"])
        dt = LEAKY_SCRIPT_SPAN_MS // 4
        script = [(1, 0), (L // 2, dt), (0, 2 * dt), (L - 1 - L // 2, 3 * dt),
                  (1, 4 * dt), (0, 4 * dt)]
        ls = []
        for s, (hits, at) in enumerate(script):
            step = []
            for k in range(n_per):
                exp = leak.check(("leakspec", k), t0 + at, hits, L, dur)
                step.append((
                    item("leakspec", k, hits, L, algo=wirefmt.LEAKY, at=t0 + at, duration=dur),
                    L, exp, f"leakspec/{k} step {s} hits={hits} at=+{at}",
                ))
            ls.append(step)
        groups.append(ls)
    if "dup" in left_out:
        return groups
    # the same key three times in one RPC
    step = []
    for k in range(n_per):
        if dup_aggregates:
            exp3 = [tok.check(("dup", k), t0, 6, 10, D)] * 3
        else:
            exp3 = [tok.check(("dup", k), t0, 2, 10, D) for _ in range(3)]
        step += [(item("dup", k, 2, 10), 10, e, f"dup/{k}") for e in exp3]
    groups.append([step])
    return groups


def _admits(admitted: list, row) -> bool:
    """Whether one of `GlobalOracle.answers`' (status, remaining, reset_lo,
    reset_hi) is this row."""
    return any(
        (row[0], row[2]) == (a[0], a[1]) and a[2] <= row[3] <= a[3] for a in admitted)


async def run_scenarios(door, groups, drain=None) -> dict:
    """Every step as one RPC after another (a step of more than 1,000 rows
    as several), each answer held to its expectation. With `drain` (a GLOBAL
    keyspace: module docstring) the cluster is drained after every step, and
    a row's expectation is the `Op` that `GlobalOracle` answers for: what it
    admits before the step, then the step settled between the clock at its
    send and the clock at the drain's end."""
    out = {"compared": 0, "mismatches": 0, "examples": []}
    oracle = GlobalOracle()
    for steps in groups:
        for step in steps:
            t_sent, answers = now_ms(), []
            for lo in range(0, len(step), FILL_RPC_ITEMS):
                part = step[lo : lo + FILL_RPC_ITEMS]
                data = await door.check_raw(b"".join(p[0] for p in part))
                rows = wirefmt.decode_response_slow(data)
                if len(rows) != len(part):
                    out["mismatches"] += len(part)
                    out["examples"].append(f"{len(rows)} answers for {len(part)} items")
                    continue
                answers += zip(part, rows)
            if drain is not None:
                await drain()
                applied = (t_sent, now_ms())
            for (_b, limit, exp, label), row in answers:
                out["compared"] += 1
                if drain is None:
                    ok = (row[0], row[2], row[3]) == exp
                else:
                    op, exp = exp, oracle.answers(*exp)
                    ok = _admits(exp, row)
                    oracle.settle(*op, applied)
                if row[4] or row[1] != limit or not ok:
                    out["mismatches"] += 1
                    if len(out["examples"]) < 5:
                        out["examples"].append(f"{label}: {row} expected {exp}")
    return out


# ------------------------------------------------- the window's answers


def hit_counts(n_keys: int, ledgers: list):
    """How many checks the generator sent to each key (`counts`: one by the
    fill, whose failure ends the run, and every RPC of the ledgers that went
    out, answered or not), and which keys it knows the count of exactly
    (`known`: no RPC that carried the key failed)."""
    counts = np.ones(n_keys, dtype=np.int64)
    known = np.ones(n_keys, dtype=bool)
    for led in ledgers:
        sent = [i for i, s in zip(led.idx, led.sent) if s == s]  # not NaN
        if sent:
            counts += np.bincount(np.concatenate(sent), minlength=n_keys)
        lost = [i for i, s, r in zip(led.idx, led.sent, led.resp) if s == s and r is None]
        if lost:
            known[np.concatenate(lost)] = False
    return counts, known


def settle(led) -> None:
    """Decode a finished ledger's answers once (`led.answers`). An RPC in
    which the server answered an item with an error string was refused, not
    answered: it joins the failed ones, and nothing is known of its hits."""
    datas = [r for r in led.resp if r is not None]
    ans = wirefmt.decode_responses(datas)
    if ans.errors:
        bad = set(np.flatnonzero(ans.n_errors > 0).tolist())
        k = 0
        for i, r in enumerate(led.resp):
            if r is None:
                continue
            if k in bad:
                row = next(x for x in wirefmt.decode_response_slow(r) if x[4])
                led.note(f"item error: {row[4]}")
                led.resp[i] = None
            k += 1
        ans = wirefmt.decode_responses([r for r in led.resp if r is not None])
    led.answers = ans


def window_invariants(ledgers: list, counts: np.ndarray, keyspec: dict,
                      t_lo_ms: int, t_hi_ms: int) -> dict:
    """Every answer of warm-up and window (ledgers that `settle` has read),
    whatever order the server took them in: as many answers as items, the
    limit echoed, status 0
    or 1, UNDER_LIMIT only with the hits taken (remaining <= limit - hits),
    OVER_LIMIT only on a key that was sent more hits than its limit,
    remaining never below limit - (all hits ever sent to the key), and
    reset_time inside [first fill, now] + duration (leaky: + (limit -
    remaining) * int(duration/limit), what the answer's own stamp carries)."""
    limit, hits = int(keyspec["limit"]), int(keyspec["hits"])
    dur = int(keyspec["duration_ms"])
    per_token = leaky_ms_per_token(keyspec) if is_leaky(keyspec) else None
    out = {"answers": 0, "violations": 0, "examples": []}

    def bad(mask, what, idx, ans):
        n = int(mask.sum())
        if n:
            out["violations"] += n
            j = int(np.flatnonzero(mask)[0])
            if len(out["examples"]) < 5:
                out["examples"].append(
                    f"{what}: key {int(idx[j])} status={int(ans.status[j])} "
                    f"limit={int(ans.limit[j])} remaining={int(ans.remaining[j])} "
                    f"reset_time={int(ans.reset_time[j])} sent={int(counts[idx[j]])}"
                )

    for led in ledgers:
        ok = [i for i, r in zip(led.idx, led.resp) if r is not None]
        if not ok:
            continue
        ans = led.answers
        want = np.fromiter((len(i) for i in ok), dtype=np.int64, count=len(ok))
        out["answers"] += int(want.sum())
        if not np.array_equal(ans.n_items, want):
            n = int((ans.n_items != want).sum())
            out["violations"] += n
            out["examples"].append(f"{n} RPCs answered another number of items than sent")
            continue
        idx = np.concatenate(ok)
        sent_hits = counts[idx] * hits
        under, over = ans.status == wirefmt.UNDER, ans.status == wirefmt.OVER
        bad(~(under | over), "status", idx, ans)
        bad(ans.limit != limit, "limit not echoed", idx, ans)
        bad(under & (ans.remaining > limit - hits), "UNDER_LIMIT without the hit", idx, ans)
        bad(over & (sent_hits <= limit), "OVER_LIMIT on a key under its limit", idx, ans)
        bad((ans.remaining < np.maximum(limit - sent_hits, 0)) | (ans.remaining > limit),
            "remaining out of range", idx, ans)
        ahead = dur if per_token is None else (limit - ans.remaining) * per_token
        bad((ans.reset_time < t_lo_ms + ahead) | (ans.reset_time > t_hi_ms + ahead),
            "reset_time outside the key's window", idx, ans)
    return out


def over_admission(ledgers: list, n_keys: int, keyspec: dict, peers: int) -> dict:
    """What GLOBAL admits beyond a key's limit (module docstring): `keys`,
    the keys whose UNDER_LIMIT answers of warm-up and window carried more
    than limit * peers hits between them (the fill's one granted check
    included), and `excess_hits`, the hits granted beyond `limit` summed
    over all keys, which upstream documents and nothing judges."""
    limit, hits = int(keyspec["limit"]), int(keyspec["hits"])
    granted = np.ones(n_keys, dtype=np.int64)
    for led in ledgers:
        ok = [i for i, r in zip(led.idx, led.resp) if r is not None]
        if ok and len(led.answers.status) == sum(len(i) for i in ok):
            idx = np.concatenate(ok)
            granted += np.bincount(idx[led.answers.status == wirefmt.UNDER], minlength=n_keys)
    granted *= hits
    return {"keys": int((granted > limit * peers).sum()),
            "excess_hits": int(np.maximum(granted - limit, 0).sum())}


# --------------------------------------------- counters after the window


def eviction_allowance(n: int, p: float) -> int:
    """How many of `n` sampled keys may have been evicted live, when the
    server's own count of live evictions is the share `p` of the keys: three
    times the expected n*p, and ten for small counts. Sound runs on the chip
    read 1.03 to 1.06 times n*p over a dozen seeds (PERF.md, section 4); the
    fault this number is there to catch, live keys lost without being
    counted (a wiped or restarted table), reads ten times and more."""
    return int(math.ceil(3.0 * n * min(max(p, 0.0), 1.0) + 10.0))


def eviction_bound(keyspec: dict, n: int, evicted_live: int, n_keys: int) -> int:
    """The limit of `counters_evicted_in_sample`. Token: the allowance above.
    Leaky: a key above expected cannot be told from an evicted one, so the
    allowance is capped by the server's own count, which is a hard bound (an
    eviction lifts at most one key): 0 evictions counted allow 0 keys above."""
    allowance = eviction_allowance(n, evicted_live / n_keys)
    return min(evicted_live, allowance) if is_leaky(keyspec) else allowance


def draw_sample(seed: int, n_keys: int, known: np.ndarray, spec: dict) -> np.ndarray:
    """The keys to read back: the hottest ranks (where a skewed mix puts its
    work) and a uniform draw from the seed, less the keys whose count the
    generator does not know."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    uni = rng.choice(n_keys, size=min(int(spec["sample_uniform"]), n_keys), replace=False)
    hot = np.arange(min(int(spec.get("sample_hot_ranks", 0)), n_keys))
    idx = np.union1d(uni, hot)
    return idx[known[idx]]


async def read_back(door, seed: int, idx: np.ndarray, keyspec: dict, t_peek: int,
                    readings: int = 1):
    """hits=0 on every sampled key, `created_at` pinned to t_peek, 64 RPCs
    in flight. With `readings` > 1 (a GLOBAL keyspace: as many as it has
    peers) every RPC goes out that many times, one straight after another
    and nothing else in flight, so that successive dispatches walk the
    peers (the server gives each the next one); the answer is then a list,
    one `Answers` a reading."""
    limit, dur = int(keyspec["limit"]), int(keyspec["duration_ms"])
    algorithm = wirefmt.keyspec_algorithm(keyspec)
    behavior = wirefmt.keyspec_behavior(keyspec)
    parts = [idx[lo : lo + FILL_RPC_ITEMS] for lo in range(0, len(idx), FILL_RPC_ITEMS)]
    datas = [[None] * len(parts) for _ in range(readings)]
    sem = asyncio.Semaphore(FILL_INFLIGHT if readings == 1 else 1)

    async def one(j: int) -> None:
        async with sem:
            body = wirefmt.request_bytes(
                wirefmt.key_ids(seed, parts[j]), 0, limit, dur, created_at=t_peek,
                algorithm=algorithm, behavior=behavior,
            )
            for r in range(readings):
                datas[r][j] = await door.check_raw(body)

    await gather_all(one(j) for j in range(len(parts)))
    want = np.fromiter((len(p) for p in parts), dtype=np.int64, count=len(parts))
    out = [wirefmt.decode_responses(d) for d in datas]
    for ans in out:
        if not np.array_equal(ans.n_items, want) or ans.errors:
            raise ValueError("a read-back RPC answered with errors or another number of items")
    return out[0] if readings == 1 else out


def judge_global_counters(idx, readings: list, counts, created_ms, keyspec: dict,
                          t_peek: int, t_filled_ms: int) -> dict:
    """`judge_counters` for a GLOBAL keyspace (module docstring): every
    sampled key was read once per peer. `t_filled_ms` is the clock at the
    end of the drain that followed the fill: no key never evicted answers a
    later reset_time less its duration. Counts are of keys; `evicted` counts
    a key once if any peer answered a bucket installed after the fill."""
    limit, hits = int(keyspec["limit"]), int(keyspec["hits"])
    dur = int(keyspec["duration_ms"])
    sent = counts[idx] * hits
    exp_rem = np.maximum(limit - sent, 0)
    past_limit = sent > limit
    born = created_ms[idx // FILL_RPC_ITEMS] + dur
    rem = np.stack([a.remaining for a in readings])
    reset = np.stack([a.reset_time for a in readings])
    status = np.stack([a.status for a in readings])
    fields_ok = np.stack([(a.limit == limit) for a in readings]) & ((status == 0) | (status == 1))
    kept = fields_ok & (reset >= born) & (reset <= t_filled_ms + dur)
    anew = fields_ok & (reset > t_filled_ms + dur) & (reset <= t_peek + dur)
    below = (kept | anew) & (rem < exp_rem)
    above_kept = kept & (rem > exp_rem)
    status_wrong = (kept | anew) & (status == 1) & ~past_limit
    fields_wrong = ~(kept | anew) | (anew & (rem > limit))
    # the readings a key was never evicted in must be one answer
    first = np.argmax(kept, axis=0)
    cols = np.arange(len(idx))
    disagree = kept & ((rem != rem[first, cols]) | (reset != reset[first, cols]))

    def keys(mask):
        return mask.any(axis=0)

    out = {
        "sample": int(len(idx)),
        "readings": len(readings),
        "below_expected": int(keys(below).sum()),
        "above_expected_not_evicted": int(keys(above_kept).sum()),
        "above_on_keys_past_limit": int((keys(above_kept) & past_limit).sum()),
        "status_wrong": int(keys(status_wrong).sum()),
        "fields_wrong": int(keys(fields_wrong).sum()),
        "evicted": int(keys(anew).sum()),
        "evicted_at_every_peer": int(anew.all(axis=0).sum()),
        "replica_disagreements": int(keys(disagree).sum()),
        "examples": [],
    }
    for name, mask in (("below", below), ("above", above_kept), ("status", status_wrong),
                       ("fields", fields_wrong), ("peers disagree", disagree)):
        for j in np.flatnonzero(keys(mask))[:2]:
            out["examples"].append(
                f"{name}: key {int(idx[j])} sent {int(sent[j])} -> by reading: status="
                f"{status[:, j].tolist()} remaining={rem[:, j].tolist()} reset_time-"
                f"duration={(reset[:, j] - dur).tolist()} (fill RPC at {int(born[j] - dur)}, "
                f"fill drained at {t_filled_ms})"
            )
    return out


def judge_counters(idx, ans, counts, created_ms, keyspec: dict, t_peek: int) -> dict:
    """Sort every sampled key into exact / evicted / wrong (module docstring).

    One way of reading above is named apart, `above_on_keys_past_limit`: the
    key was sent more than its limit and still holds 1..limit-1. That is
    what an aggregate refused whole leaves behind (the local engine answers
    copies 8 and up of one key in one dispatch as one check of their summed
    hits, which is refused whole where single checks would have been granted
    in part), when the key then ends the run before the rest is taken. It
    counts as above all the same; the name makes it recognisable (PERF.md,
    section 7)."""
    if is_leaky(keyspec):
        return _judge_leaky_counters(idx, ans, counts, keyspec, t_peek)
    limit, hits = int(keyspec["limit"]), int(keyspec["hits"])
    dur = int(keyspec["duration_ms"])
    sent = counts[idx] * hits
    exp_rem = np.maximum(limit - sent, 0)
    exp_over = sent > limit
    born = created_ms[idx // FILL_RPC_ITEMS] + dur
    fields_ok = (ans.limit == limit) & ((ans.status == 0) | (ans.status == 1))
    kept = fields_ok & (ans.reset_time == born)
    anew = fields_ok & (ans.reset_time > born) & (ans.reset_time <= t_peek + dur)
    below = (kept | anew) & (ans.remaining < exp_rem)
    above_kept = kept & (ans.remaining > exp_rem)
    status_wrong = (
        (kept & (ans.remaining == exp_rem) & ((ans.status == 1) != exp_over))
        | (anew & (ans.status == 1) & ~exp_over)
    )
    over_full = anew & (ans.remaining > limit)
    out = {
        "sample": int(len(idx)),
        "below_expected": int(below.sum()),
        "above_expected_not_evicted": int(above_kept.sum()),
        "above_on_keys_past_limit": int((above_kept & exp_over).sum()),
        "status_wrong": int(status_wrong.sum()),
        "fields_wrong": int((~(kept | anew)).sum() + over_full.sum()),
        "evicted": int(anew.sum()),
        "examples": [],
    }
    for name, mask in (("below", below), ("above", above_kept & ~exp_over),
                       ("above, past its limit (an aggregate refused whole?)",
                        above_kept & exp_over),
                       ("status", status_wrong), ("fields", ~(kept | anew))):
        for j in np.flatnonzero(mask)[:2]:
            out["examples"].append(
                f"{name}: key {int(idx[j])} sent {int(sent[j])} -> status="
                f"{int(ans.status[j])} remaining={int(ans.remaining[j])} "
                f"reset_time={int(ans.reset_time[j])} (installed reset {int(born[j])})"
            )
    return out


def _judge_leaky_counters(idx, ans, counts, keyspec: dict, t_peek: int) -> dict:
    """The same sorting for a leaky keyspace, by `remaining` alone (module
    docstring): exact / below / above, where every key above counts as
    `evicted` (a hit lost or a live eviction: `eviction_bound` says how many
    there may be) and none can be shown `above_expected_not_evicted`."""
    limit, hits = int(keyspec["limit"]), int(keyspec["hits"])
    per_token = leaky_ms_per_token(keyspec)
    sent = counts[idx] * hits
    exp_rem = np.maximum(limit - sent, 0)
    exp_over = sent > limit
    fields_ok = (
        (ans.limit == limit) & ((ans.status == 0) | (ans.status == 1))
        & (ans.remaining >= 0) & (ans.remaining <= limit)
        & (ans.reset_time == t_peek + (limit - ans.remaining) * per_token)
    )
    below = fields_ok & (ans.remaining < exp_rem)
    above = fields_ok & (ans.remaining > exp_rem)
    status_wrong = fields_ok & (ans.status != 0)  # hits = 0 is never over the limit
    out = {
        "sample": int(len(idx)),
        "below_expected": int(below.sum()),
        "above_expected_not_evicted": 0,
        "above_on_keys_past_limit": int((above & exp_over).sum()),
        "status_wrong": int(status_wrong.sum()),
        "fields_wrong": int((~fields_ok).sum()),
        "evicted": int(above.sum()),
        "examples": [],
    }
    for name, mask in (("below", below), ("above (a hit lost, or the key evicted live)", above),
                       ("status", status_wrong), ("fields", ~fields_ok)):
        for j in np.flatnonzero(mask)[:2]:
            out["examples"].append(
                f"{name}: key {int(idx[j])} sent {int(sent[j])} -> status="
                f"{int(ans.status[j])} remaining={int(ans.remaining[j])} "
                f"reset_time={int(ans.reset_time[j])} (peek at {t_peek}, "
                f"{per_token} ms a token)"
            )
    return out
