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
"""

from __future__ import annotations

import asyncio
import math
import time

import numpy as np

import wirefmt
from oracles import LeakyOracle, TokenOracle

FILL_RPC_ITEMS = 1_000  # upstream's batch cap
FILL_INFLIGHT = 64
FRESH_KEYS_PER_SCRIPT = 200


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


async def gather_all(coros) -> None:
    """Await every task; the first failure cancels the rest and raises."""
    tasks = [asyncio.ensure_future(c) for c in coros]
    try:
        await asyncio.gather(*tasks)
    finally:
        for t in tasks:
            t.cancel()


# ------------------------------------------------------------------- fill


async def fill(door, seed: int, keyspec: dict) -> dict:
    """One check with the cell's `hits` per live key, `created_at` pinned
    per RPC, 64 RPCs in flight. Every answer must be UNDER_LIMIT with
    remaining == limit - hits and reset_time == created_at + duration; the
    usual answer is compared as bytes and only another is decoded."""
    n_keys, limit = int(keyspec["keys"]), int(keyspec["limit"])
    hits, duration = int(keyspec["hits"]), int(keyspec["duration_ms"])
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
                duration, created_at=t,
            )
            data = await door.check_raw(body)
            want = (wirefmt.UNDER, limit, limit - hits, t + duration)
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


def fresh_scenarios(seed: int, n_per: int, t0: int, dup_aggregates: bool, duration: int):
    """Scripts over fresh keys: a list of steps, each a list of (item bytes,
    limit, expected (status, remaining, reset_time), label) that goes out
    as ONE RPC, steps strictly one after another. Expectations come from the
    plain oracles. `dup_aggregates`: what the same key several times in one
    batch means on the engine under test — the local engine decides them one
    after another; the mesh's in-trace dedup aggregates them into one
    decision whose answer every copy gets (upstream's own GLOBAL hot-key
    rule, docs/architecture.md). `duration` is twice the bulk keys', so a
    fresh key can evict a bulk key and never another fresh key."""
    tok, leak = TokenOracle(), LeakyOracle()
    D = duration
    R, O = wirefmt.RESET_REMAINING, wirefmt.DRAIN_OVER_LIMIT

    def item(name, k, hits, limit, algo=wirefmt.TOKEN, behavior=0, at=t0, duration=D):
        return wirefmt.encode_item(
            name, f"s{seed}-{k}", hits, limit, duration, algo, behavior, at
        )

    def tstep(name, script):
        steps = []
        for s, (hits, limit, beh) in enumerate(script):
            step = []
            for k in range(n_per):
                exp = tok.check(
                    (name, k), t0 + s, hits, limit, D,
                    reset=bool(beh & R), drain=bool(beh & O),
                )
                step.append((
                    item(name, k, hits, limit, behavior=beh, at=t0 + s), limit,
                    exp, f"{name}/{k} step {s} hits={hits} behavior={beh}",
                ))
            steps.append(step)
        return steps

    groups = [
        # down to OVER_LIMIT and past it; the at-limit status is sticky
        tstep("drain", [(2, 5, 0), (2, 5, 0), (2, 5, 0), (1, 5, 0), (1, 5, 0), (0, 5, 0)]),
        tstep("reset", [(3, 5, 0), (1, 5, R), (1, 5, 0)]),
        tstep("drainover", [(3, 5, 0), (4, 5, O), (0, 5, 0), (1, 5, 0)]),
        tstep("peek", [(0, 7, 0), (1, 7, 0), (0, 7, 0)]),
        # hits >= 2^18 cannot ride the compact wire: the full-width fallback
        tstep("wide", [(1 << 18, 1 << 20, 0), (1 << 18, 1 << 20, 0), (0, 1 << 20, 0)]),
    ]
    # leaky bucket across a pinned created_at step: rate = 60000/10 = 6000
    # ms per token, so +15000 ms leaks 2.5 tokens (never a borderline value)
    lk = []
    for s, (hits, dt, beh) in enumerate(
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
    groups.append(lk)
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


async def run_scenarios(door, groups) -> dict:
    out = {"compared": 0, "mismatches": 0, "examples": []}
    for steps in groups:
        for step in steps:
            for lo in range(0, len(step), FILL_RPC_ITEMS):
                part = step[lo : lo + FILL_RPC_ITEMS]
                data = await door.check_raw(b"".join(p[0] for p in part))
                rows = wirefmt.decode_response_slow(data)
                if len(rows) != len(part):
                    out["mismatches"] += len(part)
                    out["examples"].append(f"{len(rows)} answers for {len(part)} items")
                    continue
                for (_b, limit, exp, label), row in zip(part, rows):
                    out["compared"] += 1
                    if row[4] or row[1] != limit or (row[0], row[2], row[3]) != exp:
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
    reset_time inside [first fill, now] + duration."""
    limit, hits = int(keyspec["limit"]), int(keyspec["hits"])
    dur = int(keyspec["duration_ms"])
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
        bad((ans.reset_time < t_lo_ms + dur) | (ans.reset_time > t_hi_ms + dur),
            "reset_time outside the key's window", idx, ans)
    return out


# --------------------------------------------- counters after the window


def eviction_allowance(n: int, p: float) -> int:
    """How many of `n` sampled keys may have been evicted live, when the
    server's own count of live evictions is the share `p` of the keys: three
    times the expected n*p, and ten for small counts. Sound runs on the chip
    read 1.03 to 1.06 times n*p over a dozen seeds (PERF.md, section 4); the
    fault this number is there to catch, live keys lost without being
    counted (a wiped or restarted table), reads ten times and more."""
    return int(math.ceil(3.0 * n * min(max(p, 0.0), 1.0) + 10.0))


def draw_sample(seed: int, n_keys: int, known: np.ndarray, spec: dict) -> np.ndarray:
    """The keys to read back: the hottest ranks (where a skewed mix puts its
    work) and a uniform draw from the seed, less the keys whose count the
    generator does not know."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    uni = rng.choice(n_keys, size=min(int(spec["sample_uniform"]), n_keys), replace=False)
    hot = np.arange(min(int(spec.get("sample_hot_ranks", 0)), n_keys))
    idx = np.union1d(uni, hot)
    return idx[known[idx]]


async def read_back(door, seed: int, idx: np.ndarray, keyspec: dict, t_peek: int):
    """hits=0 on every sampled key, `created_at` pinned to t_peek."""
    limit, dur = int(keyspec["limit"]), int(keyspec["duration_ms"])
    parts = [idx[lo : lo + FILL_RPC_ITEMS] for lo in range(0, len(idx), FILL_RPC_ITEMS)]
    datas: list = [None] * len(parts)
    sem = asyncio.Semaphore(FILL_INFLIGHT)

    async def one(j: int) -> None:
        async with sem:
            datas[j] = await door.check_raw(wirefmt.request_bytes(
                wirefmt.key_ids(seed, parts[j]), 0, limit, dur, created_at=t_peek
            ))

    await gather_all(one(j) for j in range(len(parts)))
    ans = wirefmt.decode_responses(datas)
    want = np.fromiter((len(p) for p in parts), dtype=np.int64, count=len(parts))
    if not np.array_equal(ans.n_items, want) or ans.errors:
        raise ValueError("a read-back RPC answered with errors or another number of items")
    return ans


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
