"""The plain reference: token and leaky bucket after upstream's algorithms.go,
and GLOBAL's contract over the token bucket.

`TokenOracle` and `LeakyOracle` are copied from `tests/oracle/algos.py` at
commit 846d0923 (unchanged below the imports) so that a later change to the
repository's test oracle cannot move the benchmark's yardstick.
`GlobalOracle` (PR 46) is written from upstream's gubernator.go and global.go
on top of `TokenOracle`, not from the program. Pure Python, imports nothing
of the program.
"""

from __future__ import annotations

from typing import Dict, Tuple


class TokenOracle:
    """Token bucket after upstream's algorithms.go `tokenBucket`, written
    from the Go source and independent of ops/math.py: per-key (remaining,
    expire_at, sticky status), lazy expiry (`expire_at < now` is gone),
    RESET_REMAINING (removes the item, answers a full bucket with reset 0),
    DRAIN_OVER_LIMIT (an over-ask empties the bucket), `hits == 0` peeks.
    Constant limit/duration per key — the config-change branches of the Go
    code are out of scope. Returns (status, remaining, reset_time)."""

    def __init__(self):
        self.state: Dict[int, Tuple[int, int, int]] = {}  # key -> (rem, exp, status)

    def check(
        self, key, now, hits, limit, duration, reset=False, drain=False
    ) -> Tuple[int, int, int]:
        item = self.state.get(key)
        if item is not None and item[1] < now:
            item = None
        if item is not None and reset:
            del self.state[key]
            return 0, limit, 0
        if item is None:
            exp = now + duration
            if hits > limit:
                self.state[key] = (limit, exp, 0)
                return 1, limit, exp
            self.state[key] = (limit - hits, exp, 0)
            return 0, limit - hits, exp
        rem, exp, status = item
        if hits == 0:
            return status, rem, exp
        if rem == 0 and hits > 0:
            self.state[key] = (rem, exp, 1)  # the one branch that persists OVER
            return 1, rem, exp
        if hits == rem:
            self.state[key] = (0, exp, status)
            return status, 0, exp
        if hits > rem:
            if drain:
                self.state[key] = (0, exp, status)
                return 1, 0, exp
            return 1, rem, exp
        self.state[key] = (rem - hits, exp, status)
        return status, rem - hits, exp


class LeakyOracle:
    """Leaky bucket after upstream's algorithms.go `leakyBucket`: float
    remaining, `rate = duration / limit` ms per token, a leak applied only
    once a whole token has leaked, Go's truncating int64(float) at every
    comparison, burst defaulting to limit. Constant limit/duration/burst
    per key. Returns (status, remaining, reset_time)."""

    def __init__(self):
        self.state: Dict[int, Tuple[float, int, int]] = {}  # key -> (rem, updated, exp)

    def check(
        self, key, now, hits, limit, duration, burst=0, reset=False, drain=False
    ) -> Tuple[int, int, int]:
        burst = burst or limit
        rate = duration / limit
        irate = int(rate)
        item = self.state.get(key)
        if item is not None and item[2] < now:
            item = None
        if item is None:
            if hits > burst:
                self.state[key] = (0.0, now, now + duration)
                return 1, 0, now + limit * irate
            self.state[key] = (float(burst - hits), now, now + duration)
            return 0, burst - hits, now + (limit - (burst - hits)) * irate
        rem, updated, exp = item
        if reset:
            rem = float(burst)
        if hits != 0:
            exp = now + duration
        leak = (now - updated) / rate
        if int(leak) > 0:
            rem += leak
            updated = now
        if int(rem) > burst:
            rem = float(burst)
        irem = int(rem)
        reset_time = now + (limit - irem) * irate
        status = 0
        if irem == 0 and hits > 0:
            status = 1
        elif irem == hits:
            rem, irem = 0.0, 0
            reset_time = now + limit * irate
        elif hits > irem:
            status = 1
            if drain:
                rem, irem = 0.0, 0
        elif hits != 0:
            rem -= hits
            irem = int(rem)
            reset_time = now + (limit - irem) * irate
        self.state[key] = (rem, updated, exp)
        return status, irem, reset_time


class GlobalOracle:
    """What a client may hold a cluster to when a key's checks carry GLOBAL
    and the cluster is drained between them (every queued hit applied by
    its owner, the owner's broadcast installed by every peer), after
    upstream's gubernator.go `getGlobalRateLimit` / `getLocalRateLimit` and
    global.go. Token buckets only; every transition is `TokenOracle`'s.

    A GLOBAL check is answered by the peer it reaches "as if it owned the
    key", from the state that peer holds: `answers` is the plain oracle's
    answer on that state. What the key's state is afterwards depends on
    which peer that was, and a client cannot know:
      the owner       applied the check itself, with the request's own flags
                      and its pinned `created_at`, and broadcasts the result;
      another peer    queued the hits; the owner applies their sum at its
                      next sync tick, on ITS clock (somewhere between the
                      send and the end of the drain: `applied`), with
                      DRAIN_OVER_LIMIT forced (gubernator.go:526-532), and
                      every other peer installs the owner's ANSWER: its
                      status too, which after an over-ask is OVER_LIMIT
                      where the owner's own item keeps the status it had.
    hits = 0 is never queued (global.go:85-95): a peek changes nothing
    anywhere, except that the peer it reached now holds a full bucket for a
    key it had never seen.
    So the oracle carries every state some peer may hold, an answer is right
    if any of them admits it, and a reset_time fixed by an owner's tick is an
    interval. Where no over-ask and no peek of an unseen key happened there
    is one state, and the answer is the plain oracle's to the digit."""

    def __init__(self):
        # key -> set of states some peer may hold: None (never heard of the
        # key) or (remaining, status, reset_lo, reset_hi)
        self.alts: Dict[object, set] = {}

    @staticmethod
    def _step(state, at_lo, at_hi, hits, limit, duration, drain):
        """TokenOracle on one state, at a clock known as [at_lo, at_hi].
        Returns (answer, state afterwards), the answer as (status, remaining,
        reset_lo, reset_hi)."""
        tok = TokenOracle()
        if state is not None:
            tok.state[0] = (state[0], state[2], state[1])
        status, rem, _ = tok.check(0, at_lo, hits, limit, duration, drain=drain)
        lo, hi = (at_lo + duration, at_hi + duration) if state is None else state[2:]
        rem_after, _, status_after = tok.state[0]
        return (status, rem, lo, hi), (rem_after, status_after, lo, hi)

    def answers(self, key, at, hits, limit, duration, drain=False) -> list:
        """Every (status, remaining, reset_lo, reset_hi) the contract admits
        for this check, pinned at `created_at` = `at`."""
        states = self.alts.get(key, {None})
        return sorted({self._step(s, at, at, hits, limit, duration, drain)[0] for s in states})

    def settle(self, key, at, hits, limit, duration, drain, applied) -> None:
        """The cluster has drained since that check went out: `applied` is
        (lo, hi) of the clock between its send and the drain's end."""
        states = self.alts.get(key, {None})
        if hits == 0:
            self.alts[key] = states | {
                self._step(s, at, at, 0, limit, duration, drain)[1] for s in states}
            return
        after = set()
        for s in states:
            after.add(self._step(s, at, at, hits, limit, duration, drain)[1])
            ans, own = self._step(s, *applied, hits, limit, duration, True)
            after.update({own, (own[0], ans[0], *own[2:])})
        self.alts[key] = after
