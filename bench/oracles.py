"""The plain reference: token and leaky bucket after upstream's algorithms.go.

Copied from `tests/oracle/algos.py` at commit 846d0923 (`TokenOracle`,
`LeakyOracle`, unchanged below the imports) so that a later change to the
repository's test oracle cannot move the benchmark's yardstick. Pure Python,
imports nothing of the program.
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
