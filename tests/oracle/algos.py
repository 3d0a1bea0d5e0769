"""Pure-Python reference oracles for the extended in-kernel algorithms.

One class per algorithm, dict-of-key state, integer-millisecond arithmetic
mirroring the masked decision tables in ops/math.py EXACTLY (same rounding,
same clamps, same expiry rules) — the parity contract every device
implementation (local + 8-dev mesh, full + compact wire) is tested against
in tests/test_algorithms.py: GCRA, sliding-window counters, concurrency
leases. TokenOracle and LeakyOracle follow upstream's algorithms.go instead
and import nothing from the package, so a process that must stay off the
device (chip_smoke.py's parent) can use them; the JAX token/leaky oracle is
tests/oracle/kernel_v1.py (the v1 plane kernel).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple


def _clip(v: int, lo: int, hi: int) -> int:
    return max(lo, min(v, hi))


@dataclass
class GcraOracle:
    """Virtual scheduling: one theoretical-arrival-time (TAT) per key.

    T = duration // limit (ms per token), tau = T * burst. State is
    self-expiring — once now >= TAT the bucket is indistinguishable from a
    fresh one, which is exactly how the kernel's ExpireAt = TAT interacts
    with lazy expiry, so the oracle needs no explicit expiry handling:
    max(TAT, now) covers both."""

    tat: Dict[int, int] = field(default_factory=dict)

    def check(
        self, key: int, now: int, hits: int, limit: int, duration: int,
        burst: int = 0, drain: bool = False,
    ) -> Tuple[int, int, int]:
        burst = burst or limit
        T = max(duration // max(limit, 1), 1)
        tau = T * burst
        stored = self.tat.get(key)
        if hits < 0 and (stored is None or stored < now):
            # miss-release (ops/math.py neg_miss): a return against a key
            # with no live TAT removes instead of installing — full
            # bucket, reset 0
            self.tat.pop(key, None)
            return (0, burst, 0)
        tat0 = max(self.tat.get(key, now), now)
        # releases rewind the TAT but never below now (the GCRA analog of
        # the token clamp at `limit`)
        tat1 = max(tat0 + hits * T, now)
        deny = hits > 0 and tat1 - tau > now
        if deny:
            out = now + tau if drain else tat0
        else:
            out = tat1
        self.tat[key] = out
        rem = _clip((now + tau - out) // T, 0, burst)
        reset = out - tau + T * limit
        if deny and not drain:
            # exact conforming instant for the denied request (the
            # TAT-derived retry_after bound, ops/math.py gcra_lanes)
            reset = tat1 - tau
        return (1 if deny else 0, rem, reset)


@dataclass
class SlidingWindowOracle:
    """Previous+current window interpolation; windows align to duration
    boundaries. State: (window_start, current_count, previous_count)."""

    state: Dict[int, Tuple[int, int, int]] = field(default_factory=dict)

    def check(
        self, key: int, now: int, hits: int, limit: int, duration: int,
        drain: bool = False,
    ) -> Tuple[int, int, int]:
        dur = max(duration, 1)
        ws = now - now % dur
        s_ws, s_cur, s_prev = self.state.get(key, (None, 0, 0))
        if hits < 0 and (s_ws is None or now >= s_ws + 2 * dur):
            # miss-release: the slot (exp = ws + 2·dur) is gone — remove,
            # never install fresh state from a return (ops/math.py)
            self.state.pop(key, None)
            return (0, limit, 0)
        if s_ws == ws:
            cur, prev = s_cur, s_prev
        elif s_ws == ws - dur:
            cur, prev = 0, s_cur
        else:  # stale beyond one window (== the slot's ws+2dur expiry)
            cur, prev = 0, 0
        used = cur + (prev * (dur - (now - ws))) // dur
        deny = hits > 0 and used + hits > limit
        take = 0 if (deny and not drain) else hits
        # releases clamp at an empty window — a return can never drive the
        # stored count negative (remaining past `limit`)
        cur = max(cur + take, 0)
        self.state[key] = (ws, cur, prev)
        rem = _clip(limit - (used + take), 0, limit)
        return (1 if deny else 0, rem, ws + dur)


@dataclass
class LeaseOracle:
    """Concurrency leases: hits>0 acquires, hits<0 releases, 0 queries.
    State: (inflight, expire_at); an expired slot reclaims every lease —
    the TTL-eviction reclamation contract."""

    state: Dict[int, Tuple[int, int]] = field(default_factory=dict)

    def check(
        self, key: int, now: int, hits: int, limit: int, duration: int,
        drain: bool = False,
    ) -> Tuple[int, int, int]:
        inflight, exp = self.state.get(key, (0, None))
        if exp is None or exp < now:  # lazy expiry (exp >= now keeps it live)
            inflight, exp = 0, None
        if hits < 0 and exp is None:
            # miss-release: a late release after TTL reclamation (or of a
            # never-seen key) removes instead of installing — the
            # miss-safety rule (ops/math.py neg_miss)
            self.state.pop(key, None)
            return (0, limit, 0)
        deny = hits > 0 and inflight + hits > limit
        take = 0 if (deny and not drain) else hits
        inflight = max(inflight + take, 0)
        refresh = hits > 0 and not (deny and not drain)
        if refresh or exp is None:
            exp = now + duration
        self.state[key] = (inflight, exp)
        rem = _clip(limit - inflight, 0, limit)
        return (1 if deny else 0, rem, exp)


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
