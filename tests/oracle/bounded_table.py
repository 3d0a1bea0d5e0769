"""A plain reference of the bounded table: what a peer that holds its token
buckets in a fixed number of 8-lane hash buckets answers, one check after
another, and which live keys it forgets on the way.

Upstream's cache is an LRU of `GUBER_CACHE_SIZE` items (lrucache.go); the
device table is that cache cut into buckets: a key may live only in the
bucket its fingerprint names, `fingerprint % n_buckets`, and a bucket has
K = 8 lanes. A key that is in its bucket keeps its lane (an expired one
starts a fresh count there). A key that is not takes the lane that is
cheapest to give up: an empty lane, then a dead one (its item expired), the
longest dead first, and only then a live one, the soonest to expire, lowest
lane first among equals. Taking a live lane forgets a live key: that is an
eviction, counted in `evicted_live_total` and remembered in `evicted`.
Inside a lane sits upstream's token bucket, `tests/oracle/algos.TokenOracle`
(one `state` entry a key; the entry goes when the lane does).

Written from that description and from algorithms.go, in plain Python over a
dictionary; it imports nothing of the package but the key's fingerprint
(what a bucket is chosen by is part of the contract, as the consistent hash
is upstream). `tests/test_table_scale.py` holds `LocalEngine` to it,
dispatch by dispatch (`check_together`).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from tests.oracle.algos import TokenOracle

K = 8  # lanes a bucket


class BoundedTable:
    def __init__(self, n_buckets: int):
        self.n_buckets = n_buckets
        self.buckets: Dict[int, List[Optional[int]]] = {}
        self.tokens = TokenOracle()  # state: fp -> (remaining, expire_at, status)
        self.evicted_live_total = 0
        self.evicted: List[int] = []  # fingerprints, in the order they went

    def _lane_for(self, lanes: List[Optional[int]], now: int) -> int:
        """The lane a new key takes: (live?, expire_at, lane) ascending; an
        empty lane counts as expired at 0."""
        def cost(j: int) -> Tuple[int, int, int]:
            fp = lanes[j]
            if fp is None:
                return (0, 0, j)
            exp = self.tokens.state[fp][1]
            return (int(exp >= now), exp, j)

        return min(range(K), key=cost)

    def check(
        self, fp: int, now: int, hits: int, limit: int, duration: int,
        reset: bool = False, drain: bool = False,
    ) -> Tuple[int, int, int]:
        """One check: (status, remaining, reset_time)."""
        lanes = self.buckets.setdefault(fp % self.n_buckets, [None] * K)
        if fp in lanes:
            j = lanes.index(fp)
        else:
            j = self._lane_for(lanes, now)
            old = lanes[j]
            if old is not None:
                if self.tokens.state[old][1] >= now:
                    self.evicted_live_total += 1
                    self.evicted.append(old)
                del self.tokens.state[old]
            lanes[j] = fp
        answer = self.tokens.check(fp, now, hits, limit, duration, reset=reset, drain=drain)
        if fp not in self.tokens.state:  # RESET_REMAINING removed the item
            lanes[j] = None
        return answer

    def check_together(
        self, fps, now: int, hits: int, limit: int, duration: int
    ) -> List[Tuple[int, int, int]]:
        """One dispatch: checks that arrived together, no key twice. They are
        concurrent, so any order is an answer; the device's is the keys the
        table holds before the keys it has to install, each group as it
        arrived (a newcomer never pushes out a key whose check rides in the
        same dispatch before that check is served). Answers in arrival
        order."""
        order = sorted(range(len(fps)), key=lambda j: not self.holds(fps[j]))
        answers: List[Optional[Tuple[int, int, int]]] = [None] * len(fps)
        for j in order:
            answers[j] = self.check(fps[j], now, hits, limit, duration)
        return answers

    def holds(self, fp: int) -> bool:
        """Whether the key has a lane (live or dead)."""
        return fp in self.buckets.get(fp % self.n_buckets, ())
