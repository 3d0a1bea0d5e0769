"""A plain reference of the tiered deployment: the bounded table with a store
behind it, which forgets nothing.

Upstream's cache is an LRU of `GUBER_CACHE_SIZE` items (lrucache.go) and may
have a `Store` behind it (store.go): `OnChange` hands the store every change,
`Get` asks it on a cache miss. A peer set up so answers, for every key it has
ever seen and however small its cache, what a peer with an unbounded cache
would: the cache's size and what it pushes out change no answer. The device
table is that cache cut into buckets of K = 8 lanes, a key living only in
bucket `fingerprint % n_buckets` (tests/oracle/bounded_table.py), and the
host-RAM shadow is the store. Here the store is a set over one dictionary of
token buckets (`tests/oracle/algos.TokenOracle`, whose state is never
dropped: that IS the unbounded table), and the lanes say which keys are
resident:

* a key that is resident is decided where it lies, and its lane is touched
  (`touch` = the check's clock in units of 1,024 ms);
* a key that is not takes the lane that is cheapest to give up — an empty
  lane, then a dead one, then the live lane touched longest ago (upstream's
  least recently used), the soonest to expire among lanes touched in the same
  unit, the lowest lane among equals. A live key that loses its lane is
  DEMOTED: its token bucket goes to the store. A key found in the store is
  PROMOTED into its lane, its token bucket as it was, and then decided.
  Nothing is ever lost, so `lost` stays 0.

A dispatch is the checks that arrived together (`check_together`). Copies of
one key are decided one after another, copy 7 and up as ONE check of their
summed hits whose answer every one of them gets (the local engine's
aggregate; ops/plan.py). Checks of different keys are concurrent, so any
order is an answer; which keys end up resident depends on it, and the order
taken here is the device's: first the dispatch's stored keys are promoted,
each bucket giving its K cheapest lanes to the first K of them, in the order
of their keys (a ninth stays in the store); then, pass by pass, the keys the
table now holds; then the others in rounds — the stored ones (a ninth, a key
of this dispatch that a promote pushed out) are promoted by the same rule (a
ninth waits for the next round); then the round's keys are decided,
the promoted ones where they now lie, a new key on the cheapest lane of its
bucket as it was when the round's decisions began, unless that lane belongs
to a key decided in the same round (then the newcomer waits a round: a
newcomer never pushes out a key whose check rides with it before that check
is served).

Written from that description and from algorithms.go, in plain Python; it
imports nothing of the package (the caller brings the fingerprints: what a
bucket is chosen by is part of the contract, as the consistent hash is
upstream). `tests/test_tiered_deployment.py` holds `LocalEngine` with a
shadow attached to it, dispatch by dispatch.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

from tests.oracle.algos import TokenOracle

K = 8  # lanes a bucket
MAX_EXACT = 8  # copies 0..6 of a key one after another, 7 and up as one
Answer = Tuple[int, int, int]


def touch_tick(now: int) -> int:
    return now >> 10


class StoredTable:
    def __init__(self, n_buckets: int):
        self.n_buckets = n_buckets
        self.buckets: Dict[int, List[Optional[int]]] = {}
        self.touch: Dict[int, int] = {}  # resident fp -> its lane's touch
        self.tokens = TokenOracle()  # every key ever seen: the unbounded table
        self.store: Set[int] = set()  # fps whose token bucket is in the store
        self.demoted = 0  # live keys that lost their lane to the store
        self.promoted = 0  # keys that came back from it
        self.promoted_ahead = 0  # of those, ahead of their dispatch's passes
        self.returned = 0  # promotes that found no lane and waited a round
        self.lost = 0  # live keys whose count is gone: never

    # ------------------------------------------------------------- the lanes
    def holds(self, fp: int) -> bool:
        return fp in self.buckets.get(fp % self.n_buckets, ())

    def _lanes(self, fp: int) -> List[Optional[int]]:
        return self.buckets.setdefault(fp % self.n_buckets, [None] * K)

    def _cheapest(self, lanes: List[Optional[int]], now: int) -> List[int]:
        """Lanes in the order a bucket gives them up."""
        def cost(j: int):
            fp = lanes[j]
            if fp is None:
                return (0, 0, 0, j)
            exp = self.tokens.state[fp][1]
            return (int(exp >= now), self.touch[fp], exp, j)

        return sorted(range(K), key=cost)

    def _take_lane(self, lanes: List[Optional[int]], j: int, fp: int, now: int) -> None:
        old = lanes[j]
        if old is not None:
            del self.touch[old]
            if self.tokens.state[old][1] >= now:
                self.demoted += 1
                self.store.add(old)
            else:
                del self.tokens.state[old]  # dead: nothing to keep
        lanes[j] = fp
        self.touch[fp] = touch_tick(now)

    def _promote(self, back: List[int], now: int) -> Set[int]:
        """One launch's promotes: the stored keys `back` come back in the
        order of their keys, K a bucket at most. Returns those that found
        no lane and wait, back in the store."""
        waits: Set[int] = set()
        ranks: Dict[int, int] = {}
        order: Dict[int, List[int]] = {}
        for fp in back:
            b = fp % self.n_buckets
            if b not in order:
                order[b] = self._cheapest(self._lanes(fp), now)
            r = ranks[b] = ranks.get(b, -1) + 1
            if r >= K:
                waits.add(fp)
                self.returned += 1
        # (two steps: every lane ranked first, then the moves, as one
        # launch does)
        ranks = {}
        for fp in back:
            if fp in waits:
                continue
            b = fp % self.n_buckets
            r = ranks[b] = ranks.get(b, -1) + 1
            self.store.discard(fp)
            self._take_lane(self._lanes(fp), order[b][r], fp, now)
            self.promoted += 1
        return waits

    # -------------------------------------------------------------- one pass
    def _decide(self, fp: int, now: int, hits: int, limit: int, duration: int) -> Answer:
        self.touch[fp] = touch_tick(now)
        return self.tokens.check(fp, now, hits, limit, duration)

    def _held(self, items: Sequence[Tuple[int, int, int]], now: int, limit: int,
              duration: int, out: Dict[int, Answer]) -> List[Tuple[int, int, int]]:
        """A pass over the keys the table holds: decided where they lie.
        Returns the others, for `_faulted`."""
        todo = []
        for row, fp, hits in items:
            if self.holds(fp):
                out[row] = self._decide(fp, now, hits, limit, duration)
            else:
                todo.append((row, fp, hits))
        return todo

    def _faulted(self, todo: List[Tuple[int, int, int]], now: int, limit: int,
                 duration: int, out: Dict[int, Answer]) -> None:
        """The keys a pass did not find in the table, in rounds."""
        while todo:
            waits = self._promote(
                sorted(fp for _row, fp, _hits in todo if fp in self.store), now
            )
            # a key of this round that a promote has just pushed out waits
            # for the next round, where it is promoted in its turn
            waits |= {fp for _row, fp, _hits in todo if fp in self.store}
            # the round's decisions: lanes ranked as the buckets now stand
            run = [x for x in todo if x[1] not in waits]
            owned = {(fp % self.n_buckets, self._lanes(fp).index(fp))
                     for _r, fp, _h in run if self.holds(fp)}
            order, ranks, claim = {}, {}, {}
            for _row, fp, _hits in run:
                if self.holds(fp):
                    continue
                b = fp % self.n_buckets
                if b not in order:
                    order[b] = self._cheapest(self._lanes(fp), now)
                r = ranks[b] = ranks.get(b, -1) + 1
                if r < K and (b, order[b][r]) not in owned:
                    claim[fp] = order[b][r]
            again = [x for x in todo if x[1] in waits]
            for row, fp, hits in run:
                if not self.holds(fp):
                    if fp not in claim:
                        again.append((row, fp, hits))
                        continue
                    self._take_lane(self._lanes(fp), claim[fp], fp, now)
                out[row] = self._decide(fp, now, hits, limit, duration)
            again.sort()
            todo = again

    # ---------------------------------------------------------- one dispatch
    def check_together(self, fps: Sequence[int], now: int, hits: int, limit: int,
                       duration: int) -> List[Answer]:
        """The checks of one dispatch, a key as often as it likes; answers
        in arrival order."""
        seen: Dict[int, int] = {}
        passes: List[List[Tuple[int, int, int]]] = [[] for _ in range(MAX_EXACT - 1)]
        tail: Dict[int, List[int]] = {}
        for row, fp in enumerate(fps):
            c = seen[fp] = seen.get(fp, -1) + 1
            if c < MAX_EXACT - 1:
                passes[c].append((row, fp, hits))
            else:
                tail.setdefault(fp, []).append(row)
        if tail:
            # one check of the summed hits, carried by the newest member;
            # the aggregates go in the order of their keys
            passes.append([
                (rows[-1], fp, hits * len(rows)) for fp, rows in sorted(tail.items())
            ])
        # the dispatch's stored keys come back first, ahead of its passes;
        # every pass then meets the table as that left it (the pipelined
        # launches create nothing and push nothing out), and only then are
        # the keys it did not hold faulted in, pass after pass
        before = self.promoted
        self._promote(sorted({fp for fp in fps if fp in self.store}), now)
        self.promoted_ahead += self.promoted - before
        got: Dict[int, Answer] = {}
        left = [self._held(items, now, limit, duration, got) for items in passes]
        for todo in left:
            self._faulted(todo, now, limit, duration, got)
        answers: List[Optional[Answer]] = [got.get(row) for row in range(len(fps))]
        for rows in tail.values():
            for row in rows:
                answers[row] = got[rows[-1]]
        return answers  # type: ignore[return-value]

    def peek(self, fp: int, now: int, limit: int, duration: int) -> Answer:
        """What the key holds, wherever it lies; moves nothing."""
        item = self.tokens.state.get(fp)
        if item is None or item[1] < now:
            return 0, limit, now + duration
        return item[2], item[0], item[1]
