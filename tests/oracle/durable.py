"""The plain durable reference: a rate limiter that keeps its counters in a
dict, copies the dict at every checkpoint, and after a crash wakes up with
the copy.

None of the program's code: the algorithms are tests/oracle/algos.py (token
and leaky bucket after upstream's algorithms.go), the durability is
`copy.deepcopy`. It states what docs/durability.md promises of a peer with
the incremental checkpoint plane armed:

* before a crash every answer is the uncrashed limiter's;
* after an unclean death and a restart a key holds what it held at the last
  completed checkpoint: every hit admitted before it is remembered, the hits
  admitted since (`since[key]`) are granted again and nothing more.

A test or ci/chip_durable.py feeds two of these the same checks, crashes one,
and holds the restarted server between them: no key below the one that never
crashed (a hit counted twice), none above the crashed one (a hit the
checkpoint should have held).
"""

from __future__ import annotations

import copy
from typing import Dict, Tuple

from tests.oracle.algos import LeakyOracle, TokenOracle

_ALGOS = {"token": TokenOracle, "leaky": LeakyOracle}


class DurableOracle:
    def __init__(self, algorithm: str = "token"):
        self.live = _ALGOS[algorithm]()
        self._saved: dict = {}
        # hits admitted per key since the last checkpoint: what a crash
        # now would grant again
        self.since: Dict[object, int] = {}
        self.checkpoints = 0

    def check(self, key, now, hits, limit, duration, **kw) -> Tuple[int, int, int]:
        status, remaining, reset = self.live.check(
            key, now, hits, limit, duration, **kw
        )
        if hits > 0 and status == 0:
            self.since[key] = self.since.get(key, 0) + hits
        return status, remaining, reset

    def checkpoint(self) -> None:
        """Everything admitted so far is durable."""
        self._saved = copy.deepcopy(self.live.state)
        self.since.clear()
        self.checkpoints += 1

    def crash(self) -> None:
        """Unclean death and restart: the last checkpoint's state is all
        there is."""
        self.live.state = copy.deepcopy(self._saved)
        self.since.clear()
