#!/usr/bin/env python3
"""The control: the served path with one stated guarantee broken, run at the
cell's own size, which the check must call incorrect.

  python3 bench/control.py --workload <cell> --seed <n> [--seconds 8]

One set-up, then three stretches of the cell's traffic, each followed by the
whole check:
  sound    nothing broken: every compared number inside its limit
  replay   one RPC in 1,000 is applied twice by the door (a hit granted
           twice): counters_below_expected must leave 0
  lost     one RPC in 1,000 is entered in the generator's ledger and never
           reaches the server (an acknowledged hit that is lost):
           counters_above_expected_not_evicted must leave 0; in a leaky
           keyspace (the cell's `keyspace.algorithm`: fill, traffic and
           read-back all take it from there) a key above cannot be told from
           one evicted live, so counters_evicted_in_sample must pass its
           limit, which is 0 where the server counted no live eviction
A GLOBAL keyspace (`keyspace.behavior`) is broken the same way and has to
fail by the same numbers: GLOBAL's contract lets a peer answer from stale
state, not count a hit twice or lose one, and the read-back comes after a
drain (bench/checker.py).
A stretch has to hold its 1,000th RPC or nothing is broken (`rpcs_broken`
0, three verdicts of true, exit 1): a cell that answers under 125 RPC/s needs
more than the default 8 s (the scratch GLOBAL cell, 44 RPC/s on four chips:
--seconds 30).
The benchmark's own runs never come here. One JSON line per stretch; exit
code 0 when the three verdicts are true, false, false.
"""

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import checker  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
from doors import Door  # noqa: E402

EVERY = 1000


class ReplayDoor(Door):
    """While `replaying`, every 1,000th RPC goes out twice; the second
    answer is read and thrown away."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.replaying = False
        self.extra: list = []

    def start(self, body: bytes):
        if self.replaying and self._n % EVERY == EVERY - 1:
            self.extra.append(super().start(body))
        return super().start(body)

    async def drain(self) -> int:
        for call in self.extra:
            await call
        return len(self.extra)


def phantom_rpcs(led: loadgen.Ledger) -> int:
    """Enter every 1,000th answered RPC of `led` a second time: the
    generator now counts hits the server never saw."""
    picks = [i for i in range(EVERY - 1, len(led.idx), EVERY) if led.resp[i] is not None]
    for i in picks:
        for col in (led.idx, led.behavior, led.due, led.sent, led.done, led.resp):
            col.append(col[i])
    checker.settle(led)
    return len(picks)


async def control(workload, seed, seconds, platform="tpu", spec=None):
    spec = spec or harness.load_cell(workload)
    ses = harness.Session(spec, seed, platform, door_cls=ReplayDoor)
    out = []
    try:
        await ses.open()
        for k, fault in enumerate(("sound", "replay", "lost")):
            traffic = loadgen.Traffic(spec["traffic"], ses.keyspec, seed + k, seconds, key_seed=seed)
            traffic.prepare()
            ses.door.replaying = fault == "replay"
            ctx = await ses.offer(traffic, trace=False)
            ses.door.replaying = False
            broken = await ses.door.drain() if fault == "replay" else 0
            if fault == "lost":
                broken = phantom_rpcs(ctx["ledger"])
            verdict = await ses.check()
            row = {"fault": fault, "rpcs_broken": broken, "correct": verdict["correct"],
                   "rpcs": len(ctx["ledger"].idx),
                   "compared": {c["name"]: [c["value"], c["limit"]] for c in verdict["compared"]}}
            out.append(row)
            print(json.dumps(row), flush=True)
    except BaseException:
        await ses.close(failed=True)
        raise
    await ses.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    rows = asyncio.run(control(args.workload, args.seed, args.seconds))
    return 0 if [r["correct"] for r in rows] == [True, False, False] else 1


if __name__ == "__main__":
    sys.exit(main())
