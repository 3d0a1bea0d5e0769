#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: one set-up, then the
cell's traffic at each of several fixed rates for a short window each.

  python3 bench/sweep.py --workload <open-loop cell> --rates 2000,4000,... \\
      [--seconds 8] [--seed n]

The knee is the highest rate at which the backlog does not grow over the
window (RPCs still unanswered as it closes stay at a handful) and no RPC is
refused or fails. The cell's traffic file then fixes four fifths of it as a
number; the benchmark's own runs never search. One JSON line per rate.
"""

import argparse
import asyncio
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import loadgen  # noqa: E402


async def sweep(workload, rates, seconds, seed, platform="tpu", spec=None):
    spec = spec or harness.load_cell(workload)
    ses = harness.Session(spec, seed, platform)
    rows = []
    try:
        await ses.open()
        for k, rate in enumerate(rates):
            tr = copy.deepcopy(spec["traffic"])
            tr["rate_rpc_per_s"] = rate
            traffic = loadgen.Traffic(tr, ses.keyspec, seed + k, seconds, key_seed=seed)
            traffic.prepare()
            ctx = await ses.offer(traffic, trace=False)
            gen = ctx["generator"]
            row = {"rate_rpc_per_s": rate, **harness.end_to_end_values(ctx["ledger"], gen),
                   **{k2: gen[k2] for k2 in (
                       "rpc_p99_ms", "rpcs_due_in_window", "achieved_rpc_per_s", "rpcs_failed_in_window",
                       "rpcs_refused_by_generator", "unanswered_at_window_end",
                       "late_p50_ms", "late_p99_ms", "late_max_ms")}}
            a, b = ctx["pipeline_before"]["engine"], ctx["pipeline_after"]["engine"]
            d = b["dispatches"] - a["dispatches"]
            row["rows_per_dispatch"] = (b["checks"] - a["checks"]) / d if d else None
            rows.append(row)
            print(json.dumps(row), flush=True)
        verdict = await ses.check()
        print(json.dumps({k: verdict[k] for k in ("correct", "compared", "examples")}))
    except BaseException:
        await ses.close(failed=True)
        raise
    await ses.close()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    asyncio.run(sweep(args.workload, rates, args.seconds, args.seed))
    return 0


if __name__ == "__main__":
    sys.exit(main())
