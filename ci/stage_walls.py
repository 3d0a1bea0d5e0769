"""One UNTRACED run of a benchmark cell with the stage histograms read on
either side of its traffic: the walls of `put`, `issue`, `fetch`, ... as they
are when no profiler runs (a traced run inflates every one by 10-20%, and
`bench/run.py` reads the stages in traced runs only).

    cd <a checkout> && python3 <repo>/ci/stage_walls.py --workload <name>
        --seed <n> [--seconds 20] [--tag <name>]

Run from the root of the checkout to measure (`ci/driver_check.py` leaves one
at `--dest`, with the program to measure in place); its `bench/` is the
harness. Two `/metrics` scrapes are added, both outside the window, as a
traced run makes them. One JSON line on stdout and appended to
`chiprun_out/stage_walls.jsonl` of this repo: the end-to-end metrics,
`correct`, per stage the mean in ms and the samples, and per batcher dispatch
the CPU ms of each thread pool. Needs the chip, as `bench/run.py` does.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out", "stage_walls.jsonl")


def _growth(before, after) -> dict:
    """after - before for every number both snapshots hold (one level)."""
    if not isinstance(before, dict) or not isinstance(after, dict):
        return {}
    return {
        k: round(after[k] - before[k], 3) for k in after
        if isinstance(after[k], (int, float)) and not isinstance(after[k], bool)
        and isinstance(before.get(k), (int, float))
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.getcwd(), "bench"))
    import harness

    seen: dict = {}
    offer = harness.Session.offer

    async def offer_between_scrapes(self, traffic, trace):
        async def stages():
            return harness.parse_stages(await self.door.get("/metrics", as_json=False))

        seen["before"] = await stages()
        ctx = await offer(self, traffic, trace)
        seen["after"] = await stages()
        seen["pipeline"] = ctx["pipeline_before"], ctx["pipeline_after"]
        return ctx

    harness.Session.offer = offer_between_scrapes
    t0 = time.monotonic()
    out = asyncio.run(asyncio.wait_for(
        harness.run_cell(args.workload, args.seed, args.seconds, False, t_process0=t0),
        harness.RUN_BUDGET_S,
    ))
    res, ctx = out["result"], out["context"]
    stage_ms = {}
    for stage, (total, count) in sorted(seen["after"].items()):
        s0, c0 = seen["before"].get(stage, (0.0, 0.0))
        if count > c0:
            stage_ms[stage] = [round(1e3 * (total - s0) / (count - c0), 3), int(count - c0)]
    p0, p1 = seen["pipeline"]
    dispatches = p1["batcher"]["dispatches"] - p0["batcher"]["dispatches"]
    cpu = {
        pool: round((t["cpu_ms"] - p0["threads"][pool]["cpu_ms"]) / dispatches, 3)
        for pool, t in p1.get("threads", {}).items()
        if isinstance(t, dict) and "cpu_ms" in t and pool in p0.get("threads", {})
    }
    line = {
        "tag": args.tag, "workload": args.workload, "seed": args.seed,
        "correct": res["correct"], "failed": res["failed"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "healthy_s": ctx["walls_s"]["server_healthy"],
        "dispatches": dispatches, "fused": ctx["dispatches"],
        "native_staged": p1["engine"].get("native_staged"),
        "native_finished": p1["engine"].get("native_finished"),
        "stage_ms": stage_ms, "cpu_ms_per_dispatch": cpu,
        # the growth, over warm-up and window, of the counters of the tier
        # block (a tiered deployment's; absent otherwise), of the engine's
        # and of the process's clocks (`threads`: CPU, collector pauses, wall)
        "tier": _growth(p0.get("tier"), p1.get("tier")),
        "engine": _growth(p0["engine"], p1["engine"]),
        "clocks": _growth(p0.get("threads"), p1.get("threads")),
    }
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
    return 0 if res["correct"] and not res["failed"] else 1


if __name__ == "__main__":
    sys.exit(main())
