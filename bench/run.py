#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU: the server child is started with JAX_PLATFORMS=tpu and nothing
falls back to the CPU; with no chip, or fewer than the cell asks for, the
run exits non-zero and prints no result. Stdout ends with two JSON lines:
the context (resolved selectors, counts, every number compared beside its
limit, walls) and, last, the contract's object and nothing else.
"""

import time

T_PROCESS0 = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from doors import BenchFailure  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = asyncio.run(asyncio.wait_for(
            harness.run_cell(
                args.workload, args.seed, args.seconds, bool(args.trace),
                t_process0=T_PROCESS0,
            ),
            harness.RUN_BUDGET_S,
        ))
    except asyncio.TimeoutError:
        print(f"bench: FAILED: not done after {harness.RUN_BUDGET_S:.0f} s", file=sys.stderr)
        return 1
    except (BenchFailure, OSError, ValueError, KeyError) as exc:
        print(f"bench: FAILED: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(out["context"]))
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
