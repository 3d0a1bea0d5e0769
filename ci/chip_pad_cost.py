"""What one decide pass costs on the device by padded batch size and table
size, and what `kernel2.resolve_write` chose for it against the other write.

    chiprun --chips 1 --timeout 900 -- python3 ci/chip_pad_cost.py

The benchmark's trace gives a mean over a mix of pads (`decide_device_ms`);
this times each pad alone: the token program of the compact wire
(`decide2_wire_cols`, what cells 1 and 6 run), uniform random keys so that
every row lands in another bucket, on an empty table of 16,777,216 slots
(1 GiB) and of 134,217,728 (8 GiB), launch to `block_until_ready`, the median
of `--reps` passes after one that compiles. For pads of 4,096 rows and up
the write `resolve_write` did not choose is timed too (`--both`), which is
where the crossover between `sparse` and `sweep` can be read. One JSON line
a (slots, pad, write), the device's kind in it, to stdout and to
`chiprun_out/chip_pad_cost.jsonl`; exit 2 where JAX reports no TPU.
Host-clock times of a device program with nothing else queued: good to the
launch's ≈0.05 ms, not a trace.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gubernator_tpu.ops.batch import HostBatch  # noqa: E402
from gubernator_tpu.ops.engine import LocalEngine  # noqa: E402
from gubernator_tpu.ops.kernel2 import resolve_write  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")
NOW = 1_700_000_000_000
LIMIT, DURATION = 100, 3_600_000


def batch(rng, pad: int) -> HostBatch:
    """`pad` live token rows with keys nobody has sent before."""
    full = lambda v: np.full(pad, v, dtype=np.int64)
    return HostBatch(
        fp=rng.integers(1, 2**62, size=pad, dtype=np.int64),
        algo=np.zeros(pad, dtype=np.int32), behavior=np.zeros(pad, dtype=np.int32),
        hits=full(1), limit=full(LIMIT), burst=full(0), duration=full(DURATION),
        created_at=full(NOW), expire_new=full(NOW + DURATION), greg_interval=full(0),
        duration_eff=full(DURATION), active=np.ones(pad, dtype=bool),
    )


def time_pad(eng: LocalEngine, rng, pad: int, reps: int) -> list:
    ms = []
    for i in range(reps + 1):
        dev, wired = eng._stage_ingress(batch(rng, pad))
        assert wired, "the batch left the compact wire"
        dev.block_until_ready()
        t0 = time.perf_counter()
        eng._issue_from_dev(dev, pad, "token", wired).block_until_ready()
        if i:  # the first pass compiles
            ms.append(1e3 * (time.perf_counter() - t0))
    return ms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, nargs="+", default=[1 << 24, 1 << 27])
    ap.add_argument("--pads", type=int, nargs="+", default=[1 << i for i in range(10, 16)])
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--both", type=int, default=4096, help="time both writes from this pad up")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_pad_cost: no TPU here (JAX reports {dev.platform}); "
              "a pass timed elsewhere is no chip number", file=sys.stderr)
        return 2
    os.makedirs(OUT, exist_ok=True)
    rng = np.random.default_rng(40)
    with open(os.path.join(OUT, "chip_pad_cost.jsonl"), "a") as f:
        for slots in args.slots:
            eng = LocalEngine(capacity=slots, wire="compact")
            nb = eng.table.n_buckets
            for pad in args.pads:
                os.environ.pop("GUBER_WRITE_SPARSE_CROSSOVER", None)
                chosen = resolve_write("sparse", nb, pad)  # the rule as deployed
                for write in ("sparse", "sweep") if pad >= args.both else (chosen,):
                    # the knob at 0 while a program is traced makes "sparse"
                    # the sparse grid at any coverage
                    os.environ["GUBER_WRITE_SPARSE_CROSSOVER"] = "0"
                    eng.write_mode = write
                    ms = time_pad(eng, rng, pad, args.reps)
                    rec = {"device_kind": dev.device_kind, "slots": slots, "table_bytes": int(eng.table.rows.nbytes), "pad": pad,
                           "write": write, "chosen": write == chosen,
                           "ms_median": statistics.median(ms), "ms_min": min(ms), "ms_max": max(ms),
                           "us_per_row": 1e3 * statistics.median(ms) / pad}
                    line = json.dumps(rec)
                    print(line, flush=True)
                    f.write(line + "\n")
                    f.flush()
            del eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
