"""Run every Pallas kernel in the tree once on the chip, at the 1 GiB table.

chip_smoke.py proves the DEFAULT served path on a TPU. This script is the
record for the rest (ROADMAP D2, S4/S5): each kernel — default or not —
is driven through the engine option that selects it and compared, answer
for answer, with the XLA form (`write="xla"`, `wire="full"`) on the same
traffic. Every kernel listed lowers on a v5e; one the compiler refuses is
recorded with the first line of its message (this script is the one place
that catches it — the product never does) and fails the run.

One process, holds the chip, fails when JAX finds no TPU. Prints one JSON
line {"device": ..., "kernels": {name: {"outcome": "matches"|"refused"|
"MISMATCH", ...}}} and writes the same to chiprun_out/chip_kernels.json.
Exit code 1 on a MISMATCH (a kernel that lowered and answered wrong) or a
refusal.

    python ci/chip_kernels.py            # on the chip
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import gubernator_tpu  # noqa: F401,E402  (x64 on, compile cache placed)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from gubernator_tpu.ops.batch import RequestColumns  # noqa: E402
from gubernator_tpu.ops.engine import LocalEngine  # noqa: E402

CAPACITY = 16_777_216  # 1 GiB full layout, 512 MiB packed
NOW = 1_790_000_000_000
RESET, DRAIN = 8, 32


def traffic(rng, n: int, mode: str, pool: np.ndarray) -> RequestColumns:
    """n distinct keys: half fresh, half drawn from `pool` (already-seen
    keys, so existing-item branches run). mode: token | mixed | gcra."""
    fresh = rng.integers(1, 1 << 62, size=n - n // 2, dtype=np.int64)
    old = rng.choice(pool, size=n // 2, replace=False) if pool.size >= n else fresh[: n // 2] + 1
    fp = np.concatenate([fresh, old])
    if mode == "token":
        algo = np.zeros(n, np.int32)
        beh = np.zeros(n, np.int32)
    elif mode == "gcra":
        algo = np.full(n, 2, np.int32)
        beh = np.zeros(n, np.int32)
    else:
        algo = (fp & 1).astype(np.int32)  # per-key stable: token or leaky
        beh = rng.choice(
            np.array([0, 0, 0, RESET, DRAIN], np.int32), size=n
        )
    return RequestColumns(
        fp=fp, algo=algo, behavior=beh,
        hits=rng.integers(0, 4, size=n).astype(np.int64),
        limit=np.full(n, 10, np.int64), burst=np.zeros(n, np.int64),
        duration=np.full(n, 600_000, np.int64),
        created_at=np.zeros(n, np.int64), err=np.zeros(n, np.int8),
    )


def same(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def engine_case(kw: dict, mode: str, batches) -> dict:
    """Drive the engine under test and the XLA reference with the same
    dispatches; every response column must be equal."""
    rng = np.random.default_rng(7)
    eng = LocalEngine(capacity=CAPACITY, **kw)
    ref = LocalEngine(capacity=CAPACITY, write_mode="xla", wire="full")
    pool = np.zeros(0, np.int64)
    rows = 0
    for i, n in enumerate(batches):
        cols = traffic(rng, n, mode, pool)
        now = NOW + 1000 * i
        got = eng.check_columns(cols, now_ms=now)
        want = ref.check_columns(cols, now_ms=now)
        if not same(got, want):
            bad = int(sum((np.asarray(x) != np.asarray(y)).sum() for x, y in zip(got, want)))
            return {"outcome": "MISMATCH", "dispatch": i, "rows": n, "cells_differ": bad}
        pool = np.concatenate([pool, cols.fp])
        rows += n
    out = {"outcome": "matches", "rows": rows, "dispatches": len(batches)}
    if eng.table.layout is ref.table.layout:
        out["tables_equal"] = bool(jnp.array_equal(eng.table.rows, ref.table.rows))
        if not out["tables_equal"]:
            out["outcome"] = "MISMATCH"
    return out


CASES = {
    # ---- on the default served path
    "write_sparse/full/blk64 (default ≤4K rows)": lambda: engine_case(
        {"write_mode": "sparse", "wire": "compact"}, "mixed", [4096, 4096]),
    "write_sweep/full (default ≥8K rows)": lambda: engine_case(
        {"write_mode": "sparse", "wire": "compact"}, "token", [16384, 16384]),
    "wire_compact decode/encode": lambda: engine_case(
        {"write_mode": "xla", "wire": "compact"}, "mixed", [4096, 4096]),
    # ---- packed 32 B layouts (GUBER_SLOT_LAYOUT): 64-lane rows, fl=8
    "write_sparse/token32": lambda: engine_case(
        {"write_mode": "sparse", "layout": "token32"}, "token", [4096, 4096]),
    "write_sweep/token32": lambda: engine_case(
        {"write_mode": "sweep", "layout": "token32"}, "token", [16384, 16384]),
    "write_sparse/gcra32": lambda: engine_case(
        {"write_mode": "sparse", "layout": "gcra32"}, "gcra", [4096, 4096]),
}


def main() -> int:
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_kernels: no TPU (JAX reports {dev[0].platform})", file=sys.stderr)
        return 2
    record = {
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind,
                   "count": len(dev)},
        "jax": jax.__version__, "kernels": {},
    }
    only = sys.argv[1:]
    for name, case in CASES.items():
        if only and not any(o in name for o in only):
            continue
        t0 = time.monotonic()
        try:
            res = case()
        except Exception as exc:  # the record of a refusal — see docstring
            lines = [ln for ln in str(exc).splitlines() if ln.strip()]
            res = {"outcome": "refused", "error": type(exc).__name__,
                   "message": (lines[0] if lines else "")[:400]}
        res["wall_s"] = round(time.monotonic() - t0, 1)
        record["kernels"][name] = res
        print(f"{name}: {res}", file=sys.stderr, flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_kernels.json"), "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))
    return 1 if any(
        r["outcome"] != "matches" for r in record["kernels"].values()
    ) else 0


if __name__ == "__main__":
    sys.exit(main())
