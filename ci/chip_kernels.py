"""Run every Pallas kernel in the tree once on the chip, at the 1 GiB table.

chip_smoke.py proves the DEFAULT served path on a TPU. This script is the
record for the rest (ROADMAP D1/D2, S4/S5): each kernel — default or not —
is driven through the engine option that selects it and compared, answer
for answer, with the XLA form (`write="xla"`, `probe="xla"`, `wire="full"`)
on the same traffic. A kernel the compiler refuses is recorded with the
first line of its message; refusal is an outcome here, not an error, because
this script is the one place that is allowed to catch it (the product never
does — a refused kernel raises when selected).

One process, holds the chip, fails when JAX finds no TPU. Prints one JSON
line {"device": ..., "kernels": {name: {"outcome": "matches"|"refused"|
"MISMATCH", ...}}} and writes the same to chiprun_out/chip_kernels.json.
Exit code 1 only on a MISMATCH (a kernel that lowered and answered wrong).

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

from gubernator_tpu.ops.batch import RequestColumns, pack_columns, pad_batch  # noqa: E402
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
    ref = LocalEngine(
        capacity=CAPACITY, write_mode="xla", wire="full", probe="xla",
        walk="xla",
    )
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


def fused_drain_case() -> dict:
    """ops/ring_drain.DeviceRing.drain (one while_loop launch over K slots)
    against K direct compact-wire dispatches."""
    from gubernator_tpu.ops import wire
    from gubernator_tpu.ops.ring_drain import DeviceRing

    S, W, K = 16, 4096, 8
    rng = np.random.default_rng(11)
    eng = LocalEngine(capacity=CAPACITY, wire="compact")
    ref = LocalEngine(capacity=CAPACITY, wire="compact")
    ring = DeviceRing(S, W, K)
    grids = []
    for t in range(K):
        hb, _ = pack_columns(traffic(rng, W - 96, "token", np.zeros(0, np.int64)), NOW)
        grid = wire.pack_wire_full(pad_batch(hb, W), NOW)
        ring.stage(t % S, grid, t)
        grids.append(grid)
    bank, n = ring.drain(eng, 0, K, "token", False)
    bank = np.asarray(bank)
    if int(n) != K:
        return {"outcome": "MISMATCH", "drained": int(n), "published": K}
    for t, grid in enumerate(grids):
        ref.table, out = wire.decide2_wire_cols(
            ref.table, jax.device_put(grid), write=ref.write_mode,
            math="token", cascade=False, probe="xla", evictees=False,
        )
        if not np.array_equal(bank[t], np.asarray(out)):
            return {"outcome": "MISMATCH", "slot": t}
    return {"outcome": "matches", "rows": K * W, "slots": K}


def fence_claim_case() -> dict:
    from gubernator_tpu.ops.ring_drain import fence_claim_ref, make_fence_claim

    S, W, K = 16, 4096, 8
    rng = np.random.default_rng(13)
    grids = rng.integers(0, 1 << 30, size=(S, 5, W + 1)).astype(np.int32)
    seq_in = np.zeros(S, np.int32)
    seq_in[:5] = np.arange(1, 6)
    seq_out = np.zeros(S, np.int32)
    so, bank, n = make_fence_claim(S, W, K)(
        jnp.asarray(seq_in), jnp.asarray(seq_out), jnp.asarray(grids),
        jnp.asarray([0, K], dtype=jnp.int32),
    )
    n_ref, bank_ref, so_ref = fence_claim_ref(seq_in, seq_out, grids, 0, K)
    ok = (
        int(n[0]) == n_ref and np.array_equal(np.asarray(so), so_ref)
        and np.array_equal(np.asarray(bank)[:n_ref], bank_ref)
    )
    return {"outcome": "matches" if ok else "MISMATCH", "rows": n_ref * W}


def ring_exchange_case() -> dict:
    """parallel/ring._ring_pallas (remote-DMA hops) against lax.all_to_all,
    over the int64 (D, 12, C) blocks parallel/a2a.py really sends."""
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.ring import make_exchange_probe

    D = len(jax.devices())
    if D < 2:
        return {"outcome": "not run", "why": "needs at least two chips"}
    mesh = make_mesh(D)
    shape = (D, 12, 512)
    x = jax.device_put(
        np.random.default_rng(17).integers(0, 1 << 62, size=(D,) + shape),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("shard")),
    )
    want = np.asarray(make_exchange_probe(mesh, shape, "collective", dtype=jnp.int64)(x))
    got = np.asarray(make_exchange_probe(mesh, shape, "ring", dtype=jnp.int64)(x))
    ok = np.array_equal(got, want)
    return {"outcome": "matches" if ok else "MISMATCH", "rows": int(D * D * 512)}


def walk_case() -> dict:
    rng = np.random.default_rng(19)
    n = 4096
    kw = dict(
        fp=rng.integers(1, 1 << 62, size=n, dtype=np.int64),
        algo=np.zeros(n, np.int32), status=np.zeros(n, np.int32),
        limit=np.full(n, 10, np.int64), remaining=np.full(n, 4, np.int64),
        reset_time=np.full(n, NOW + 60_000, np.int64),
        duration=np.full(n, 60_000, np.int64), now_ms=NOW,
    )
    eng = LocalEngine(capacity=CAPACITY, walk="pallas")
    ref = LocalEngine(capacity=CAPACITY, walk="xla", write_mode="xla")
    a, b = eng.install_columns(**kw), ref.install_columns(**kw)
    ok = a == b and bool(jnp.array_equal(eng.table.rows, ref.table.rows))
    return {"outcome": "matches" if ok else "MISMATCH", "rows": n}


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
    # ---- off by default
    "probe megakernel (GUBER_PROBE_KERNEL=pallas)": lambda: engine_case(
        {"probe": "pallas"}, "token", [4096, 4096]),
    "fused install/merge walk (GUBER_WALK_KERNEL=pallas)": walk_case,
    "fused ring drain (GUBER_RING_ISSUE=fused)": fused_drain_case,
    "fence-claim kernel (GUBER_RING_ISSUE=persistent, staged)": fence_claim_case,
    "ring exchange _ring_pallas (GUBER_A2A_IMPL=ring)": ring_exchange_case,
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
        r["outcome"] == "MISMATCH" for r in record["kernels"].values()
    ) else 0


if __name__ == "__main__":
    sys.exit(main())
